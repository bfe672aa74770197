"""async_scatter — GUPS-update / embedding-grad: read-modify-write rows of an
HBM table through a VMEM slot ring, with CAM-free software disambiguation.

Per update j (paper Fig 4 + §5.1, on TPU):

  1. slot reuse  -> wait the store that last used slot ``j mod K``
                    (drain watermark, the "free list");
  2. conflict    -> compare ``idx[j]`` against the K-1 in-flight store
                    indices (a register ring, not a CAM — §5.1's "only
                    active locations matter"); on a hit, drain stores up to
                    the conflicting one so the aload sees fresh data;
  3. aload       -> async copy ``table[idx[j]] -> slot``;
  4. modify      -> ``slot += update[j]`` (or xor);
  5. astore      -> async copy ``slot -> table[idx[j]]``, retire immediately.

Loads are issued K ahead of use; stores drain lazily. The watermark (kept in
SMEM) guarantees each store semaphore is waited exactly once.

The table is held as ``[N, R, L]`` row tiles, as in ``async_gather``, so
each row load and store is one DMA aligned to the HBM tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scatter_kernel(idx_ref, upd_ref, table_in_ref, out_ref, slots_ld,
                    slots_st, load_sems, store_sems, wm_ref, *,
                    block_m: int, num_slots: int, op: str):
    base = pl.program_id(0) * block_m
    K = num_slots
    del table_in_ref  # aliased with out_ref; all access goes through out_ref

    def load_dma(j):
        return pltpu.make_async_copy(out_ref.at[idx_ref[base + j]],
                                     slots_ld.at[j % K], load_sems.at[j % K])

    def store_dma(j):
        return pltpu.make_async_copy(slots_st.at[j % K],
                                     out_ref.at[idx_ref[base + j]],
                                     store_sems.at[j % K])

    def drain_to(j_req):
        """Wait every store with index in (watermark, j_req]."""
        def wait_one(t, _):
            store_dma(t).wait()
            return 0
        wm = wm_ref[0]
        jax.lax.fori_loop(wm + 1, j_req + 1, wait_one, 0)
        wm_ref[0] = jnp.maximum(wm, j_req)

    wm_ref[0] = jnp.int32(-1)

    def prime(j, _):
        load_dma(j).start()
        return 0
    jax.lax.fori_loop(0, min(K, block_m), prime, 0)

    def body(j, _):
        slot = j % K
        load_dma(j).wait()
        # CAM-free software disambiguation (§5.1) at consume time: if any
        # store in (watermark, j) targets this row, the speculative aload
        # read stale data -> drain to the youngest conflicting store and
        # re-load synchronously. Conflicts are rare (the paper's premise),
        # so the common path stays fully pipelined.
        my_row = idx_ref[base + j]

        def scan(t, acc):
            hit = idx_ref[base + t] == my_row
            return jnp.where(hit, jnp.maximum(acc, t), acc)
        # candidates: stores that may not have completed before THIS load was
        # issued (load j issues at step j-K; by then stores <= j-2K had been
        # drained) -> scan the last 2K-1 indices, not from the watermark.
        h = jax.lax.fori_loop(jnp.maximum(0, j - 2 * K + 1), j, scan,
                              jnp.int32(-1))

        @pl.when(h >= 0)
        def _():
            drain_to(h)
            load_dma(j).start()
            load_dma(j).wait()
        # store-slot reuse: the store that used this slot (j-K) must be done
        @pl.when(j >= K)
        def _():
            drain_to(j - K)
        if op == "add":
            slots_st[slot] = slots_ld[slot] + upd_ref[j]
        else:  # xor
            slots_st[slot] = slots_ld[slot] ^ upd_ref[j]
        store_dma(j).start()

        @pl.when(j + K < block_m)
        def _():
            load_dma(j + K).start()
        return 0

    jax.lax.fori_loop(0, block_m, body, 0)
    drain_to(block_m - 1)         # retire everything before the block ends


@functools.partial(jax.jit, static_argnames=("block_m", "num_slots", "op",
                                             "interpret"))
def async_scatter(table: jnp.ndarray, indices: jnp.ndarray,
                  updates: jnp.ndarray, op: str = "add",
                  block_m: int = 256, num_slots: int = 8,
                  interpret: bool = False) -> jnp.ndarray:
    """Returns table with rows RMW-updated: table[idx[j]] op= updates[j].
    table: [N, R, L] row tiles; updates: [M, R, L]."""
    M = indices.shape[0]
    _, R, L = table.shape
    assert M % block_m == 0, (M, block_m)
    assert updates.shape == (M, R, L)
    grid = (M // block_m,)
    kernel = functools.partial(_scatter_kernel, block_m=block_m,
                               num_slots=num_slots, op=op)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, R, L),                    # updates
                             lambda i, idx: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),               # table
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((num_slots, R, L), table.dtype),
                pltpu.VMEM((num_slots, R, L), table.dtype),
                pltpu.SemaphoreType.DMA((num_slots,)),
                pltpu.SemaphoreType.DMA((num_slots,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(indices, updates, table)
