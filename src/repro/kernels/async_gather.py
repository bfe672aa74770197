"""async_gather — the AMU mechanism as a TPU kernel.

GUPS-gather / embedding-lookup: ``out[i] = table[idx[i]]`` where `table`
lives in HBM ("far memory" relative to VMEM) and rows are random.

This is a direct transcription of the paper's AMI pipeline:

* ``aload``   -> ``pltpu.make_async_copy(table[row], slot[j % K]).start()``
                 issued K rows ahead (request issuing decoupled from use);
* SPM         -> a VMEM slot ring (``K`` slots x row bytes), the repurposed
                 scratch the paper carves out of L2;
* ``getfin``  -> ``.wait()`` on the slot's DMA semaphore right before the
                 row is consumed (completion decoupled from issue);
* request IDs -> slot index ``j mod K``; the free list/finished list
                 degenerate to the ring order because TPU DMAs complete
                 in-order per (src, dst, sem) triple.

K is sized by the latency-bandwidth product (``K * row_bytes >=
HBM_latency * HBM_bw``), exactly the paper's "queue_length follows demand"
rule. The grid is over index blocks so the scalar indices arrive via
scalar prefetch (SMEM) before the block body runs.

Layout: the table is held as ``[N, R, L]`` row tiles (``row_shape``). HBM
tiles the last two dims in (8, 128) blocks, so a one-row slice of a plain
``[N, D]`` table is not aligned to the tiling and the TPU compiler refuses
the DMA. The leading dim of ``[N, R, L]`` is untiled, so one row is one
aligned DMA at any dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def row_shape(d: int) -> tuple:
    """Tile shape ``(R, L)`` of one row of width `d`: whole 128-lane tiles
    when `d` allows it, else the row as a single tile."""
    return (d // LANES, LANES) if d % LANES == 0 else (1, d)


def _gather_kernel(idx_ref, table_ref, out_ref, slots, sems, *,
                   block_m: int, num_slots: int):
    """One grid step gathers `block_m` rows through a `num_slots`-deep ring.

    idx_ref: SMEM [M] (scalar-prefetched); table_ref: ANY [N, R, L];
    out_ref: VMEM [block_m, R, L]; slots: VMEM [num_slots, R, L];
    sems: DMA [K].
    """
    base = pl.program_id(0) * block_m

    def dma(j, slot):
        return pltpu.make_async_copy(table_ref.at[idx_ref[base + j]],
                                     slots.at[slot], sems.at[slot])

    # prime the ring: issue the first K aloads back-to-back (MLP!)
    def prime(j, _):
        dma(j, j % num_slots).start()
        return 0
    jax.lax.fori_loop(0, min(num_slots, block_m), prime, 0)

    def body(j, _):
        slot = j % num_slots
        dma(j, slot).wait()                    # getfin for this slot
        out_ref[j] = slots[slot]

        @pl.when(j + num_slots < block_m)
        def _():                               # reuse the freed slot
            dma(j + num_slots, slot).start()
        return 0

    jax.lax.fori_loop(0, block_m, body, 0)


@functools.partial(jax.jit, static_argnames=("block_m", "num_slots",
                                             "interpret"))
def async_gather(table: jnp.ndarray, indices: jnp.ndarray,
                 block_m: int = 256, num_slots: int = 8,
                 interpret: bool = False) -> jnp.ndarray:
    """out[i] = table[indices[i]]; table: [N, R, L] row tiles, indices: [M]
    int32 -> [M, R, L].

    M must be a multiple of block_m (ops.py pads).
    """
    M = indices.shape[0]
    _, R, L = table.shape
    assert M % block_m == 0, (M, block_m)
    grid = (M // block_m,)
    kernel = functools.partial(_gather_kernel, block_m=block_m,
                               num_slots=num_slots)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block_m, R, L), lambda i, idx: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((num_slots, R, L), table.dtype),
                pltpu.SemaphoreType.DMA((num_slots,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((M, R, L), table.dtype),
        interpret=interpret,
    )(indices, table)
