"""Public jit'd wrappers around the Pallas kernels: layout/padding glue and
backend dispatch (interpret mode on the CPU, compiled on a TPU, an error on
any other backend).

The attention wrappers' bodies run under `jax.named_scope(<kernel>)`
(`repro.scopes`), so the compiled step programs name the kernel's glue and
call by it. The model layer (`repro.models.blocks`) calls these when
`use_kernels=True`;
the multi-pod dry-run lowers the pure-jnp reference path instead (Pallas
interpret mode does not compose with SPMD partitioning on the CPU backend),
so the kernels are validated standalone against ref.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import scopes
from repro.kernels import ref
from repro.kernels.async_gather import async_gather as _gather, row_shape
from repro.kernels.async_scatter import async_scatter as _scatter
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.stream_triad import DEFAULT_BLOCK
from repro.kernels.stream_triad import stream_triad as _triad


def _interpret() -> bool:
    """Interpret on the CPU, compile on a TPU. Any other backend is an
    error: a kernel never falls back quietly."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels run on 'tpu' (compiled) or 'cpu' "
                           f"(interpreted), not on {backend!r}")
    return backend == "cpu"


def row_tiles(x: jnp.ndarray) -> jnp.ndarray:
    """[N, D] -> [N, R, L] row tiles, the table layout of the gather and
    scatter kernels. On a TPU this view copies `x`: a table that is gathered
    from often is best kept as row tiles."""
    return x.reshape((x.shape[0],) + row_shape(x.shape[1]))


def _pad_to(x: jnp.ndarray, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def gather(table: jnp.ndarray, indices: jnp.ndarray,
           block_m: int = 256, num_slots: int = 8) -> jnp.ndarray:
    """Embedding/GUPS gather: out[i] = table[indices[i]]. `table` is
    [N, R, L] row tiles, or [N, D] (viewed through `row_tiles`)."""
    tiles = table if table.ndim == 3 else row_tiles(table)
    idx_p, m = _pad_to(indices.astype(jnp.int32), 0, block_m)
    out = _gather(tiles, idx_p, block_m=block_m, num_slots=num_slots,
                  interpret=_interpret())
    return out[:m].reshape((m,) + table.shape[1:])


def scatter_update(table: jnp.ndarray, indices: jnp.ndarray,
                   updates: jnp.ndarray, op: str = "add",
                   block_m: int = 256, num_slots: int = 8) -> jnp.ndarray:
    """RMW scatter: table[idx[j]] op= updates[j], for a table as in
    `gather`. Padding applies zero updates to row 0, which add and xor
    leave unchanged."""
    tiles = table if table.ndim == 3 else row_tiles(table)
    upd = updates.reshape((updates.shape[0],) + tiles.shape[1:])
    idx_p, _ = _pad_to(indices.astype(jnp.int32), 0, block_m)
    upd_p, _ = _pad_to(upd, 0, block_m)
    out = _scatter(tiles, idx_p, upd_p, op=op, block_m=block_m,
                   num_slots=num_slots, interpret=_interpret())
    return out.reshape(table.shape)


def triad(b: jnp.ndarray, c: jnp.ndarray, s: float,
          block: int = DEFAULT_BLOCK) -> jnp.ndarray:
    bp, n = _pad_to(b, 0, block)
    cp, _ = _pad_to(c, 0, block)
    return _triad(bp, cp, s, block=block, interpret=_interpret())[:n]


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128) -> jnp.ndarray:
    """Model-layer layout: q [B, S, Hq, D], k/v [B, S, Hkv, D] ->
    [B, S, Hq, D]. Pads S to the block size (extra keys are masked by
    causality; extra query rows are sliced off)."""
    with jax.named_scope(scopes.FLASH_ATTENTION):
        Bq = jnp.swapaxes(q, 1, 2)          # [B, Hq, S, D]
        Bk = jnp.swapaxes(k, 1, 2)
        Bv = jnp.swapaxes(v, 1, 2)
        S = Bq.shape[2]
        blk = min(block_q, block_k)
        Bq, _ = _pad_to(Bq, 2, blk)
        Bk, _ = _pad_to(Bk, 2, blk)
        Bv, _ = _pad_to(Bv, 2, blk)
        out = _flash(Bq, Bk, Bv, causal=causal, window=window,
                     block_q=min(block_q, Bq.shape[2]),
                     block_k=min(block_k, Bk.shape[2]),
                     interpret=_interpret())
        return jnp.swapaxes(out[:, :, :S], 1, 2)


def paged_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                    v_cache: jnp.ndarray, lengths: jnp.ndarray,
                    page: int = 512) -> jnp.ndarray:
    """Decode attention. q: [B, Hq, D]; caches [B, T, Hkv, D]; lengths [B]."""
    with jax.named_scope(scopes.PAGED_ATTENTION):
        kp, _ = _pad_to(k_cache, 1, page)
        vp, _ = _pad_to(v_cache, 1, page)
        return _paged(q, kp, vp, lengths.astype(jnp.int32), page=page,
                      interpret=_interpret())


__all__ = ["gather", "scatter_update", "triad", "flash_attention",
           "paged_attention", "ref"]
