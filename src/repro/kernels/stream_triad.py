"""stream_triad — STREAM triad ``a = b + s*c`` with large-granularity
asynchronous block transfers.

The paper's STREAM port issues 512B+ aloads; on TPU the analogous structure
is the Pallas grid pipeline: each grid step's BlockSpec block is fetched
HBM->VMEM by an async DMA issued ahead of use (double buffering), i.e. the
compiler-managed version of the AMU slot ring. Block size = the `aload`
granularity; the pipeline depth plays the role of `queue_length`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# 1024 rows of 128 lanes: a whole number of (8, 128) f32, (16, 128) bf16 and
# (32, 128) int8 tiles, and 512 KiB of f32 per operand block.
DEFAULT_BLOCK = 1024 * 128


def _triad_kernel(s_ref, b_ref, c_ref, a_ref):
    a_ref[...] = b_ref[...] + s_ref[0] * c_ref[...]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def stream_triad(b: jnp.ndarray, c: jnp.ndarray, s: float,
                 block: int = DEFAULT_BLOCK,
                 interpret: bool = False) -> jnp.ndarray:
    """b, c: [N] (N % block == 0) -> a = b + s*c, streamed block by block."""
    (N,) = b.shape
    assert N % block == 0, (N, block)
    lanes = 128
    rows = block // lanes
    assert block % lanes == 0
    b2 = b.reshape(N // lanes, lanes)
    c2 = c.reshape(N // lanes, lanes)
    sv = jnp.array([s], b.dtype)
    out = pl.pallas_call(
        _triad_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N // block,),
            in_specs=[pl.BlockSpec((rows, lanes), lambda i, s_: (i, 0)),
                      pl.BlockSpec((rows, lanes), lambda i, s_: (i, 0))],
            out_specs=pl.BlockSpec((rows, lanes), lambda i, s_: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((N // lanes, lanes), b.dtype),
        interpret=interpret,
    )(sv, b2, c2)
    return out.reshape(N)
