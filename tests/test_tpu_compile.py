"""Compile the main-path Pallas kernels at real widths for a described TPU
v5e, without the chip: the TPU compiler refuses what interpret mode accepts
(blocks off the (8, 128) tiling, unaligned DMA slices). Nothing runs, so
these tests say nothing about results or speed; tests/test_kernels.py checks
results in interpret mode.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU compiler's library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.async_gather import async_gather, row_shape
from repro.kernels.async_scatter import async_scatter
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.stream_triad import stream_triad

VOCAB, D_MODEL = 151936, 2048      # qwen2.5-3b embedding table
ROWS = 1024                        # indices per gather / scatter call


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *specs) -> str:
    hlo = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


# every decoder config whose decode reaches the kernel, and plain MHA
@pytest.mark.parametrize("hq,hkv,d", [
    (16, 2, 128),     # qwen2.5-3b
    (16, 8, 64),      # granite-moe-1b-a400m
    (4, 4, 128),      # MHA
    (64, 8, 128),     # kimi-k2-1t-a32b
    (40, 8, 128),     # qwen2.5-32b
    (28, 4, 128),     # qwen2-7b
    (24, 8, 128),     # phi4-mini-3.8b
    (12, 2, 128),     # qwen2-vl-2b
])
def test_paged_attention_compiles(one_chip, hq, hkv, d):
    B, T = 8, 1024
    q = jax.ShapeDtypeStruct((B, hq, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, T, hkv, d), jnp.bfloat16, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    _compile(lambda q, k, v, n: paged_attention(q, k, v, n, page=512),
             q, kv, kv, lens)


@pytest.mark.parametrize("hq,hkv,d,causal", [(16, 2, 128, True),
                                             (16, 8, 64, True),
                                             (16, 16, 80, False)])
def test_flash_attention_compiles(one_chip, hq, hkv, d, causal):
    B, S = 2, 1024
    q = jax.ShapeDtypeStruct((B, hq, S, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, hkv, S, d), jnp.bfloat16, sharding=one_chip)
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=causal),
             q, kv, kv)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_async_gather_compiles(one_chip, dtype):
    table = jax.ShapeDtypeStruct((VOCAB,) + row_shape(D_MODEL), dtype,
                                 sharding=one_chip)
    idx = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    _compile(lambda t, i: async_gather(t, i), table, idx)


@pytest.mark.parametrize("dtype,op", [(jnp.bfloat16, "add"),
                                      (jnp.float32, "add"),
                                      (jnp.int32, "xor")])
def test_async_scatter_compiles(one_chip, dtype, op):
    tiles = row_shape(D_MODEL)
    table = jax.ShapeDtypeStruct((VOCAB,) + tiles, dtype, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    upd = jax.ShapeDtypeStruct((ROWS,) + tiles, dtype, sharding=one_chip)
    _compile(lambda t, i, u: async_scatter(t, i, u, op=op), table, idx, upd)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_stream_triad_compiles(one_chip, dtype):
    x = jax.ShapeDtypeStruct((1 << 24,), dtype, sharding=one_chip)
    _compile(lambda b, c: stream_triad(b, c, 3.0), x, x)
