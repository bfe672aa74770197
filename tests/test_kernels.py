"""Per-kernel correctness: shape/dtype sweeps against the ref.py oracles,
all in interpret mode (CPU validates the TPU kernel bodies). The gather and
scatter kernels take their tables as [N, R, L] row tiles (`ops.row_tiles`).
tests/test_tpu_compile.py compiles the same kernels for a described TPU."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.async_gather import async_gather
from repro.kernels.async_scatter import async_scatter
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.stream_triad import stream_triad

RNG = np.random.default_rng(0)


# ------------------------------------------------------------- async_gather
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("n,d,m,bm,k", [
    (64, 128, 256, 128, 8),
    (512, 256, 128, 64, 4),
    (33, 128, 64, 32, 2),
    (1024, 512, 512, 256, 16),
])
def test_async_gather(n, d, m, bm, k, dtype):
    if dtype == jnp.int32:
        table = jnp.array(RNG.integers(0, 1 << 20, (n, d)), dtype)
    else:
        table = jnp.array(RNG.standard_normal((n, d)), dtype)
    idx = jnp.array(RNG.integers(0, n, m), jnp.int32)
    out = async_gather(ops.row_tiles(table), idx, block_m=bm, num_slots=k,
                       interpret=True)
    np.testing.assert_array_equal(np.asarray(out).reshape(m, d),
                                  np.asarray(ref.gather_ref(table, idx)))


# ------------------------------------------------------------ async_scatter
@pytest.mark.parametrize("n,d,m,bm,k", [
    (64, 128, 256, 128, 8),   # heavy conflicts
    (8, 128, 64, 32, 4),      # extreme conflicts
    (1024, 256, 128, 128, 8), # sparse
    (16, 8, 128, 64, 8),
])
def test_async_scatter_add(n, d, m, bm, k):
    table = jnp.array(RNG.standard_normal((n, d)), jnp.float32)
    idx = jnp.array(RNG.integers(0, n, m), jnp.int32)
    upd = jnp.array(RNG.standard_normal((m, d)), jnp.float32)
    out = async_scatter(ops.row_tiles(table), idx, ops.row_tiles(upd),
                        op="add", block_m=bm, num_slots=k, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out).reshape(n, d),
        np.asarray(ref.scatter_update_ref(table, idx, upd, "add")),
        atol=1e-4, rtol=1e-4)


def test_async_scatter_xor_gups():
    """GUPS semantics: integer xor RMW with many conflicts."""
    n, d, m = 32, 8, 256
    table = jnp.array(RNG.integers(0, 1 << 30, (n, d)), jnp.int32)
    idx = jnp.array(RNG.integers(0, n, m), jnp.int32)
    upd = jnp.array(RNG.integers(0, 1 << 30, (m, d)), jnp.int32)
    out = async_scatter(ops.row_tiles(table), idx, ops.row_tiles(upd),
                        op="xor", block_m=128, num_slots=8, interpret=True)
    expect = ref.scatter_update_ref(table, idx, upd, "xor")
    assert bool(jnp.all(out.reshape(n, d) == expect))


@pytest.mark.slow
def test_async_scatter_fuzz():
    rng = np.random.default_rng(7)
    for _ in range(10):
        n = int(rng.integers(4, 128))
        bm = int(rng.choice([16, 64]))
        m = bm * int(rng.integers(1, 4))
        k = int(rng.choice([2, 4, 8]))
        table = jnp.array(rng.standard_normal((n, 32)), jnp.float32)
        idx = jnp.array(rng.integers(0, n, m), jnp.int32)
        upd = jnp.array(rng.standard_normal((m, 32)), jnp.float32)
        out = async_scatter(ops.row_tiles(table), idx, ops.row_tiles(upd),
                            op="add", block_m=bm, num_slots=k, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out).reshape(n, 32),
            np.asarray(ref.scatter_update_ref(table, idx, upd, "add")),
            atol=1e-4, rtol=1e-4)


# -------------------------------------------------------------- stream_triad
@pytest.mark.parametrize("n,block", [(4096, 512), (8192, 1024), (512, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stream_triad(n, block, dtype):
    b = jnp.array(RNG.standard_normal(n), dtype)
    c = jnp.array(RNG.standard_normal(n), dtype)
    out = stream_triad(b, c, 3.0, block=block, interpret=True)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.triad_ref(b, c, 3.0),
                                          np.float32), atol=tol, rtol=tol)


# ----------------------------------------------------------- flash_attention
@pytest.mark.parametrize("b,hq,hkv,s,d,bq,bk", [
    (2, 4, 2, 256, 64, 64, 64),
    (1, 8, 1, 128, 128, 128, 128),   # MQA
    (2, 2, 2, 512, 32, 128, 64),     # MHA, rectangular blocks
])
@pytest.mark.parametrize("window", [0, 64])
def test_flash_attention(b, hq, hkv, s, d, bq, bk, window):
    q = jnp.array(RNG.standard_normal((b, hq, s, d)), jnp.float32) * 0.3
    k = jnp.array(RNG.standard_normal((b, hkv, s, d)), jnp.float32) * 0.3
    v = jnp.array(RNG.standard_normal((b, hkv, s, d)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=bq, block_k=bk, interpret=True)
    expect = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=1e-4)


def test_flash_attention_bf16():
    q = jnp.array(RNG.standard_normal((1, 4, 128, 64)), jnp.bfloat16) * 0.3
    k = jnp.array(RNG.standard_normal((1, 2, 128, 64)), jnp.bfloat16) * 0.3
    v = jnp.array(RNG.standard_normal((1, 2, 128, 64)), jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    expect = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=3e-2, rtol=3e-2)


# ----------------------------------------------------------- paged_attention
@pytest.mark.parametrize("b,hq,hkv,t,d,page", [
    (3, 8, 2, 1024, 64, 256),
    (1, 4, 4, 512, 128, 512),    # MHA
    (2, 16, 2, 2048, 64, 512),   # deep GQA
    (2, 10, 2, 512, 64, 256),    # odd group (5 query heads per KV)
])
def test_paged_attention(b, hq, hkv, t, d, page):
    q = jnp.array(RNG.standard_normal((b, hq, d)), jnp.float32) * 0.3
    kc = jnp.array(RNG.standard_normal((b, t, hkv * d)), jnp.float32) * 0.3
    vc = jnp.array(RNG.standard_normal((b, t, hkv * d)), jnp.float32)
    lens = jnp.array(RNG.integers(1, t + 1, b), jnp.int32)
    out = paged_attention(q, kc, vc, lens, page=page, interpret=True)
    expect = ref.paged_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=2e-5, rtol=1e-4)


# ------------------------------------------------------------- ops wrappers
def test_ops_padding_paths():
    table = jnp.array(RNG.standard_normal((100, 64)), jnp.float32)
    idx = jnp.array(RNG.integers(0, 100, 37), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(ops.gather(table, idx, block_m=16)),
        np.asarray(ref.gather_ref(table, idx)))
    upd = jnp.array(RNG.standard_normal((37, 64)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.scatter_update(table, idx, upd, block_m=16,
                                      num_slots=4)),
        np.asarray(ref.scatter_update_ref(table, idx, upd)), atol=1e-4)
    b = jnp.array(RNG.standard_normal(1000), jnp.float32)
    c = jnp.array(RNG.standard_normal(1000), jnp.float32)
    np.testing.assert_allclose(np.asarray(ops.triad(b, c, 2.5, block=512)),
                               np.asarray(ref.triad_ref(b, c, 2.5)),
                               atol=1e-6)
