"""Per-arch smoke tests: reduced configs, one forward/train step on CPU,
output shapes + no NaNs; prefill/decode consistency; M-RoPE/frontends."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import blocks, lm

ARCHS = list(configs.ARCH_IDS)


def make_inputs(cfg, B=2, S=32, seed=0):
    rng = np.random.default_rng(seed)
    inputs = {"labels": jnp.array(rng.integers(0, cfg.vocab_size, (B, S)))}
    if cfg.frontend is not None and cfg.frontend.kind == "audio":
        inputs["features"] = jnp.array(
            rng.standard_normal((B, S, cfg.frontend.feature_dim)),
            jnp.float32)
    else:
        inputs["tokens"] = jnp.array(rng.integers(0, cfg.vocab_size, (B, S)))
    if cfg.frontend is not None and cfg.frontend.kind == "vision":
        inputs["vision_embeds"] = jnp.array(rng.standard_normal(
            (B, cfg.frontend.prefix_len, cfg.frontend.feature_dim)),
            jnp.float32)
    return inputs


@pytest.mark.slow
@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = configs.get_smoke_config(arch)
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    inputs = make_inputs(cfg)
    loss, metrics = jax.jit(
        lambda p, i: lm.train_loss(cfg, p, i, remat="full"))(params, inputs)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch}: loss not finite"
    grads = jax.jit(jax.grad(lambda p, i: lm.train_loss(cfg, p, i)[0]))(
        params, inputs)
    gsq = jax.tree.reduce(
        lambda a, b: a + b,
        jax.tree.map(lambda t: jnp.sum(jnp.square(t.astype(jnp.float32))),
                     grads))
    assert bool(jnp.isfinite(gsq)), f"{arch}: grad not finite"
    assert float(gsq) > 0.0, f"{arch}: zero gradients"


@pytest.mark.slow
@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if configs.get_config(a).is_decoder])
def test_prefill_decode_consistency(arch):
    """Decode step t must equal prefill of the t+1-long prefix (same model,
    cached vs uncached paths agree)."""
    cfg = configs.get_smoke_config(arch)
    params = lm.init_model(cfg, jax.random.PRNGKey(1))
    B, S = 2, 16
    # capacity-MoE token dropping depends on batch composition, which breaks
    # cached-vs-uncached equivalence by design -> compare under dense routing
    moe_mode = "dense"
    inputs = make_inputs(cfg, B, S, seed=3)
    cache = lm.init_cache(cfg, B, S + 8)
    logits_p, cache = lm.prefill(cfg, params, inputs, cache,
                                 moe_mode=moe_mode)
    tok = jnp.argmax(logits_p[:, -1], -1)[:, None]
    logits_d, _ = lm.decode_step(cfg, params, tok, cache, moe_mode=moe_mode)

    # reference: prefill over the extended sequence
    ext = dict(inputs)
    ext["tokens"] = jnp.concatenate([inputs["tokens"], tok], axis=1)
    ext["labels"] = jnp.zeros_like(ext["tokens"])
    cache2 = lm.init_cache(cfg, B, S + 8)
    logits_ref, _ = lm.prefill(cfg, params, ext, cache2, moe_mode=moe_mode)
    np.testing.assert_allclose(
        np.asarray(logits_d[:, -1], np.float32),
        np.asarray(logits_ref[:, -1], np.float32), atol=0.15, rtol=0.05)


@pytest.mark.parametrize("arch,use_kernels", [
    ("qwen2.5-3b", True),           # the paged kernel, interpreted
    ("qwen2.5-3b", False),          # the masked jnp decode
    ("recurrentgemma-9b", False),   # the ring of its `local` layers
], ids=["qwen2.5-3b-kernels", "qwen2.5-3b", "recurrentgemma-9b"])
def test_kv_cache_flat_over_heads_and_decode_matches_prefill(arch,
                                                             use_kernels):
    """Attention caches are [B, T, Hkv * D], the layout the paged kernel
    reads; one decode step through them after a prefill gives the logits
    of a prefill one token longer."""
    cfg = configs.get_smoke_config(arch)
    params = lm.init_model(cfg, jax.random.PRNGKey(2))
    B, S, T = 2, 20, 24          # S past recurrentgemma's window of 16
    f32 = jnp.float32
    cache = lm.init_cache(cfg, B, T, dtype=f32)
    width = cfg.num_kv_heads * cfg.resolved_head_dim
    lens = {T, min(cfg.window_size or T, T)}
    kv = [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
          if jax.tree_util.keystr(path).endswith(("['k']", "['v']"))]
    assert kv and all(leaf.shape[-3] == B and leaf.shape[-2] in lens
                      and leaf.shape[-1] == width for leaf in kv)

    tokens = make_inputs(cfg, B, S + 1, seed=4)["tokens"]
    logits, cache = lm.prefill(cfg, params, {"tokens": tokens[:, :S]}, cache,
                               use_kernels=use_kernels, dtype=f32)
    logits_d, _ = lm.decode_step(cfg, params, tokens[:, S:], cache,
                                 use_kernels=use_kernels, dtype=f32)
    logits_ref, _ = lm.prefill(cfg, params, {"tokens": tokens},
                               lm.init_cache(cfg, B, T, dtype=f32),
                               dtype=f32)
    np.testing.assert_allclose(np.asarray(logits_d[:, -1]),
                               np.asarray(logits_ref[:, -1]),
                               atol=1e-4, rtol=1e-4)


def test_mrope_text_equals_rope():
    """For pure text (three equal position streams), M-RoPE == RoPE."""
    x = jnp.array(np.random.default_rng(0).standard_normal((2, 8, 4, 16)),
                  jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(8)[None], (2, 8))
    pos3 = jnp.stack([pos, pos, pos])
    a = blocks.apply_rope(x, pos, 10000.0)
    b = blocks.apply_rope(x, pos3, 10000.0, mrope_sections=(2, 3, 3))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def _rope_half_split(x, positions, theta, sections=()):
    """RoPE as the rotation of each head's halves: (x1, x2) ->
    (x1·cos − x2·sin, x2·cos + x1·sin); with M-RoPE sections, each
    frequency takes its position from the stream its section names."""
    D = x.shape[-1]
    freqs = blocks.rope_frequencies(D, theta)
    if sections:
        stream = np.repeat(np.arange(len(sections)), sections)    # [D/2]
        pos = jnp.moveaxis(positions[stream], 0, -1)              # [B,S,D/2]
    else:
        pos = positions[..., None]
    angles = pos.astype(jnp.float32) * freqs
    cos = jnp.cos(angles)[..., None, :].astype(x.dtype)
    sin = jnp.sin(angles)[..., None, :].astype(x.dtype)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("D,theta,sections", [
    (64, 10000.0, ()), (128, 1e6, ()),
    (64, 10000.0, (8, 12, 12)), (128, 1e6, (16, 24, 24))])
@pytest.mark.parametrize("S", [1, 9])
def test_rope_bit_equal_to_half_split(dtype, D, theta, sections, S):
    """`apply_rope` computes the half-split rotation's products and sums:
    bit-equal with and without M-RoPE. Both run op by op: under jit a
    backend may contract a product and a sum into one multiply-add, and
    which it contracts is its choice for either form."""
    rng = np.random.default_rng(D + S)
    B, H = 3, 4
    x = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype)
    positions = jnp.asarray(rng.integers(0, 32768, (3, B, S) if sections
                                         else (B, S)), jnp.int32)
    want = np.asarray(_rope_half_split(x, positions, theta, sections))
    got = np.asarray(blocks.apply_rope(x, positions, theta, sections))
    assert got.dtype == want.dtype
    bits = np.uint16 if dtype == jnp.bfloat16 else np.uint32
    np.testing.assert_array_equal(got.view(bits), want.view(bits))


def test_chunked_attention_matches_naive():
    cfg = configs.get_smoke_config("qwen2-7b")
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    inputs = make_inputs(cfg, B=1, S=64)
    old = dict(blocks.ATTN_CONFIG)
    try:
        blocks.ATTN_CONFIG.update(chunk_threshold=1 << 30)
        l_naive, _ = lm.train_loss(cfg, params, inputs, remat="none")
        blocks.ATTN_CONFIG.update(chunk_threshold=1, q_chunk=16, kv_chunk=16)
        l_chunk, _ = lm.train_loss(cfg, params, inputs, remat="none")
    finally:
        blocks.ATTN_CONFIG.update(old)
    assert abs(float(l_naive) - float(l_chunk)) < 2e-2


def test_moe_capacity_vs_dense_smoke():
    cfg = configs.get_smoke_config("granite-moe-1b-a400m")
    params = lm.init_model(cfg, jax.random.PRNGKey(0))
    inputs = make_inputs(cfg, B=2, S=16)
    l_dense, _ = lm.train_loss(cfg, params, inputs, moe_mode="dense",
                               remat="none")
    l_cap, _ = lm.train_loss(cfg, params, inputs, moe_mode="capacity",
                             remat="none")
    # capacity path may drop tokens but must be finite and close-ish
    assert bool(jnp.isfinite(l_dense)) and bool(jnp.isfinite(l_cap))
    assert abs(float(l_dense) - float(l_cap)) < 1.0


def test_param_counts_match_analytic():
    """init_model parameter totals track ModelConfig.param_count within 2%."""
    for arch in ("qwen2.5-3b", "granite-moe-1b-a400m", "rwkv6-7b"):
        cfg = configs.get_smoke_config(arch)
        params = lm.init_model(cfg, jax.random.PRNGKey(0))
        actual = sum(x.size for x in jax.tree.leaves(params))
        expect = cfg.param_count()
        assert abs(actual - expect) / expect < 0.05, (arch, actual, expect)
