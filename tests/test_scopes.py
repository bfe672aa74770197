"""The step programs name their layers with `jax.named_scope`: every block
kind's mixer half and FFN half, and the embedding, the layer stack and the
head around them, reach the compiled program's `op_name` metadata, which
the device trace is joined to. Tiny configs, compiled on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro import scopes as P
from repro.models import lm
from repro.runtime import steps

B, S, T_MAX = 2, 8, 16

# (arch, block kind, its mixer scope, the FFN scope)
CASES = [
    ("qwen2.5-3b", "full", P.MIXER["full"], P.MLP),
    ("recurrentgemma-9b", "local", P.MIXER["local"], P.MLP),
    ("recurrentgemma-9b", "rglru", P.MIXER["rglru"], P.MLP),
    ("rwkv6-7b", "rwkv6", P.MIXER["rwkv6"], P.MLP),
    ("granite-moe-1b-a400m", "moe", P.ATTN, P.MOE),
]
ATTENTION = (P.QKV, P.KV_CACHE_WRITE, P.OUT_PROJ)


def _hlo(arch: str, program: str) -> str:
    cfg = configs.get_smoke_config(arch)
    par = configs.ParallelConfig()
    params = jax.eval_shape(lambda k: lm.init_model(cfg, k, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, B, T_MAX))
    if program == "decode":
        fn = steps.make_serve_step(cfg, par)
        args = (params, jax.ShapeDtypeStruct((B, 1), jnp.int32), cache)
    elif program == "prefill":
        fn = steps.make_prefill_step(cfg, par)
        args = (params, {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)},
                cache)
    else:
        def fn(p, batch):
            return lm.train_loss(cfg, p, batch)[0]
        tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
        args = (params, {"tokens": tokens, "labels": tokens})
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("program", ["decode", "prefill", "train"])
@pytest.mark.parametrize("arch,kind,mixer,ffn", CASES,
                         ids=[c[1] for c in CASES])
def test_each_block_kind_names_its_halves(arch, kind, mixer, ffn, program):
    text = _hlo(arch, program)
    step = {"decode": "serve_step", "prefill": "prefill_step"}.get(
        program, "fn")
    for scope in (P.EMBED, P.LAYERS, P.HEAD):
        assert f'op_name="jit({step})/{scope}/' in text, (scope, text[:200])
    assert f"/{mixer}/" in text and f"/{ffn}/" in text
    if mixer == P.ATTN:
        inner = ATTENTION if program != "train" else (P.QKV, P.OUT_PROJ)
        for scope in inner:
            assert f"/{P.ATTN}/{scope}/" in text, scope
