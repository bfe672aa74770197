"""Each plain reference against the program's own entry points at smoke
sizes, in float32 on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import run
from benchmarks.chip.tests import smoke


@pytest.fixture(autouse=True)
def _smoke(monkeypatch):
    smoke.use_smoke_program(monkeypatch)


def test_qwen2_reference_matches_prefill_and_decode():
    from repro import configs
    from repro.models import lm

    ref = run.load_module(run.HERE / "reference" / "qwen2.py")
    drv = run.load_module(run.HERE / "drivers" / "serve_batches.py")
    cfg_json = smoke.smoke_config("qwen2.5-3b")
    cfg = configs.get_smoke_config("qwen2.5-3b")
    w = ref.init_weights(cfg_json, 11, dtype=jnp.float32)
    params = drv.program_params(cfg_json["program_params"], w)
    rng = np.random.default_rng(0)
    B, S, n = 2, 12, 5
    seq = rng.integers(0, cfg.vocab_size, (B, S + n), dtype=np.int32)
    cache = lm.init_cache(cfg, B, S + n, dtype=jnp.float32)
    logits, cache = lm.prefill(cfg, params, {"tokens": jnp.asarray(seq[:, :S])},
                               cache, dtype=jnp.float32)
    got = [logits[:, -1]]
    for i in range(n - 1):
        logits, cache = lm.decode_step(cfg, params,
                                       jnp.asarray(seq[:, S + i:S + i + 1]),
                                       cache, dtype=jnp.float32)
        got.append(logits[:, -1])
    got = np.stack(got, 1)                                   # [B, n, V]
    items = tuple(sorted((k, v) for k, v in cfg_json.items()
                         if k in ref.ARCH_KEYS))
    h = ref._hidden(items, w, jnp.asarray(seq[:, :S + n - 1]), "f32")
    want = np.asarray(jnp.einsum("npd,vd->npv", h[:, S - 1:], w["embed"],
                                 precision=ref.HI))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # served_gaps: how far each fed token's logit lies below the best
    served = seq[:, S:]
    gaps, _ = ref.served_gaps(cfg_json, w, seq[:, :S], served)
    chosen = np.take_along_axis(want, served[..., None], -1)[..., 0]
    np.testing.assert_allclose(gaps, want.max(-1) - chosen, atol=1e-5)
