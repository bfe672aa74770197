"""A whole run with the timed path broken underneath, at smoke size on the
CPU: `correct` has to come out false for each fault the cell can have.

The faults are planted in the program's own step builders, which the
driver calls, so the warm-up, the window and the check all run on the
broken path. The search for a chip is skipped (`run_cell` is given the CPU
devices)."""
import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import run
from benchmarks.chip.tests import smoke

SERVE = "qwen2.5-3b.decode_heavy"


@pytest.fixture(autouse=True)
def _smoke(monkeypatch):
    smoke.use_smoke_program(monkeypatch)


def _run(cell):
    s = smoke.spec(cell)
    return run.run_cell(s, 2**31 + 29, 0.2, False, jax.devices()[:s.chips])


def _wrap(monkeypatch, name, fault):
    from repro.runtime import steps

    orig = getattr(steps, name)
    monkeypatch.setattr(steps, name, lambda *a, **k: fault(orig(*a, **k)))


# ------------------------------------------------------------------ serving
def test_serving_sound_run_is_correct():
    assert _run(SERVE)["correct"] is True


def test_serving_decode_that_leaves_the_cache_unchanged(monkeypatch):
    _wrap(monkeypatch, "make_serve_step",
          lambda f: lambda p, t, c: (f(p, t, c)[0], c))
    assert _run(SERVE)["correct"] is False


def test_serving_token_altered_where_it_is_produced(monkeypatch):
    def fault(f):
        def step(p, t, c):
            logits, cache = f(p, t, c)
            return jnp.roll(logits, 1, axis=-1), cache
        return step
    _wrap(monkeypatch, "make_serve_step", fault)
    assert _run(SERVE)["correct"] is False


def test_serving_half_of_the_batch_left_out(monkeypatch):
    def fault(f):
        def step(p, batch, c):
            tok = batch["tokens"]
            half = tok.shape[0] // 2
            tok = jnp.concatenate([tok[:half], tok[:half]])
            return f(p, {"tokens": tok}, c)
        return step
    _wrap(monkeypatch, "make_prefill_step", fault)
    # every finished request in the sample, so the broken half is in it
    driver = run.load_module(run.HERE / "drivers" / "serve_batches.py")
    monkeypatch.setattr(driver, "CHECK_REQUESTS", 10**6)
    assert _run(SERVE)["correct"] is False
