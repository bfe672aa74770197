"""The serving driver at smoke size on the CPU, called through its functions."""
import jax
import pytest

from benchmarks.chip import run
from benchmarks.chip.tests import smoke


@pytest.fixture(autouse=True)
def _smoke(monkeypatch):
    smoke.use_smoke_program(monkeypatch)


def _drive(cell, seconds=0.3):
    driver, st, spec = smoke.state(cell, seed=2**31 + 5)
    driver.setup(st)
    counter = run.CompileCounter()
    counter.armed = True
    win = driver.window(st, seconds, traced=False)
    counter.armed = False
    return driver, st, spec, win, counter


def test_serve_batches_runs_whole_batches_and_checks_them():
    driver, st, spec, win, counter = _drive("qwen2.5-3b.decode_heavy")
    t = st.traffic
    assert counter.count == 0, "nothing compiles inside the window"
    assert len(st.batches) >= 1
    assert win["t1"] - win["t0"] >= 0.3
    e2e = driver.end_to_end(st, win)
    served = len(st.batches) * t["batch"] * t["new_tokens"]
    assert e2e["serve_tok_s"] == pytest.approx(
        served / (win["t1"] - win["t0"]))
    assert 0 < e2e["ttft_p95_ms"] and 0 < e2e["tpot_p95_ms"]
    assert driver.counts(st) == {"attempted": len(st.batches) * t["batch"],
                                 "failed": 0}
    work = driver.work(st)
    assert work["prefill_calls"] == len(st.batches)
    assert len(work["decode_lengths"]) == len(st.batches) * (
        t["new_tokens"] - 1)
    assert work["decode_lengths"][0] == [t["prompt_len"] + 1] * t["batch"]
    assert "batches (ttft ms, tpot ms)" in driver.describe(st, win)
    checks = driver.check(st, spec.limits)
    value, limit = checks["logit_gap"]
    assert value <= limit
    assert st.params is None, "the program's state is freed before the check"


def test_serving_sample_holds_the_window_requests():
    driver, st, spec, win, _ = _drive("qwen2.5-3b.decode_heavy", 0.1)
    prompts, served = driver._sample(st)
    n = min(driver.CHECK_REQUESTS, driver.counts(st)["attempted"])
    assert prompts.shape == (n, st.traffic["prompt_len"])
    assert served.shape == (n, st.traffic["new_tokens"])
    assert jax.numpy.asarray(served).max() < st.cfg_json["vocab_size"]
