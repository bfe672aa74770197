"""The harness: every entry of BENCHMARK.json finds its files by name, a
cell defined by new files alone runs, and the command refuses to run
without a TPU or without the program."""
import gc
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import jax
import pytest

from benchmarks.chip import run, trace
from benchmarks.chip.tests import smoke

BENCH = smoke.bench()


def test_every_entry_has_its_files():
    for conf in BENCH["configs"]:
        cfg = run.read_json(run.ROOT / conf["file"])
        assert cfg["source"] == conf["source"]
        assert cfg["reduced"] == conf["reduced"]
        assert (run.HERE / "reference" / f"{cfg['reference']}.py").is_file()
    for cell in BENCH["workloads"]:
        spec = run.cell_spec(BENCH, cell["name"])
        assert (run.HERE / "drivers"
                / f"{spec.traffic['driver']}.py").is_file()
        assert spec.per_layer, "every cell reports a per-layer metric"
        names = {m["name"] for m in spec.e2e}
        assert "setup_s" in names and len(names) >= 2
        assert {m["moves"] for m in spec.per_layer} <= names
    for m in BENCH["per_layer"]:
        assert (run.HERE / "metrics" / f"{m['name']}.py").is_file()


def test_metric_readers_return_nothing_on_an_empty_trace():
    from benchmarks.chip import peaks

    empty = trace.Trace(ops=[], spans=[], window=(0.0, 0.0), devices=[])
    for m in BENCH["per_layer"]:
        reader = run.load_module(run.HERE / "metrics" / f"{m['name']}.py")
        for work in ({}, {"kind": "serve", "batch": 2, "prompt_len": 8,
                          "prefill_calls": 1, "decode_lengths": [[9, 9]]}):
            ctx = SimpleNamespace(trace=empty, work=work,
                                  config=smoke.smoke_config("qwen2.5-3b"),
                                  peaks=peaks.peaks_for("TPU v5 lite"),
                                  log=lambda *a: None)
            assert reader.read(ctx) is None, m["name"]


def test_a_cell_defined_by_new_files_alone_runs(tmp_path, monkeypatch):
    """A new traffic file and its limits, and a new BENCHMARK.json entry:
    the harness finds them by name and runs the cell."""
    smoke.use_smoke_program(monkeypatch)
    for sub in ("traffic", "limits"):
        shutil.copytree(run.HERE / sub, tmp_path / sub)
    traffic = dict(run.read_json(run.HERE / "traffic" / "decode_heavy.json"),
                   batch=2, prompt_len=8, new_tokens=5, cache_len=16)
    (tmp_path / "traffic" / "short_answers.json").write_text(
        json.dumps(traffic))
    (tmp_path / "limits" / "qwen2.5-3b.short_answers.json").write_text(
        json.dumps({"logit_gap": 0.01}))
    bench = run.read_json(run.ROOT / "BENCHMARK.json")
    bench["workloads"].append({"name": "qwen2.5-3b.short_answers",
                               "config": "qwen2.5-3b",
                               "traffic": "short_answers", "chips": 1,
                               "why": "a cell added as data"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "qwen2.5-3b.decode_heavy" in m.get("workloads", ()):
            m["workloads"].append("qwen2.5-3b.short_answers")
    spec = run.cell_spec(bench, "qwen2.5-3b.short_answers", data_dir=tmp_path)
    assert spec.traffic["new_tokens"] == 5
    assert {m["name"] for m in spec.e2e} == {
        "serve_tok_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert {m["name"] for m in spec.per_layer} >= {"mfu.decode", "idle.serve"}
    spec.cfg_json = smoke.smoke_config("qwen2.5-3b")
    result = run.run_cell(spec, 5, 0.1, False, jax.devices()[:1])
    assert result["correct"] is True
    assert set(result["metrics"]) == {m["name"] for m in spec.e2e}
    assert result["attempted"] % 2 == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"


def test_a_configuration_defined_by_new_files_alone_runs(tmp_path,
                                                        monkeypatch):
    """A configuration of the same family with other widths and its own
    head (Qwen2-7B's layout, at the program's smoke widths): its file
    carries its parameter table, and with its traffic, limits and
    BENCHMARK.json entries the harness serves and checks it unchanged."""
    smoke.use_smoke_program(monkeypatch)
    cfg = dict(run.read_json(run.HERE / "configs" / "qwen2.5-3b.json"),
               hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, intermediate_size=128,
               vocab_size=512, rope_theta=10000.0, tie_word_embeddings=False,
               repro_config="qwen2-7b")
    cfg["program_fields"] = dict(cfg["program_fields"],
                                 resolved_head_dim={"value": 16})
    cfg["program_params"] = dict(cfg["program_params"], head="lm_head")
    (tmp_path / "configs").mkdir()
    cfg_file = tmp_path / "configs" / "qwen2-7b.json"
    cfg_file.write_text(json.dumps(cfg))
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "decode_heavy.json").write_text(json.dumps(dict(
        run.read_json(run.HERE / "traffic" / "decode_heavy.json"),
        **smoke.SMOKE_TRAFFIC["decode_heavy"])))
    # its own head gives logits of about unit scale (the tied one, 0.02), so
    # its own limit: on the CPU over five seeds the program read 0 to 0.025
    # and the fp8 control 0.20 to 0.34
    (tmp_path / "limits").mkdir()
    (tmp_path / "limits" / "qwen2-7b.decode_heavy.json").write_text(
        json.dumps({"logit_gap": 0.06}))
    bench = run.read_json(run.ROOT / "BENCHMARK.json")
    bench["configs"].append({"name": "qwen2-7b", "source": "a test",
                             "file": str(cfg_file), "reduced": [],
                             "why": "a configuration added as data"})
    bench["workloads"].append({"name": "qwen2-7b.decode_heavy",
                               "config": "qwen2-7b",
                               "traffic": "decode_heavy", "chips": 1,
                               "why": "a configuration added as data"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "qwen2.5-3b.decode_heavy" in m.get("workloads", ()):
            m["workloads"].append("qwen2-7b.decode_heavy")
    spec = run.cell_spec(bench, "qwen2-7b.decode_heavy", data_dir=tmp_path)
    assert spec.cfg_json["tie_word_embeddings"] is False
    result = run.run_cell(spec, 2**32 + 3, 0.1, False, jax.devices()[:1])
    assert result["correct"] is True
    assert set(result["metrics"]) == {m["name"] for m in spec.e2e}
    assert result["checks"]["logit_gap"]["limit"] == 0.06


def test_traced_run_reads_the_trace(monkeypatch):
    """With --trace 1 the run traces the window and reports the device's
    busy and window seconds; on the CPU no TPU plane is found, so the
    per-layer readers return nothing."""
    from benchmarks.chip import peaks

    smoke.use_smoke_program(monkeypatch)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    s = smoke.spec("qwen2.5-3b.decode_heavy")
    result = run.run_cell(s, 9, 0.1, True, jax.devices()[:1])
    assert result["correct"] is True
    assert result["metrics"] == {}
    assert result["device"]["busy_s"] == 0 and result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(result)[-2:] == ["breakdown", "checks"]


EXISTING = ("mfu.prefill", "mfu.decode", "paged_attention_roofline",
            "flash_attention_roofline", "idle.serve")


@pytest.mark.parametrize("hook", [True, False], ids=["programs", "none"])
def test_traced_run_joins_the_programs_the_driver_names(monkeypatch, hook):
    """A traced run on the CPU backend's plane: with the driver's
    `programs()` every decode operation joins its instruction and
    `data_movement.decode` is read; without it the run still ends and
    leaves that metric out. Either way the existing readers and the
    breakdown read what they read on the same trace without the join, and
    no `gc` callback is left behind."""
    from benchmarks.chip import peaks

    smoke.use_smoke_program(monkeypatch)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    load, plain, seen = trace.load, [], {}

    def cpu_load(path, span_names=()):
        plain.append(load(path, span_names, device_plane=r"^/host:CPU$"))
        return load(path, span_names, device_plane=r"^/host:CPU$")

    monkeypatch.setattr(trace, "load", cpu_load)
    driver = run.load_module(run.HERE / "drivers" / "serve_batches.py")
    work = driver.work
    monkeypatch.setattr(driver, "work",
                        lambda st: seen.setdefault("work", work(st)))
    if not hook:
        monkeypatch.delattr(driver, "programs")
    callbacks = list(gc.callbacks)
    s = smoke.spec("qwen2.5-3b.decode_heavy")
    result = run.run_cell(s, 2**31 + 17, 0.1, True, jax.devices()[:1])
    assert gc.callbacks == callbacks
    assert result["correct"] is True
    metrics = result["metrics"]
    if hook:
        assert 0 < metrics["data_movement.decode"]["value"] < 100
    else:
        assert "data_movement.decode" not in metrics
    ctx = SimpleNamespace(trace=plain[0], work=seen["work"],
                          config=s.cfg_json, peaks=peaks.peaks_for("cpu"),
                          log=lambda *a: None)
    assert {"mfu.prefill", "mfu.decode", "idle.serve"} <= set(metrics)
    for name in EXISTING:
        reader = run.load_module(run.HERE / "metrics" / f"{name}.py")
        want = reader.read(ctx)
        assert metrics.get(name, {}).get("value") == want, name
    assert result["breakdown"] == json.loads(json.dumps(
        trace.breakdown(plain[0])))


def test_untraced_run_reads_no_program_text(monkeypatch):
    """A timed run neither asks the driver for its programs nor registers
    a `gc` callback."""
    smoke.use_smoke_program(monkeypatch)
    driver = run.load_module(run.HERE / "drivers" / "serve_batches.py")

    def refuse(state):
        raise AssertionError("programs() called in a timed run")

    monkeypatch.setattr(driver, "programs", refuse)
    appended = []
    monkeypatch.setattr(gc, "callbacks", _Watched(gc.callbacks, appended))
    s = smoke.spec("qwen2.5-3b.decode_heavy")
    result = run.run_cell(s, 2**31 + 19, 0.1, False, jax.devices()[:1])
    assert result["correct"] is True and appended == []


class _Watched(list):
    def __init__(self, items, appended):
        super().__init__(items)
        self._appended = appended

    def append(self, item):
        self._appended.append(item)
        super().append(item)


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen2.5-3b.decode_heavy", "--seed", str(2**33 + 1), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_command_refuses_to_run_without_a_tpu():
    proc = _command(run.ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "{" not in proc.stdout


def test_command_refuses_to_run_without_the_program(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(run.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _command(tmp_path)
    assert proc.returncode != 0
    assert "no program" in proc.stderr
    assert "{" not in proc.stdout
