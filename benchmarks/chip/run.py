"""On-chip benchmark: one cell of BENCHMARK.json per run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout. The harness is driven by data: the cell
names a configuration (`configs/<config>.json`, with the id of the
program's config and the plain reference it is checked against) and a
traffic mix (`traffic/<traffic>.json`, which names its driver,
`drivers/<driver>.py`, and gives the driver's parameters); the limits that
decide `correct` are in `limits/<cell>.json`; each per-layer metric is read
by `metrics/<metric>.py` from the reduced trace and the cell's work counts.
A driver may name its compiled programs (`programs(state)`): in a traced
run each device operation is then joined to its HLO instruction, for its
scope and class (`trace.attach_hlo`). A traced run also holds a host span
"gc" open over each garbage collection, so that an idle gap under one
reads `gc`.

A run loads, compiles and warms up the cell's own shapes (set-up), measures
for `--seconds` (or, with `--trace 1`, traces the traffic's `trace_*` share
of it), reads the peak device memory, frees the program's state and
compares a sample of what the window produced with the reference. Its last
line on stdout is one JSON object; the numbers compared, each beside its
limit, are the last lines on stderr and the result's last key. It exits
non-zero, printing no result, when the checkout holds no program
(`src/repro`), or JAX finds no TPU or fewer chips than the cell asks for.

JAX's persistent compilation cache is where the program's
`enable_compile_cache()` puts it: `JAX_COMPILATION_CACHE_DIR` where that is
set, else `<checkout>/.jax_cache`.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Any, Callable, Dict  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import jax  # noqa: E402

T_IMPORT = time.perf_counter()

from benchmarks.chip import peaks as peaks_mod  # noqa: E402
from benchmarks.chip import trace as trace_mod  # noqa: E402


def load_module(path: Path):
    """Import a file of the benchmark by path (names may hold dots)."""
    name = "bench_" + "_".join(path.relative_to(HERE).with_suffix("")
                               .parts).replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(bench: dict, name: str, data_dir: Path = HERE,
              root: Path = ROOT) -> SimpleNamespace:
    """Everything the harness reads for one cell, found by name."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg_json = read_json(root / conf["file"])
    traffic = read_json(data_dir / "traffic" / f"{cell['traffic']}.json")
    limits = read_json(data_dir / "limits" / f"{name}.json")

    def applies(metric):
        return "workloads" not in metric or name in metric["workloads"]

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return SimpleNamespace(name=name, chips=cell["chips"], cfg_json=cfg_json,
                           traffic_name=cell["traffic"], traffic=traffic,
                           limits=limits, e2e=e2e, per_layer=per_layer)


def require_program(root: Path = ROOT) -> None:
    """The system under test is the checkout's `src/repro`."""
    if not (root / "src" / "repro" / "__init__.py").is_file():
        raise SystemExit(f"run.py: no program under {root / 'src'}")


def require_devices(chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"run.py: no TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"run.py: the cell needs {chips} TPU chips, JAX "
                         f"found {len(devices)}")
    return devices[:chips]


def check_widths(program_cfg, cfg_json: dict) -> None:
    """Every width of the program's config against the published one."""
    bad = []
    for attr, key in cfg_json["program_fields"].items():
        got = program_cfg
        for part in attr.split("."):
            got = getattr(got, part)
        want = cfg_json[key] if isinstance(key, str) else key["value"]
        if isinstance(got, tuple):
            got = list(got)
        if got != want:
            bad.append(f"{attr}={got!r} vs {key}={want!r}")
    if bad:
        raise SystemExit("run.py: the program's config departs from the "
                         "published one: " + "; ".join(bad))


class CompileCounter:
    """Counts compilation events (tracing, lowering, backend compiles and
    cache lookups) while armed."""

    def __init__(self):
        self.armed = False
        self.count = 0
        self.events: Dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **kw) -> None:
        self._note(event)

    def _on_event(self, event: str, **kw) -> None:
        self._note(event)

    def _note(self, event: str) -> None:
        self.events[event] = self.events.get(event, 0) + 1
        if self.armed and ("compil" in event or "trace" in event):
            self.count += 1


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _log(*parts) -> None:
    print(*parts, flush=True)


def hlo_texts(driver, state) -> Dict[str, str]:
    """HLO text of each compiled program the driver names, where it has
    `programs(state) -> {name: Compiled}`."""
    programs = getattr(driver, "programs", None)
    texts = {}
    for name, compiled in (programs(state) if programs else {}).items():
        try:
            texts[name] = compiled.as_text()
        except NotImplementedError as e:
            _log(f"hlo join: no text of {name}: {e}")
    return texts


def log_join(tr, modules) -> None:
    """For each program joined, the share of its device time whose
    operation found its instruction, the share without `op_name`, and its
    seconds by class."""
    for m in modules:
        pat = re.escape(m)
        joined, unnamed = trace_mod.join_shares(tr, pat)
        split = ", ".join(f"{c} {tr.cls_s(c, pat)!r} s"
                          for c in trace_mod.CLASSES)
        _log(f"hlo join {m}: {100 * joined!r}% of device time joined, "
             f"{100 * unnamed!r}% without op_name; {split}")


def gc_spans() -> Callable[[str, dict], None]:
    """A `gc.callbacks` entry that holds a host span named "gc" open over
    each collection."""
    open_ = []

    def on_gc(phase: str, info: dict) -> None:
        if phase == "start":
            open_.append(jax.profiler.TraceAnnotation("gc"))
            open_[-1].__enter__()
        elif open_:
            open_.pop().__exit__(None, None, None)

    return on_gc


def run_cell(spec: SimpleNamespace, seed: int, seconds: float, traced: bool,
             devices: list, backend_s: float = 0.0) -> Dict[str, Any]:
    from repro import configs
    from repro.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = CompileCounter()
    program_cfg = configs.get_config(spec.cfg_json["repro_config"])
    check_widths(program_cfg, spec.cfg_json)
    driver = load_module(HERE / "drivers" / f"{spec.traffic['driver']}.py")
    reference = load_module(HERE / "reference"
                            / f"{spec.cfg_json['reference']}.py")
    state = driver.State(cfg_json=spec.cfg_json, traffic=spec.traffic,
                         seed=seed, reference=reference)
    state.program_cfg = program_cfg
    state.devices = devices
    state.phases["import"] = T_IMPORT - T_START
    state.phases["backend"] = backend_s
    driver.setup(state)
    setup_s = time.perf_counter() - T_START
    hits = counter.events.get("/jax/compilation_cache/cache_hits", 0)
    misses = counter.events.get("/jax/compilation_cache/cache_misses", 0)

    # Set-up's objects (the program's and JAX's, some 10^5) are frozen out of
    # the collector: a full collection over them stalls the host for tens
    # of milliseconds, long enough to drain the steps queued on the device.
    gc.collect()
    gc.freeze()
    trace_dir = tempfile.mkdtemp(prefix="chipbench_") if traced else None
    on_gc = gc_spans()
    counter.armed = True
    if traced:
        gc.callbacks.append(on_gc)
        jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("window"):
            win = driver.window(state, seconds, traced)
    finally:
        if traced:
            jax.profiler.stop_trace()
            gc.callbacks.remove(on_gc)
        counter.armed = False
    peak = _peak_bytes(devices)
    e2e = driver.end_to_end(state, win)
    work = driver.work(state)
    n = driver.counts(state)
    texts = hlo_texts(driver, state) if traced else {}

    t0 = time.perf_counter()
    checks = driver.check(state, spec.limits)
    ref_s = time.perf_counter() - t0

    ph = state.phases
    _log(f"setup_s {setup_s!r}: " + ", ".join(
        f"{k} {v!r} s" for k, v in ph.items())
        + f"; compile cache {cache_dir}: {hits} hits, {misses} misses; "
        f"reference check {ref_s!r} s (not in setup_s)")
    _log(f"requests or steps attempted in the window: {n['attempted']}, "
         f"failed: {n['failed']}")
    _log(f"compilations inside the window: {counter.count}")
    _log(driver.describe(state, win))

    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    metrics: Dict[str, Any] = {}
    breakdown = None
    if not traced:
        for m in spec.e2e:
            value = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        path = next(Path(trace_dir).rglob("*.xplane.pb"))
        tr = trace_mod.load(str(path),
                            span_names=tuple(driver.SPANS) + ("gc",))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log_join(tr, trace_mod.attach_hlo(tr, texts.values()))
        gcs = [sp.end - sp.start for sp in tr.spans if sp.name == "gc"]
        _log(f"gc: {len(gcs)} collections traced, longest "
             f"{max(gcs, default=0.0) * 1e-6!r} ms")
        ctx = SimpleNamespace(trace=tr, work=work, config=spec.cfg_json,
                              peaks=peaks_mod.peaks_for(dev["kind"]),
                              log=_log)
        for m in spec.per_layer:
            reader = load_module(HERE / "metrics" / f"{m['name']}.py")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev["busy_s"] = tr.busy_s()
        dev["window_s"] = tr.window_s()
        breakdown = trace_mod.breakdown(tr)
    result: Dict[str, Any] = {
        "correct": all(v <= lim for v, lim in checks.values()), **n,
        "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # the numbers compared come last
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    require_program()
    bench = read_json(ROOT / "BENCHMARK.json")
    spec = cell_spec(bench, args.workload)
    t0 = time.perf_counter()
    devices = require_devices(spec.chips)
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      devices, backend_s=time.perf_counter() - t0)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
