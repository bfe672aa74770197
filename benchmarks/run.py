"""Benchmark harness: one function per paper table/figure + engine-driver
throughput. Prints ``name,us_per_call,derived`` CSV. The kernels and the
step programs are measured on the chip by `benchmarks/chip/`.

Usage: PYTHONPATH=src python -m benchmarks.run [--engine scalar|batched]
                                               [--vector] [--sanitize]
                                               [--smoke] [--list]
                                               [--json PATH]
                                               [--profile PATH] [figure ...]
(no args -> everything; `--list` prints the sweep names and the registered workloads with their
declared capabilities, then exits).
`--engine` picks the timed-engine implementation behind the AMU configs:
"batched" (default; vectorized, fast sweeps) or "scalar" (per-event oracle).
`--sanitize` arms the runtime AMI protocol sanitizer (shadow-state race/
leak checking; see TESTING.md) on every session the sweeps build — both
via AMU_SANITIZE=1 for suites that construct their own configs and by
deriving the shared config. Observation only: results are bit-identical.
`--vector` runs the AloadVec/AstoreVec (and software-pipelined chase)
workload ports — every workload has one — and adds the vector axis to the
`engine` suite. `--smoke` is the CI regression gate: a shrunken `engine`
suite only, which FAILS (exit 1) if the batched engine or the vector ports
lose their speedup floors. `--json PATH` additionally archives the rows as
JSON (name/us_per_call/derived records) — the nightly job uploads this
artifact. `--profile PATH` wraps the whole run in cProfile and dumps the
stats there (readable with `python -m pstats PATH`), so future host-side
Amdahl ceilings are diagnosable straight from a nightly artifact.
"""
from __future__ import annotations

import json
import os
import sys

# CI floors for --smoke (deliberately below the locally-measured numbers so
# noisy runners don't flake, but well above a real regression). Keyed per
# workload: the zero-copy block ports (STREAM/IS, measured 8-12x) hold a
# higher floor than the request-rate ports; LL guards the software-pipelined
# chase path (measured ~2.2x at K=16).
SMOKE_MIN_BATCHED_SPEEDUP = 2.0     # aload_batch driver vs scalar driver
SMOKE_MIN_VECTOR_SPEEDUP = {        # vector port vs scalar-yield port
    "GUPS": 1.5,
    "STREAM": 2.0,
    "IS": 2.0,
    "LL": 1.5,
}
SMOKE_MIN_VECTOR_DEFAULT = 1.5
# serving: mean per-request latency, AMI plane vs the synchronous
# page-fault baseline (measured ~12x scalar / ~19x vector at the smoke
# sizes; MLP across requests is the whole mechanism, so anything near 1x
# means the arrival/latency plumbing broke)
SMOKE_MIN_SERVE_SPEEDUP = 3.0
# epoch-fused host-throughput floor, two ceilings per flagship row:
#  * `entries` — the engine-entry count is a deterministic model fact, so a
#    ceiling catches the fused loop silently degrading back toward
#    per-command entry granularity (GUPS smoke: 119 fused vs 574 per-command;
#    serve vector: 290 vs ~430);
#  * `us_per_entry` — with the entry count pinned, ceiling-gating wall-µs of
#    driver time per entry bounds total driver time for the row's fixed
#    workload shape. These sit ~4x above the locally-measured values (GUPS
#    fused ~410 µs/entry at ~550 rows/entry, serve vector ~90 µs/entry) so
#    loaded CI runners don't flake.
SMOKE_MAX_US_PER_ENTRY = {
    "engine/GUPS_sched_vector_fused": 1600.0,
    "serve/poisson/ami_vector": 400.0,
}
SMOKE_MAX_ENTRIES = {
    "engine/GUPS_sched_vector_fused": 200,
    "serve/poisson/ami_vector": 360,
}
# fault gates (rows from the `faults` suite, retry-enabled only): GUPS at
# 1% error with retries must stay within 1.5x of its fault-free time
# (retry+failover traffic is modeled, so a blowup means the recovery path
# regressed), and serving availability must hold >= 0.99
SMOKE_MAX_FAULT_SLOWDOWN = 1.5
SMOKE_MIN_AVAILABILITY = 0.99
# rack gates (homogeneous 4-core GUPS row, uncontended link bandwidth):
# aggregate throughput must scale >= 2x over one core (measured ~3.2x —
# below that the arbiter is serializing cores it shouldn't), and Jain
# fairness across identical cores must hold >= 0.9 (measured ~0.997)
SMOKE_MIN_RACK_SCALING = 2.0
SMOKE_MIN_RACK_FAIRNESS = 0.9


def _parse_speedup(derived: str, key: str) -> float:
    for part in derived.split(","):
        if part.startswith(key + "="):
            return float(part.split("=")[1].rstrip("x"))
    return 0.0


def _print_catalog(suites, file=None) -> None:
    """``--list``: every sweep, then every registered workload with its
    declared capabilities (straight from repro.amu.REGISTRY)."""
    from repro.amu import REGISTRY
    print("sweeps:", file=file)
    for name in sorted(suites):
        print(f"  {name}", file=file)
    print("workloads (repro.amu.REGISTRY):", file=file)
    caps = ("vector", "pipelined", "locked", "distinct", "frontier",
            "request_level")
    for name, wd in REGISTRY.items():
        have = ",".join(c for c in caps if getattr(wd, c)) or "-"
        desc = f"  {wd.description}" if wd.description else ""
        print(f"  {name}: {have}{desc}", file=file)


def main() -> None:
    # imports here so `-m benchmarks.run fig2` doesn't pay for jax
    import benchmarks.paper_figures as pf
    from benchmarks.kernel_micro import engine_driver

    args = sys.argv[1:]
    if "--engine" in args:
        i = args.index("--engine")
        if i + 1 >= len(args) or args[i + 1] not in ("scalar", "batched"):
            print("error: --engine requires a value: scalar | batched",
                  file=sys.stderr)
            raise SystemExit(2)
        pf.AMU = pf.AMU.derive(engine=args[i + 1])
        del args[i:i + 2]
    if "--vector" in args:
        pf.AMU = pf.AMU.derive(vector=True)
        args.remove("--vector")
    if "--sanitize" in args:
        # env var first: suites that build their own AmuConfig (the
        # engine-driver micro) pick the default up from AMU_SANITIZE
        os.environ["AMU_SANITIZE"] = "1"
        pf.AMU = pf.AMU.derive(sanitize=True)
        args.remove("--sanitize")
    smoke = "--smoke" in args
    if smoke:
        args.remove("--smoke")
    json_path = None
    if "--json" in args:
        i = args.index("--json")
        if i + 1 >= len(args):
            print("error: --json requires a path", file=sys.stderr)
            raise SystemExit(2)
        json_path = args[i + 1]
        del args[i:i + 2]
    profile_path = None
    if "--profile" in args:
        i = args.index("--profile")
        if i + 1 >= len(args):
            print("error: --profile requires a path", file=sys.stderr)
            raise SystemExit(2)
        profile_path = args[i + 1]
        del args[i:i + 2]
    profiler = None
    if profile_path:
        import cProfile
        profiler = cProfile.Profile()
        profiler.enable()

    suites = dict(pf.ALL_FIGURES)
    suites["engine"] = lambda: engine_driver(smoke=smoke)
    suites["serve"] = lambda: pf.serve_latency(smoke=smoke)
    suites["faults"] = lambda: pf.fault_tolerance(smoke=smoke)
    suites["rack"] = lambda: pf.rack_scaling(smoke=smoke)

    if "--list" in args:
        _print_catalog(suites)
        return

    # smoke mode: the (shrunken) engine-driver throughput, serving,
    # fault-injection and rack suites always run, so the regression gates
    # below can never be vacuously green
    if smoke:
        always = ("engine", "serve", "faults", "rack")
        wanted = list(always) + [a for a in args if a not in always]
    else:
        wanted = args or list(suites)
    collected = []
    print("name,us_per_call,derived")
    for name in wanted:
        if name not in suites:
            print(f"# unknown suite {name!r}; known sweeps and workloads:",
                  file=sys.stderr)
            _print_catalog(suites, file=sys.stderr)
            continue
        for row_name, us, derived in suites[name]():
            collected.append({"name": row_name, "us_per_call": us,
                              "derived": derived})
            print(f'{row_name},{us:.2f},"{derived}"', flush=True)

    if profiler is not None:
        profiler.disable()
        profiler.dump_stats(profile_path)
        print(f"# wrote cProfile stats to {profile_path} "
              f"(python -m pstats {profile_path})", file=sys.stderr)

    if json_path:
        with open(json_path, "w") as f:
            json.dump(collected, f, indent=1)
        print(f"# wrote {len(collected)} rows to {json_path}",
              file=sys.stderr)

    if smoke:
        failures = []
        for row in collected:
            sp = _parse_speedup(row["derived"], "speedup_vs_scalar")
            if sp and sp < SMOKE_MIN_BATCHED_SPEEDUP:
                failures.append(f"{row['name']}: batched/scalar {sp:.2f}x "
                                f"< {SMOKE_MIN_BATCHED_SPEEDUP}x")
            sp = _parse_speedup(row["derived"], "speedup_vs_scalar_yield")
            wl = row["name"].split("/")[-1].split("_")[0]
            floor = SMOKE_MIN_VECTOR_SPEEDUP.get(wl, SMOKE_MIN_VECTOR_DEFAULT)
            if sp and sp < floor:
                failures.append(f"{row['name']}: vector/scalar-yield "
                                f"{sp:.2f}x < {floor}x")
            sp = _parse_speedup(row["derived"], "ami_vs_sync")
            if sp and sp < SMOKE_MIN_SERVE_SPEEDUP:
                failures.append(f"{row['name']}: serving AMI/page-fault "
                                f"{sp:.2f}x < {SMOKE_MIN_SERVE_SPEEDUP}x")
            ceil = SMOKE_MAX_US_PER_ENTRY.get(row["name"])
            if ceil is not None:
                upe = _parse_speedup(row["derived"], "us_per_entry")
                if not upe or upe > ceil:
                    failures.append(f"{row['name']}: fused driver "
                                    f"{upe:.1f} µs/engine-entry > {ceil}")
                ents = _parse_speedup(row["derived"], "entries")
                if not ents or ents > SMOKE_MAX_ENTRIES[row["name"]]:
                    failures.append(
                        f"{row['name']}: {ents:.0f} engine entries > "
                        f"{SMOKE_MAX_ENTRIES[row['name']]} — epoch fusion "
                        f"degraded toward per-command granularity")
            if row["name"] == "rack/GUPS/cores4":
                sc = _parse_speedup(row["derived"], "scaling_vs_1core")
                if sc < SMOKE_MIN_RACK_SCALING:
                    failures.append(
                        f"{row['name']}: 4-core aggregate scaling "
                        f"{sc:.2f}x < {SMOKE_MIN_RACK_SCALING}x over one "
                        f"core at uncontended bandwidth")
                fa = _parse_speedup(row["derived"], "fairness")
                if fa < SMOKE_MIN_RACK_FAIRNESS:
                    failures.append(
                        f"{row['name']}: homogeneous Jain fairness "
                        f"{fa:.4f} < {SMOKE_MIN_RACK_FAIRNESS}")
            if row["name"].startswith("faults/") \
                    and row["name"].endswith("/retry_on"):
                sp = _parse_speedup(row["derived"], "vs_clean")
                if sp and sp > SMOKE_MAX_FAULT_SLOWDOWN:
                    failures.append(
                        f"{row['name']}: faulty/fault-free {sp:.2f}x > "
                        f"{SMOKE_MAX_FAULT_SLOWDOWN}x with retries on")
                av = _parse_speedup(row["derived"], "avail")
                if av and av < SMOKE_MIN_AVAILABILITY:
                    failures.append(
                        f"{row['name']}: availability {av:.4f} < "
                        f"{SMOKE_MIN_AVAILABILITY} with retries on")
        if failures:
            print("SMOKE FAIL: driver-throughput regression:",
                  file=sys.stderr)
            for msg in failures:
                print(f"  {msg}", file=sys.stderr)
            raise SystemExit(1)
        print("# smoke: driver-throughput floors held", file=sys.stderr)


if __name__ == "__main__":
    main()
