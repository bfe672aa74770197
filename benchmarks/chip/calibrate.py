"""Readings that the limits in `limits/<cell>.json` are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1 2 3

In one process: set-up once, then for each seed the cell's timed path at
the cell's own load (its driver's `calibrate`), compared with the plain
reference, and the control (the reference in the precision below the
configuration's) on the same inputs. Prints one JSON line per seed and a
summary: the largest program reading and the smallest control reading.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import run  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3,
                    help="read the control and faults on the first N seeds")
    args = ap.parse_args()

    spec = run.cell_spec(run.read_json(run.ROOT / "BENCHMARK.json"),
                         args.workload)
    devices = run.require_devices(spec.chips)
    from repro import configs
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    program_cfg = configs.get_config(spec.cfg_json["repro_config"])
    run.check_widths(program_cfg, spec.cfg_json)
    driver = run.load_module(run.HERE / "drivers"
                             / f"{spec.traffic['driver']}.py")
    reference = run.load_module(run.HERE / "reference"
                                / f"{spec.cfg_json['reference']}.py")
    state = driver.State(cfg_json=spec.cfg_json, traffic=spec.traffic,
                         seed=args.seeds[0], reference=reference,
                         program_cfg=program_cfg, devices=devices)
    driver.setup(state)
    rows = (driver.calibrate(state, args.seeds[:args.controls])
            + driver.calibrate(state, args.seeds[args.controls:],
                               controls=False))
    keys = {k for r in rows for k in r if k not in ("seed", "tokens")}
    summary = {k: {"max": max(r[k] for r in rows if k in r),
                   "min": min(r[k] for r in rows if k in r)}
               for k in sorted(keys)}
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "summary": summary}), flush=True)


if __name__ == "__main__":
    main()
