#!/usr/bin/env python3
"""Read the numbers a cell's check compares, over many seeds, for the
program as the configuration states it and for the control (the program's
bf16 path), in one process: the readings the cell's limits are set from.

    python3 benchmarks/chip/tools/readings.py --workload <cell> \
        --seconds <s> --seeds 1 2 3 ... --control-seeds 101 102 103

Each run is a whole run of the cell through the harness (set-up, a window
of ``--seconds`` at the cell's own load, the check); one JSON line per run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args()
    from benchmarks.chip import harness, run

    catalog = harness.Catalog()
    devices = run.prepare(catalog.workload(args.workload)["chips"])
    if isinstance(devices, int):
        return devices
    runs = [(s, False) for s in args.seeds] + [(s, True) for s in args.control_seeds]
    for seed, control in runs:
        t0 = time.perf_counter()
        try:
            result, notes, lines = harness.run_cell(
                catalog, args.workload, seed=seed, seconds=args.seconds,
                trace=False, t_start=t0, devices=devices, control=control)
        except Exception as e:  # a control that crashes has failed
            print(json.dumps({"seed": seed, "control": control,
                              "error": repr(e)[:500]}), flush=True)
            continue
        print(json.dumps({
            "seed": seed, "control": control, "correct": result["correct"],
            "checks": {k: v["value"] for k, v in result["checks"].items()},
            "readings": [ln for ln in lines if ln.startswith("reading")],
            "notes": notes,
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "wall_s": time.perf_counter() - t0,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
