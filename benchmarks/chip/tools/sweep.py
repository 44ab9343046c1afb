#!/usr/bin/env python3
"""Offer an open-loop serving cell's traffic at several rates, to find the
highest rate the server sustains (the knee) once, by hand.

    python3 benchmarks/chip/tools/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 2000 4000 8000

Sets the cell up once, then runs one window per rate and prints, per rate,
p50 and p99 latency, the p99 of each half of the window's requests, the
rows still queued when the last request was submitted, and how late the
generator ran. Below the knee the backlog stays near one step's rows and
the second half's p99 is no worse than the first's.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    from benchmarks.chip import harness, run

    catalog = harness.Catalog()
    wl = catalog.workload(args.workload)
    devices = run.prepare(wl["chips"])
    if isinstance(devices, int):
        return devices
    cfg = catalog.config(wl["config"])
    mix = dict(catalog.traffic(wl["traffic"]))
    ctx = harness.Context(workload=wl, cfg=cfg, traffic=mix,
                          limits=catalog.limits(args.workload),
                          seed=args.seed, devices=devices,
                          gen=catalog.module("data", cfg["generator"]["name"]))
    cell = catalog.module("drivers", mix["driver"]).Cell(ctx)
    cell.setup()
    print(f"setup_s {time.perf_counter() - T_START:.3f}", flush=True)
    for rate in args.rates:
        mix["rate_rps"] = rate
        w = cell.measure(args.seconds)
        c = w.counters
        row = {"rate_rps": rate, **w.e2e, "failed": w.failed,
               **{k: c[k] for k in ("latency_p99_ms", "p99_ms_first_half", "p99_ms_second_half",
                                    "backlog_rows_at_close",
                                    "loadgen_late_ms_p99", "steps",
                                    "slot_busy_rows", "elapsed_s",
                                    "gc_pauses", "gc_pause_ms_max",
                                    "steps_over_20ms")}}
        row["rows_per_s"] = c["rows_answered"] / args.seconds
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
