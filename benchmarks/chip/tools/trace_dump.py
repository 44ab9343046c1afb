#!/usr/bin/env python3
"""Run one cell with a trace and keep what the trace holds, for reading
device event names by hand and for recording a test fixture.

    python3 benchmarks/chip/tools/trace_dump.py --workload <cell> --seed <n> \
        --seconds <s> --out <dir>

Writes <dir>/summary.json (planes, lines, sample events with their stats),
<dir>/trace.json (the reduced trace of the window), <dir>/result.json and
<dir>/gaps.json (the five longest idle gaps of the first device, with every
host event that overlaps them).
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
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    from benchmarks.chip import harness, run, xplane

    catalog = harness.Catalog()
    devices = run.prepare(catalog.workload(args.workload)["chips"])
    if isinstance(devices, int):
        return devices
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    raw = out / "raw"
    result, notes, lines = harness.run_cell(
        catalog, args.workload, seed=args.seed, seconds=args.seconds,
        trace=True, t_start=T_START, devices=devices, keep_trace=str(raw))
    xplane.dump(raw, out / "summary.json")
    (out / "trace.json").write_text(json.dumps(
        xplane.Trace.from_xplane(raw).to_json()))
    (out / "result.json").write_text(json.dumps(result, indent=1))
    tr = xplane.Trace.from_xplane(raw)
    gaps = xplane._gaps(tr, next(iter(tr.devices)), 5) if tr.devices else []
    (out / "gaps.json").write_text(json.dumps({
        "gaps": gaps,
        "host": xplane.host_events_during(raw, [(s, t) for _, s, t in gaps]),
    }, indent=1))
    print("\n".join(notes + lines))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
