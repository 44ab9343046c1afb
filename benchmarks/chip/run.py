#!/usr/bin/env python3
"""Run one cell of the chip benchmark on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Sets up (on-device data from ``--seed``, compile or cache load, warm-up),
measures for ``--seconds``, checks what the window produced against the
plain float64 reference, and prints one JSON object as the last line of
standard output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics from a
profiler trace of the window with ``--trace 1``), ``device`` and, last,
``checks``: each number compared beside its limit. The same numbers are the
last lines of standard error.

Exits non-zero and prints no result when JAX finds no TPU, or fewer chips
than the cell asks for, or when the program under test (``src/repro``) is
not in the checkout. The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` if
set, else ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
# the TPU runtime logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def prepare(chips: int):
    """The first ``chips`` TPU devices, with the compile cache set; or the
    exit code when there is no TPU, too few chips, or no program."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU (JAX found {devices[0].platform}); nothing run",
              file=sys.stderr)
        return 3
    if len(devices) < chips:
        print(f"run.py: the cell needs {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 3
    try:
        from repro.kernels import ops
        from repro.runtime.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"run.py: the program under test is not in the checkout ({e})",
              file=sys.stderr)
        return 4
    use_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if ops.resolve_interpret(None):
        print("run.py: kernels would run in interpret mode", file=sys.stderr)
        return 4
    return devices[:chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness

    catalog = harness.Catalog()
    chips = catalog.workload(args.workload)["chips"]

    devices = prepare(chips)
    if isinstance(devices, int):
        return devices
    result, notes, lines = harness.run_cell(
        catalog, args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START, devices=devices)
    for line in notes:
        print(line, flush=True)
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
