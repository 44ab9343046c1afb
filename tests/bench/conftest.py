"""Fixtures for the chip benchmark's tests: the harness at tiny sizes on
the CPU (kernels in interpret mode), with the committed drivers, data
generators, metrics and limits."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

#: Tiny stand-ins for the cells' configurations and traffic.
TINY_CONFIGS = {
    "covtype-ovr": {"n_rows": 512,
                    "class_counts": [180, 150, 60, 22, 30, 34, 36]},
    "imagenet-fc7-ovr": {"n_rows": 512, "n_features": 128, "n_classes": 12,
                         "generator.count_ramp": 3},
}
TINY_TRAFFIC = {
    "serve_poisson": {"rate_rps": 150, "pool_rows": 256, "size_max": 40,
                      "check_requests": 400},
}


def _update(doc: dict, changes: dict) -> None:
    for key, value in changes.items():
        node = doc
        *path, last = key.split(".")
        for p in path:
            node = node[p]
        node[last] = value


@pytest.fixture
def tiny_catalog(tmp_path):
    """A copy of the benchmark with tiny sizes, and its Catalog."""
    from benchmarks.chip import harness

    tree = tmp_path / "chip"
    shutil.copytree(ROOT / "benchmarks" / "chip", tree,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for kind, table in (("configs", TINY_CONFIGS), ("traffic", TINY_TRAFFIC)):
        for name, changes in table.items():
            path = tree / kind / f"{name}.json"
            doc = json.loads(path.read_text())
            _update(doc, changes)
            path.write_text(json.dumps(doc))
    spec = tmp_path / "BENCHMARK.json"
    shutil.copy(ROOT / "BENCHMARK.json", spec)
    return harness.Catalog(spec, tree)


def run_tiny(catalog, workload, *, seed=7, seconds=0.5, trace=False,
             control=False):
    """One run of a cell through the harness on the CPU's first device."""
    import time

    import jax

    from benchmarks.chip import harness

    return harness.run_cell(catalog, workload, seed=seed, seconds=seconds,
                            trace=trace, t_start=time.perf_counter(),
                            devices=jax.devices()[:1], control=control)
