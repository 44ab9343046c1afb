"""A configuration, traffic mix, driver, generator and per-layer metric
dropped into a benchmark directory are found by name, with no edit to
run.py or the harness."""
import json
import time

import numpy as np

from benchmarks.chip import harness

GEN = '''
import jax, jax.numpy as jnp
def prototypes(cfg, key):
    return jnp.zeros((cfg["n_classes"], cfg["n_features"]))
def stream(cfg, key, n_rows, sharding=None):
    X = jax.random.normal(key, (n_rows, cfg["n_features"]))
    return X, jnp.zeros((n_rows,), jnp.int32)
'''
DRIVER = '''
from benchmarks.chip import harness
class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
    def setup(self):
        self.X, _ = self.ctx.gen.stream(self.ctx.cfg, self.ctx.key(), 8)
    def measure(self, seconds):
        n = self.ctx.traffic["calls"]
        return harness.Window(e2e={"calls_per_s": n / seconds},
                              counters={"calls": n}, attempted=n)
    def check(self):
        return {"rows_seen": float(self.X.shape[0])}
'''
METRIC = '''
def read(run):
    return run.counters["calls"] * 2.0
'''


def test_new_files_are_found_by_name(tmp_path):
    for sub in ("configs", "traffic", "drivers", "data", "metrics", "limits"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / "toy-7.json").write_text(json.dumps(
        {"n_classes": 2, "n_features": 3, "generator": {"name": "toy_gen"}}))
    (tmp_path / "traffic" / "toy_mix.json").write_text(json.dumps(
        {"driver": "toy_driver", "calls": 5}))
    (tmp_path / "drivers" / "toy_driver.py").write_text(DRIVER)
    (tmp_path / "data" / "toy_gen.py").write_text(GEN)
    (tmp_path / "metrics" / "calls.double.py").write_text(METRIC)
    (tmp_path / "limits" / "toy-7.mix.json").write_text(json.dumps(
        {"limits": {"rows_seen": 8.0}}))
    spec = {
        "workloads": [{"name": "toy-7.mix", "config": "toy-7",
                       "traffic": "toy_mix", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "calls_per_s", "unit": "1/s", "better": "higher",
             "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "calls.double", "unit": "1", "better": "higher",
             "source": "program_counter", "layer": "toy",
             "moves": "calls_per_s"}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    catalog = harness.Catalog(tmp_path / "BENCHMARK.json", tmp_path)
    assert [m["name"] for m in catalog.per_layer("toy-7.mix")] == ["calls.double"]

    import jax

    result, _, lines = harness.run_cell(
        catalog, "toy-7.mix", seed=3, seconds=2.0, trace=False,
        t_start=time.perf_counter(), devices=jax.devices()[:1])
    assert result["correct"] is True
    assert result["metrics"]["calls_per_s"]["value"] == 2.5
    assert result["metrics"]["setup_s"]["unit"] == "s"
    assert list(result)[-1] == "checks"
    assert result["checks"] == {"rows_seen": {"value": 8.0, "limit": 8.0},
                                "failed": {"value": 0, "limit": 0}}
    assert lines[0] == "check rows_seen 8.0 limit 8.0"

    run = harness.Run(workload={}, cfg={}, traffic={}, counters={"calls": 5},
                      trace=None, peaks={}, chips=1)
    assert catalog.module("metrics", "calls.double").read(run) == 10.0


def test_the_committed_cells_resolve():
    catalog = harness.Catalog()
    for wl in catalog.spec["workloads"]:
        cfg = catalog.config(wl["config"])
        traffic = catalog.traffic(wl["traffic"])
        assert hasattr(catalog.module("drivers", traffic["driver"]), "Cell")
        gen = catalog.module("data", cfg["generator"]["name"])
        assert callable(gen.stream) and callable(gen.prototypes)
        assert catalog.limits(wl["name"])["limits"]
        names = {m["name"] for m in catalog.end_to_end(wl["name"])}
        assert "setup_s" in names and len(names) >= 2
        layer = catalog.per_layer(wl["name"])
        assert layer
        for m in layer:
            assert callable(catalog.module("metrics", m["name"]).read)
    counts = [np.sum(catalog.module("data", "relu_prototypes").class_counts(
        catalog.config("imagenet-fc7-ovr"), n)) for n in (512, 1281167)]
    assert counts == [512, 1281167]
