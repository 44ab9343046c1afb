"""A four-chip training cell (``fit_bank(mesh=)`` through the one-pass
driver), run whole on four virtual CPU devices in a child process: sound, it
is correct; with the exchange between chips left out (each shard keeps its
own bank, no fold), ``correct`` comes out false. The benchmark has no such
cell yet, so the test adds one to its copy as data: an entry and a limits
file. The child sets the device count before JAX starts, as a mesh test
must."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

from conftest import ROOT

CELL = "imagenet-fc7-ovr.train-4chip"

CHILD = textwrap.dedent('''
    import json, sys, time
    sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
    import jax
    import repro.core
    from benchmarks.chip import harness
    catalog = harness.Catalog(sys.argv[2] + "/BENCHMARK.json", sys.argv[2] + "/chip")
    real_fit = repro.core.fit_bank

    def shard_zero_only(X, Y, cs, mesh=None, **kw):
        first = lambda a: a.addressable_shards[0].data
        bank = real_fit(first(X), first(Y), first(cs), **kw)
        return jax.tree.map(lambda v: jax.device_put(
            v, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())), bank)

    out = {}
    for fault in (False, True):
        repro.core.fit_bank = shard_zero_only if fault else real_fit
        result, _, lines = harness.run_cell(
            catalog, sys.argv[3], seed=11, seconds=0.5,
            trace=False, t_start=time.perf_counter(), devices=jax.devices()[:4])
        out["fault" if fault else "sound"] = [result["correct"], lines]
    print(json.dumps(out))
''')


def add_four_chip_cell(catalog):
    spec = dict(catalog.spec, workloads=catalog.spec["workloads"] + [
        {"name": CELL, "config": "imagenet-fc7-ovr", "traffic": "one_pass",
         "chips": 4, "why": "test"}])
    (catalog.dir.parent / "BENCHMARK.json").write_text(json.dumps(spec))
    limits = catalog.dir / "limits"
    shutil.copy(limits / "covtype-ovr.train.json", limits / f"{CELL}.json")


def test_exchange_left_out_fails_the_check(tiny_catalog, tmp_path):
    add_four_chip_cell(tiny_catalog)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, str(ROOT), str(tiny_catalog.dir.parent),
         CELL],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"][0] is True, out["sound"][1]
    assert out["fault"][0] is False, out["fault"][1]
