"""The four-chip training cell ``imagenet-fc7-ovr-4chip.train``, run whole on
four virtual CPU devices in a child process (which sets the device count
before JAX starts), with its configuration shrunk here to 512 rows, D 128
and 12 classes as ``conftest.TINY_CONFIGS`` shrinks the others: sound, it is
correct; with the exchange between chips left out (shard 0's bank alone, no
fold), with each shard fitting only the first half of its range, or as the
control (the program's bf16 stream path), it is not. A trace of the sharded fit holds the fold's four spans in order. The
check's sample reaches every run of ``check_stride`` consecutive models."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np

from conftest import ROOT

from benchmarks.chip import harness

CELL = "imagenet-fc7-ovr-4chip.train"
TINY = {"n_rows": 512, "n_features": 128, "n_classes": 12}

CHILD = textwrap.dedent('''
    import glob, json, sys, tempfile, time
    sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
    import jax, jax.numpy as jnp
    import repro.core
    from jax.sharding import NamedSharding, PartitionSpec as P
    from benchmarks.chip import harness
    catalog = harness.Catalog(sys.argv[2] + "/BENCHMARK.json", sys.argv[2] + "/chip")
    real_fit = repro.core.fit_bank

    def no_exchange(X, Y, cs, mesh=None, **kw):
        first = lambda a: a.addressable_shards[0].data
        bank = real_fit(first(X), first(Y), first(cs), **kw)
        return jax.tree.map(lambda v: jax.device_put(v, NamedSharding(mesh, P())), bank)

    def half_stream(X, Y, cs, mesh=None, **kw):
        def first_halves(A, axis):
            parts = [jax.lax.slice_in_dim(s.data, 0, s.data.shape[axis] // 2,
                                          axis=axis)
                     for s in A.addressable_shards]
            shape = list(A.shape)
            shape[axis] = parts[0].shape[axis] * mesh.size
            return jax.make_array_from_single_device_arrays(
                tuple(shape), A.sharding, parts)
        return real_fit(first_halves(X, 0), first_halves(Y, 1), cs,
                        mesh=mesh, **kw)

    out = {}
    for name, fit, control in (
            ("sound", real_fit, False), ("no_exchange", no_exchange, False),
            ("half_stream", half_stream, False), ("control", real_fit, True)):
        repro.core.fit_bank = fit
        result, _, lines = harness.run_cell(
            catalog, sys.argv[3], seed=2**33 + 7, seconds=0.5,
            trace=False, t_start=time.perf_counter(), devices=jax.devices()[:4],
            control=control)
        out[name] = [result["correct"], lines]
    repro.core.fit_bank = real_fit

    mesh = jax.make_mesh((4,), ("data",))
    X = jax.random.normal(jax.random.key(0), (200, 128))
    Y = jnp.where(jax.random.normal(jax.random.key(1), (12, 200)) > 0, 1.0, -1.0)
    d = tempfile.mkdtemp()
    with jax.profiler.trace(d):
        jax.block_until_ready(real_fit(X, Y, 1.0, mesh=mesh))
    from jax.profiler import ProfileData
    path = sorted(glob.glob(d + "/**/*.xplane.pb", recursive=True))[-1]
    spans = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for p in ProfileData.from_file(path).planes
                   if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name.startswith("fit."))
    out["spans"] = spans
    print(json.dumps(out))
''')


def _shrink(catalog):
    path = catalog.dir / "configs" / "imagenet-fc7-ovr-4chip.json"
    cfg = json.loads(path.read_text())
    cfg.update(TINY)
    cfg["generator"]["count_ramp"] = 3
    path.write_text(json.dumps(cfg))


def test_cell_is_correct_and_its_faults_are_not(tiny_catalog):
    _shrink(tiny_catalog)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, str(ROOT), str(tiny_catalog.dir.parent),
         CELL],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"][0] is True, out["sound"][1]
    assert out["no_exchange"][0] is False, out["no_exchange"][1]
    assert out["half_stream"][0] is False, out["half_stream"][1]
    assert out["control"][0] is False, out["control"][1]
    names = [s[2] for s in out["spans"]]
    assert names == ["fit.shards", "fit.gather", "fit.fold", "fit.place"]
    for (_, end, _), (start, _, _) in zip(out["spans"], out["spans"][1:]):
        assert end <= start


def test_check_reaches_every_run_of_stride_models():
    catalog = harness.Catalog()
    wl = catalog.workload(CELL)
    cfg = catalog.config(wl["config"])
    traffic = catalog.traffic(wl["traffic"])
    stride = traffic["check_stride"]
    driver = catalog.module("drivers", traffic["driver"])
    starts = set()
    for seed in (1, 2, 3, 2**33 + 5, -9):
        ctx = harness.Context(workload=wl, cfg=cfg, traffic=traffic,
                              limits={}, seed=seed, devices=[None] * 4,
                              gen=None)
        models = driver.Cell(ctx).models()
        b = cfg["n_classes"] * len(cfg["c_grid"])
        assert len(models) in (b // stride, -(-b // stride))
        hit = np.zeros(b, bool)
        hit[models] = True
        runs = np.convolve(hit, np.ones(stride, int), mode="valid")
        assert runs.min() == 1 and runs.max() == 1  # every window, once
        starts.add(int(models[0]))
    assert len(starts) > 1  # the offset is drawn from the seed
