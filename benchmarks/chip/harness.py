"""The chip benchmark's core: find each piece by name, run one cell, and
build the result line.

Every configuration, traffic mix, driver, data generator, per-layer metric
and set of limits is a file of its own, found by the name that
``BENCHMARK.json`` or another such file gives:

    configs/<config>.json     sizes, source, cuts, guarantees, generator
    traffic/<traffic>.json    the mix's parameters; ``driver`` names its driver
    drivers/<driver>.py       ``Cell``: set-up, the measured window, the check
    data/<generator>.py       ``stream`` and ``prototypes``, made on the device
    metrics/<metric>.py       ``read(run)``: one per-layer metric, or None
    limits/<workload>.json    the limit of each number the check compares

So a new cell, mix or metric is a new file and a ``BENCHMARK.json`` entry.
"""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import SimpleNamespace

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class Catalog:
    """Finds the pieces of the benchmark by name."""

    def __init__(self, spec_path=ROOT / "BENCHMARK.json", bench_dir=HERE):
        self.spec = json.loads(Path(spec_path).read_text())
        self.dir = Path(bench_dir)

    def workload(self, name: str) -> dict:
        for wl in self.spec["workloads"]:
            if wl["name"] == name:
                return wl
        known = [wl["name"] for wl in self.spec["workloads"]]
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")

    def _json(self, kind: str, name: str) -> dict:
        return json.loads((self.dir / kind / f"{name}.json").read_text())

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def limits(self, workload: str) -> dict:
        return self._json("limits", workload)

    def module(self, kind: str, name: str):
        """Load ``<kind>/<name>.py`` (names may hold dots and dashes)."""
        path = self.dir / kind / f"{name}.py"
        mod_name = f"_chipbench.{kind}.{name}".replace("-", "_")
        mod = sys.modules.get(mod_name)
        if mod is not None and getattr(mod, "__file__", None) == str(path):
            return mod
        spec = importlib.util.spec_from_file_location(mod_name, path)
        if spec is None:
            raise FileNotFoundError(path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
        return mod

    def end_to_end(self, workload: str) -> list[dict]:
        return [m for m in self.spec["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> list[dict]:
        e2e = {m["name"] for m in self.end_to_end(workload)}
        return [m for m in self.spec["per_layer"]
                if workload in m.get("workloads", [workload])
                and m["moves"] in e2e]


# --- what a driver gets and returns ---------------------------------------------


def seed_key(seed: int):
    """A JAX key from any whole number, 31 bits at a time."""
    import jax

    key = jax.random.key(1 if seed < 0 else 0)
    s = abs(int(seed))
    while True:
        key = jax.random.fold_in(key, s & 0x7FFFFFFF)
        s >>= 31
        if not s:
            return key


def seed_rng(seed: int, stream: int = 0) -> np.random.Generator:
    """A numpy generator from any whole number; ``stream`` separates uses."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0), stream])


@dataclass
class Context:
    """What a driver's ``Cell`` is built from."""

    workload: dict
    cfg: dict
    traffic: dict
    limits: dict
    seed: int
    devices: list
    gen: object
    #: run the control: the computation in bf16, one precision below the
    #: configurations' f32 (each driver says how)
    control: bool = False

    @property
    def chips(self) -> int:
        return len(self.devices)

    def key(self):
        return seed_key(self.seed)

    @staticmethod
    def window():
        """The span a driver opens around its timed loop, and nothing else:
        the traced reductions are cut to it."""
        import jax

        from benchmarks.chip import xplane

        return jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)

    def rng(self, stream: int = 0) -> np.random.Generator:
        return seed_rng(self.seed, stream)

    def mesh(self):
        import jax

        return jax.make_mesh((self.chips,), ("data",), devices=self.devices)

    def stream_sharding(self):
        """Where the stream (rows) and its labels live: split over the
        cell's chips along rows, or whole on its one chip."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self.chips == 1:
            one = jax.sharding.SingleDeviceSharding(self.devices[0])
            return SimpleNamespace(x=one, labels=one, signs=one)
        mesh = self.mesh()
        return SimpleNamespace(x=NamedSharding(mesh, P("data")),
                               labels=NamedSharding(mesh, P("data")),
                               signs=NamedSharding(mesh, P(None, "data")))


def ovr_signs(labels, n_classes: int, c_grid, sharding):
    """One-vs-rest sign rows per C point, class-major within each group, and
    the (B,) Cs: the flattening ``fit_bank`` and the ovr server expect."""
    import jax
    import jax.numpy as jnp

    def signs(labels):
        one = jnp.where(labels[None, :] == jnp.arange(n_classes)[:, None],
                        1.0, -1.0).astype(jnp.float32)
        return jnp.tile(one, (len(c_grid), 1))

    Y = jax.jit(signs, out_shardings=sharding.signs)(labels)
    cs = jnp.repeat(jnp.asarray(c_grid, jnp.float32), n_classes)
    return Y, cs


def ovr_signs_host(labels, classes, c_of_model):
    """Host sign rows for the given models (model = group * K + class)."""
    labels = np.asarray(labels)
    return np.where(labels[None, :] == np.asarray(classes)[:, None],
                    np.float32(1), np.float32(-1)), np.asarray(c_of_model)


@dataclass
class Window:
    """What a measured window gives: the end-to-end metrics, the counters
    the per-layer metrics read, and the request count."""

    e2e: dict
    counters: dict
    attempted: int
    failed: int = 0
    notes: list = field(default_factory=list)


@dataclass
class Run:
    """What a per-layer metric's ``read`` gets."""

    workload: dict
    cfg: dict
    traffic: dict
    counters: dict
    trace: object  # xplane.Trace, or None in an untraced run
    peaks: dict
    chips: int


# --- one run ----------------------------------------------------------------------


def run_cell(catalog: Catalog, name: str, *, seed: int, seconds: float,
             trace: bool, t_start: float, devices: list,
             control: bool = False,
             keep_trace: str | None = None) -> tuple[dict, list[str], list[str]]:
    """Set up, measure, check. Returns the result line's object, the
    driver's notes for standard output, and the lines for standard error
    (each compared number beside its limit). ``keep_trace`` names a
    directory to trace into and keep, for reading a trace by hand."""
    import jax

    from benchmarks.chip import xplane
    from benchmarks.chip.peaks import peaks_for

    wl = catalog.workload(name)
    cfg = catalog.config(wl["config"])
    traffic = catalog.traffic(wl["traffic"])
    limits = catalog.limits(name)
    ctx = Context(workload=wl, cfg=cfg, traffic=traffic, limits=limits,
                  seed=seed, devices=devices,
                  gen=catalog.module("data", cfg["generator"]["name"]),
                  control=control)
    cell = catalog.module("drivers", traffic["driver"]).Cell(ctx)
    cell.setup()
    setup_s = time.perf_counter() - t_start

    trace_dir = keep_trace or (
        tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None)
    try:
        if trace:
            jax.profiler.start_trace(trace_dir)
        window = cell.measure(seconds)
        if trace:
            jax.profiler.stop_trace()
        tr = xplane.Trace.from_xplane(trace_dir) if trace else None
    finally:
        if trace_dir and not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)

    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in devices)
    t_check = time.perf_counter()
    readings = cell.check()
    window.notes.append(f"check took {time.perf_counter() - t_check:.3f} s")

    checks, lines = {}, []
    for key, value in readings.items():
        if key in limits["limits"]:
            checks[key] = {"value": value, "limit": limits["limits"][key]}
            lines.append(f"check {key} {value!r} limit {limits['limits'][key]!r}")
        else:
            lines.append(f"reading {key} {value!r} (not compared)")
    missing = sorted(set(limits["limits"]) - set(readings))
    if missing:
        lines.append(f"limits name numbers the check did not read: {missing}")
    checks["failed"] = {"value": int(window.failed), "limit": 0}
    lines.append(f"check failed {window.failed} limit 0 "
                 f"(of {window.attempted} attempted)")
    ok = not missing and all(c["value"] <= c["limit"]
                             for c in checks.values())

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": bool(ok), "attempted": int(window.attempted),
              "failed": int(window.failed)}
    if trace:
        run = Run(workload=wl, cfg=cfg, traffic=traffic,
                  counters=window.counters, trace=tr,
                  peaks=peaks_for(dev.device_kind), chips=len(devices))
        metrics = {}
        for m in catalog.per_layer(name):
            value = catalog.module("metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device.update(busy_s=tr.mean_busy_s(), window_s=tr.window_s)
        result.update(metrics=metrics, device=device, breakdown={
            "device_ops": xplane.top_ops(tr),
            "idle_gaps": (xplane.idle_gaps(tr, next(iter(tr.devices)))
                          if tr.devices else []),
        })
    else:
        metrics = {m["name"]: {"value": float(window.e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in catalog.end_to_end(name) if m["name"] in window.e2e}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        result.update(metrics=metrics, device=device)
    result["checks"] = checks
    return result, window.notes, lines
