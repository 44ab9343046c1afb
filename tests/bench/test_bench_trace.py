"""The trace reductions on small traces recorded on a v5e (trimmed), and
the reading of a raw profiler trace."""
import json
from pathlib import Path

import pytest

from benchmarks.chip import harness, names, xplane
from benchmarks.chip.peaks import peaks_for

FIXTURES = Path(__file__).parent / "fixtures"
CATALOG = harness.Catalog()


def load(name):
    return xplane.Trace.from_json(json.loads((FIXTURES / name).read_text()))


def read(metric, trace, counters, chips=1):
    run = harness.Run(workload={}, cfg={}, traffic={}, counters=counters,
                      trace=trace, peaks=peaks_for("TPU v5 lite"), chips=chips)
    return CATALOG.module("metrics", metric).read(run)


def test_names_read_by_hand_from_a_v5e_trace():
    tr = load("trace_covtype_train.json")
    ops = tr.ops("/device:TPU:0")
    kernels = {(e.module, e.name) for e in ops if e.kind == "tpu_custom_call"}
    assert kernels == {("jit_streamsvm_fit_many", "streamsvm_fit_many.1")}
    serve = load("trace_serve.json")
    kernels = {(e.module, e.name) for e in serve.ops("/device:TPU:0")
               if e.kind == "tpu_custom_call"}
    assert kernels == {("jit_predict_bank", "predict_bank.1")}
    four = load("trace_train_4chip.json")
    assert len(four.devices) == 4
    assert {e.module for p in four.devices for e in four.ops(p)
            if names.is_engine(e)} == {"jit__sharded_fits"}


def test_training_reductions_on_the_one_chip_trace():
    tr = load("trace_covtype_train.json")
    counters = {"n_rows": 581012, "n_models": 21, "n_features": 54,
                "stream_bytes": 4}
    ops = tr.ops("/device:TPU:0")
    assert len(names.passes(tr)) == 3
    engine = sum(e.dur for e in ops if e.name == "streamsvm_fit_many.1")
    other = sum(e.dur for e in ops if e.module == "jit_streamsvm_fit_many"
                and e.name != "streamsvm_fit_many.1")
    assert read("train_kernel_ms_per_pass", tr, counters) == pytest.approx(
        engine / 3 * 1e-6)
    assert read("train_prep_ms_per_pass", tr, counters) == pytest.approx(
        other / 3 * 1e-6)
    # hand-worked: 4*581012*21*54 ops at 197e12 vs 581012*(54*4+4)+2*21*54*4
    # bytes at 819e9; the bytes bound (0.156 ms) over a ~260 ms pass
    best = (581012 * (54 * 4 + 4) + 2 * 21 * 54 * 4) / 819e9
    assert read("train_kernel_roofline", tr, counters) == pytest.approx(
        100 * best / (engine / 3 * 1e-9))
    busy = xplane.union_ns(tr.devices["/device:TPU:0"]["XLA Ops"], *tr.window)
    assert read("device_idle_pct.train", tr, counters) == pytest.approx(
        100 * (1 - busy / (tr.window[1] - tr.window[0])))
    assert read("fold_ms_per_pass", tr, counters) >= 0


def test_fold_on_the_four_chip_trace():
    tr = load("trace_train_4chip.json")
    counters = {"n_rows": 786432, "n_models": 3000, "n_features": 4096,
                "stream_bytes": 4}
    gaps = []
    for span in names.passes(tr):
        last = max(e.end for p in tr.devices for e in tr.ops(p)
                   if names.is_engine(e) and span.start <= e.start < span.end)
        gaps.append(span.end - last)
    assert read("fold_ms_per_pass", tr, counters, chips=4) == pytest.approx(
        sum(gaps) / len(gaps) * 1e-6)
    share = read("train_kernel_roofline", tr, counters, chips=4)
    assert 0 < share <= 100
    per_dev = names.per_device(tr, names.is_engine)
    assert len(per_dev) == 4 and min(per_dev) > 0


def test_serving_reductions_on_the_serve_trace():
    tr = load("trace_serve.json")
    steps = names.steps(tr)
    kernel = [e for e in tr.ops("/device:TPU:0") if names.is_predict(e)]
    counters = {"slot_busy_rows": 40 * 100, "steps": len(steps),
                "n_models": 3000, "n_features": 4096, "out_bytes": 40,
                "query_bytes": 4, "slot_idle_rows": 40 * 156,
                "loadgen_late_ms_p99": 1.5, "latency_p99_ms": 12.5}
    assert read("predict_kernel_ms_per_step", tr, counters) == pytest.approx(
        sum(e.dur for e in kernel) / len(steps) * 1e-6)
    host = sum(s.dur for s in steps) - sum(
        e.dur for e in kernel if any(s.start <= e.start < s.end for s in steps))
    assert read("serve_host_ms_per_step", tr, counters) == pytest.approx(
        host / len(steps) * 1e-6)
    assert read("serve_slot_fill_pct", tr, counters) == pytest.approx(
        100 * 100 / 256)
    assert 0 < read("predict_kernel_roofline", tr, counters) <= 100
    assert read("loadgen_late_ms_p99", tr, counters) == 1.5
    assert read("serve_p99_ms", tr, counters) == 12.5
    gaps = xplane.idle_gaps(tr, "/device:TPU:0")
    assert gaps and all(g[1] > 0 for g in gaps)
    top = xplane.top_ops(tr)
    assert top[0][0] == "jit_predict_bank:predict_bank.1"


def test_a_reader_with_nothing_to_read_returns_none():
    tr = load("trace_serve.json")
    counters = {"n_rows": 1, "n_models": 1, "n_features": 1, "stream_bytes": 4}
    assert read("train_kernel_ms_per_pass", tr, counters) is None
    assert read("train_kernel_roofline", tr, counters) is None
    assert read("fold_ms_per_pass", tr, counters) is None


def test_union_and_gaps_by_hand():
    E = xplane.Event
    evs = [E("a", 0, 10), E("b", 5, 20), E("c", 30, 40)]
    assert xplane.union_ns(evs, 0, 100) == 30
    assert xplane.union_ns(evs, 8, 35) == 17
    tr = xplane.Trace(devices={"/device:TPU:0": {"XLA Ops": evs}},
                      spans=[E("serve.step", 18, 45)], window=(0, 50))
    assert xplane.idle_gaps(tr, "/device:TPU:0") == [
        ["serve.step", 10e-9], ["host:other", 10e-9]]


def test_a_raw_profiler_trace_reads(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with harness.Context.window():
        with jax.profiler.TraceAnnotation("bench.pass"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = xplane.Trace.from_xplane(tmp_path)
    assert tr.window_s > 0
    assert [s.name for s in tr.spans] == ["bench.window", "bench.pass"]
    assert len(names.passes(tr)) == 1
