"""The readers of the bank server's phase spans, on a hand-built trace and
on the recorded serving trace, which holds no program span."""
import json
from pathlib import Path

import pytest

from benchmarks.chip import harness, xplane
from benchmarks.chip.peaks import peaks_for

FIXTURES = Path(__file__).parent / "fixtures"
CATALOG = harness.Catalog()
PHASES = ("pack", "copy_in", "launch", "readback", "scatter")


def read(metric, trace):
    run = harness.Run(workload={}, cfg={}, traffic={}, counters={},
                      trace=trace, peaks=peaks_for("TPU v5 lite"), chips=1)
    return CATALOG.module("metrics", metric).read(run)


def by_hand():
    """Three steps in a window from 0 to 100 us, the phases of each lasting
    1, 2, 3, 4, 5 us after a 1 us head; a fourth step starts after the
    window closes and does not count."""
    spans = [["bench.window", 0, 100_000, "", ""]]
    for start in (10_000, 40_000, 70_000, 120_000):
        spans.append(["serve.step", start, start + 17_000, "", ""])
        t = start + 1_000
        for i, phase in enumerate(PHASES, 1):
            spans.append([f"serve.{phase}", t, t + i * 1_000, "", ""])
            t += i * 1_000
    return xplane.Trace.from_json({"devices": {}, "spans": spans})


@pytest.mark.parametrize("i, phase", enumerate(PHASES, 1))
def test_a_phase_reads_its_time_per_step(i, phase):
    assert read(f"serve_{phase}_ms_per_step", by_hand()) == pytest.approx(
        i * 1e-3)


@pytest.mark.parametrize("phase", PHASES)
def test_a_phase_reads_none_without_program_spans(phase):
    tr = xplane.Trace.from_json(
        json.loads((FIXTURES / "trace_serve.json").read_text()))
    assert tr.spans_named("serve.step")
    assert read(f"serve_{phase}_ms_per_step", tr) is None


def test_the_phases_are_metrics_of_the_serving_cell():
    layer = {m["name"]: m for m in CATALOG.per_layer("imagenet-fc7-ovr.serve")}
    for phase in PHASES:
        m = layer[f"serve_{phase}_ms_per_step"]
        assert (m["layer"], m["moves"], m["source"]) == (
            "bank server", "serve_p50_ms", "device_trace")
    assert not any(m["name"].startswith("serve_")
                   for m in CATALOG.per_layer("covtype-ovr.train"))
