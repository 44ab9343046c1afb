"""The phases of ``BankServer.step``, read from the program's own spans.

``serve/bank_server.py`` records five consecutive host spans in each step
(``serve.pack``, ``serve.copy_in``, ``serve.launch``, ``serve.readback``,
``serve.scatter``), on the device trace's clock. A phase's metric is the
summed duration of its spans in the window over the number of the
benchmark's ``serve.step`` spans: its share of the host time a step costs.
"""
from benchmarks.chip import names


def ms_per_step(trace, span: str):
    """Milliseconds a step spends in ``span``; None where the trace holds no
    such span (a program without the phase spans)."""
    steps = names.steps(trace)
    spans = trace.spans_named(span)
    if not steps or not spans:
        return None
    return sum(s.dur for s in spans) / len(steps) * 1e-6
