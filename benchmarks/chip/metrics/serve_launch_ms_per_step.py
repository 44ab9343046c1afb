"""Host time per ``BankServer.step`` in its ``serve.launch`` span: the call
of the serving kernel, which returns before the device finishes."""
from benchmarks.chip import phases


def read(run):
    return phases.ms_per_step(run.trace, "serve.launch")
