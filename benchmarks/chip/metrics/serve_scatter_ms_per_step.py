"""Host time per ``BankServer.step`` in its ``serve.scatter`` span: the
answers into their requests, the queue rebuild and ``ServerStats``."""
from benchmarks.chip import phases


def read(run):
    return phases.ms_per_step(run.trace, "serve.scatter")
