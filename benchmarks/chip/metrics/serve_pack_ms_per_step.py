"""Host time per ``BankServer.step`` in its ``serve.pack`` span: the
microbatch buffer and the FIFO loop that packs queued rows into it."""
from benchmarks.chip import phases


def read(run):
    return phases.ms_per_step(run.trace, "serve.pack")
