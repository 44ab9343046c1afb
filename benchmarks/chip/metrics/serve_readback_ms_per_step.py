"""Host time per ``BankServer.step`` in its ``serve.readback`` span: waiting
for the answers and copying them to the host (``np.asarray``)."""
from benchmarks.chip import phases


def read(run):
    return phases.ms_per_step(run.trace, "serve.readback")
