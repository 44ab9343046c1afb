"""Host time per ``BankServer.step`` in its ``serve.copy_in`` span: the
packed buffer to the device (``jnp.asarray``)."""
from benchmarks.chip import phases


def read(run):
    return phases.ms_per_step(run.trace, "serve.copy_in")
