"""Device time of the serving kernel per ``BankServer.step``."""
from benchmarks.chip import names


def read(run):
    n = len(names.steps(run.trace))
    per = names.per_device(run.trace, names.is_predict)
    if not n or not any(per):
        return None
    return sum(per) / len(per) / n * 1e3
