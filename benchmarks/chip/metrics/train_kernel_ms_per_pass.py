"""Device time of the training engine per pass, averaged over the devices."""
from benchmarks.chip import names


def read(run):
    n = len(names.passes(run.trace))
    per = names.per_device(run.trace, names.is_engine)
    if not n or not any(per):
        return None
    return sum(per) / len(per) / n * 1e3
