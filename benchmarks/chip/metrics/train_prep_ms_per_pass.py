"""Device time per pass of every op of the fit's program that is not the
engine kernel (the X[1:] slice, lane and row pads, casts; on a mesh also the
all-gather of the per-shard banks), averaged over the devices."""
from benchmarks.chip import names


def read(run):
    n = len(names.passes(run.trace))
    per = names.per_device(
        run.trace, lambda e: names.is_train(e) and not names.is_kernel(e))
    if not n or not any(names.per_device(run.trace, names.is_engine)):
        return None
    return sum(per) / len(per) / n * 1e3
