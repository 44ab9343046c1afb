"""Training engine's share of its roofline: the least time one device's
share of a pass could take (work.train_pass on N / chips rows, at the
stream's dtype) over the engine's device time per pass on that device."""
from benchmarks.chip import names, work


def read(run):
    n = len(names.passes(run.trace))
    per = names.per_device(run.trace, names.is_engine)
    if not n or not any(per):
        return None
    c = run.counters
    ops, nbytes = work.train_pass(c["n_rows"] / run.chips, c["n_models"],
                                  c["n_features"], c["stream_bytes"])
    best, _ = work.roofline_s(ops, nbytes, run.peaks)
    return 100.0 * best / (sum(per) / len(per) / n)
