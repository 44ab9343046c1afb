"""Serving kernel's share of its roofline: the least time the rows it
answered could take (work.serve: answered rows, the bank once per step,
the answers) over the kernel's device time."""
from benchmarks.chip import names, work


def read(run):
    per = names.per_device(run.trace, names.is_predict)
    c = run.counters
    if not any(per) or not c["slot_busy_rows"]:
        return None
    ops, nbytes = work.serve(c["slot_busy_rows"], c["steps"], c["n_models"],
                             c["n_features"], c["out_bytes"],
                             c["query_bytes"])
    best, _ = work.roofline_s(ops, nbytes, run.peaks)
    return 100.0 * best / (sum(per) / len(per))
