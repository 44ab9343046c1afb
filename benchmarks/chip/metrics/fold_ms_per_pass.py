"""Per pass, from the end of the last device's engine kernel to the end of
the pass (the folded bank ready on every device): the gather to the host,
the fold and the re-placement."""
from benchmarks.chip import names


def read(run):
    tr = run.trace
    gaps = []
    for span in names.passes(tr):
        ends = [e.end for p in tr.devices for e in tr.ops(p)
                if names.is_engine(e) and span.start <= e.start < span.end]
        if ends:
            gaps.append(span.end - max(ends))
    if not gaps:
        return None
    return sum(gaps) / len(gaps) * 1e-6
