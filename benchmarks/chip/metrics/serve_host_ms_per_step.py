"""Host time per ``BankServer.step``: the benchmark's span around each
step, less the serving kernel's device time inside it, as the mean over
steps (packing, the host-to-device copy, dispatch, the blocking read of the
answers and the scatter)."""
from benchmarks.chip import names


def read(run):
    tr = run.trace
    steps = names.steps(tr)
    if not steps or not tr.devices:
        return None
    plane = next(iter(tr.devices))
    kernels = sorted((e for e in tr.ops(plane) if names.is_predict(e)),
                     key=lambda e: e.start)
    host, i = 0.0, 0
    for span in steps:
        dev = 0.0
        while i < len(kernels) and kernels[i].start < span.end:
            if kernels[i].start >= span.start:
                dev += kernels[i].dur
            i += 1
        host += span.dur - dev
    return host / len(steps) * 1e-6
