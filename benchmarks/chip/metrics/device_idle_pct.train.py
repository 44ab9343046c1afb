"""Share of the traced training window in which no op ran on a device,
averaged over the cell's devices."""


def read(run):
    tr = run.trace
    if not tr.devices or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.mean_busy_s() / tr.window_s)
