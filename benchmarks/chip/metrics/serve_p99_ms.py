"""99th percentile of request latency over all requests due in the traced
window (due time to last row answered, on the driver's own clock). The
untraced run prints the same percentile in its notes. Not an end-to-end
bound: a pause of about 110 ms on the host every 5-7 s, seen only once the
TPU runtime is loaded, sets it, so it swings between 10 and 110 ms from run
to run."""


def read(run):
    return run.counters.get("latency_p99_ms")
