"""99th percentile of (submit time - due time) over the window's requests:
how late the one-thread load generator ran, so that a starved generator is
never read as a fast server."""


def read(run):
    return run.counters.get("loadgen_late_ms_p99")
