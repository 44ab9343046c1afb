"""Share of the server's row slots that held a query row over the window:
ServerStats.slot_busy_rows / (slot_busy_rows + slot_idle_rows)."""


def read(run):
    c = run.counters
    total = c["slot_busy_rows"] + c["slot_idle_rows"]
    return 100.0 * c["slot_busy_rows"] / total if total else None
