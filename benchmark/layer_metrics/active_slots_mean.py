"""Scheduler: decoding slots per decode dispatch over the window, from the
program's ``active_slots`` total and count (exact sums, not percentiles)."""


def read(run):
    c = run["counters_window"]
    n = c.get("active_slots.count", 0)
    return c["active_slots.total"] / n if n else None
