"""Engine dispatch: host time after the decode program's tokens are on the
host, a decode dispatch: family counters, gauges, the commit loop, finishes
(``phase_reconcile_s``), then the closing per-token observations and the
checkpoint cadence (``phase_post_s``). Delta totals over the window / delta
``phase_reconcile_s.count``. Left out where the program has no such
histograms."""


def read(run):
    c = run["counters_window"]
    n = c.get("phase_reconcile_s.count", 0)
    if not n:
        return None
    return (c["phase_reconcile_s.total"] + c["phase_post_s.total"]) * 1e3 / n
