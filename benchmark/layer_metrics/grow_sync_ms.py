"""Engine dispatch: host time from the chunk's return to the decode program
launched, a decode dispatch: the chunk's commit, page growth and preemption,
the limits and every decoding slot's table row (``phase_grow_s``), the slot
mirrors' upload after a control-plane change (``phase_sync_s``), the limits'
upload and the launch (``phase_dispatch_s``). Delta totals over the window /
delta ``phase_dispatch_s.count``. Left out where the program has no such
histograms."""

PARTS = ("phase_grow_s", "phase_sync_s", "phase_dispatch_s")


def read(run):
    c = run["counters_window"]
    n = c.get("phase_dispatch_s.count", 0)
    if not n:
        return None
    return sum(c[p + ".total"] for p in PARTS) * 1e3 / n
