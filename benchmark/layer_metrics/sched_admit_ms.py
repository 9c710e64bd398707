"""Host scheduler: host time of a step's admission phase (quota refill, TTL
expiry, the admissions with prefix adoption inside, the choice of the slot to
prefill), from the program's exact histogram ``phase_admit_s`` over the window:
delta total / delta count. A program without the phases has no such histogram
and the metric is left out."""


def read(run):
    c = run["counters_window"]
    n = c.get("phase_admit_s.count", 0)
    return c["phase_admit_s.total"] * 1e3 / n if n else None
