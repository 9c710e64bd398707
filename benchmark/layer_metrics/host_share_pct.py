"""Engine dispatch: share of the program's step time spent on the host, from
the totals of ``step_host_s`` and ``step_device_s`` over the window (the host
clock either side of the blocking token download)."""


def read(run):
    c = run["counters_window"]
    host, dev = c.get("step_host_s.total", 0.0), c.get("step_device_s.total", 0.0)
    return 100.0 * host / (host + dev) if host + dev > 0 else None
