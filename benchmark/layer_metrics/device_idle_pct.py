"""Device: share of the traced window in which no operation ran on the
device, mean over the cell's chips."""
from benchmark import trace as T


def read(run):
    tr = run["trace"]
    if tr is None or tr.t1_s <= tr.t0_s:
        return None
    return 100.0 * (1.0 - T.busy_s(tr) / (tr.t1_s - tr.t0_s))
