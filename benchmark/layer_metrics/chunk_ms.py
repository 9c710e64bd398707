"""Model programs: device time of one prefill-chunk program execution (device
0), mean over the executions in the trace. Pattern: the name jit gives the
engine's chunk closure today (``chunk``)."""
from benchmark import trace as T

PATTERN = r"^jit_chunk(\(|$)"


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    secs, n = T.module_time_s(tr, PATTERN)
    return secs * 1e3 / n if n else None
