"""Model programs: device time of the decode program per decode token-step.
Sum of the decode program's executions in the trace (device 0) over the decode
token-steps the program counted in the traced interval.

The programs carry no ``named_scope`` yet; the pattern is the name jit gives the
engine's decode closure today (``step``)."""
from benchmark import trace as T

PATTERN = r"^jit_step(\(|$)"


def read(run):
    tr, c = run["trace"], run["counters_trace"]
    if tr is None or not c.get("decode_steps"):
        return None
    secs, n = T.module_time_s(tr, PATTERN)
    return secs * 1e3 / c["decode_steps"] if n else None
