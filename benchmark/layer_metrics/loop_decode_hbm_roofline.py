"""Kernels / HBM: a LOOPED decoder's decode program as a share of its memory
roofline: this configuration's share of the whole step. The least bytes its
traced token-steps must read (``benchmark/costs_looped.py``: every walk's
layer weights, ``total_ut_steps`` x 4.933 GB, and the head, 0.201 GB, once a
token-step whatever the rows; plus the keys the rows attended, the program's
counter ``loop_plane_keys`` x 8,192 B) over the chip's peak bytes/s, divided
by the decode program's device time in the trace. Bound by memory: at 8 rows a
token-step does 8 FLOP a weight byte. A configuration without walks, and a
program without the counter, leave the metric out."""
from benchmark import costs_looped as C
from benchmark import trace as T
from benchmark.layer_metrics.decode_step_ms import PATTERN


def mine(run) -> bool:
    return "total_ut_steps" in run["cfg"]


def read(run):
    tr, c = run["trace"], run.get("counters_trace") or {}
    keys, steps = c.get("loop_plane_keys"), c.get("decode_steps")
    if tr is None or not mine(run) or not keys or not steps \
            or run.get("peaks") is None:
        return None
    secs, n = T.module_time_s(tr, PATTERN)
    if not n:
        return None
    least = C.decode_min_bytes(run["cfg"], steps, keys) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
