"""Kernels: the window layers' decode walk as a share of its roofline. Least
time = the keys the walk had to attend (the program's counter
``attn_window_keys``: min(context, window) summed over live rows, inner steps
and window layers) at their bytes and operations
(``benchmark/costs_window_moe.py``: memory bounds it), over the windowed
kernel's device time in the decode program."""
from benchmark import costs_window_moe as C
from benchmark.layer_metrics.mla_attn_ms import kernel_s
from benchmark.layer_metrics.swa_attn_ms import KERNEL

COUNTER = "attn_window_keys"


def read(run, kernel=KERNEL, counter=COUNTER):
    keys = (run.get("counters_trace") or {}).get(counter)
    secs, n = kernel_s(run, kernel)
    if not keys or not n or run.get("peaks") is None:
        return None
    return 100.0 * C.walk_least_s(run["cfg"], keys, run["peaks"]) / secs
