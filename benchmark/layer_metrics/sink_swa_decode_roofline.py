"""Kernels: the sink-window layers' decode walk as a share of its roofline.
Least time = the keys the walk had to attend (the program's counter
``attn_window_keys``: min(context, window) summed over LIVE rows, inner steps
and window layers) at the window layers' own bytes and operations
(``benchmark/costs_sink_window_moe.py``: 8 KV heads, keys of 192 and values of
128 as published; memory bounds it), over the kernel's device time in the
decode program. A row reads at most two pages: the share reads low, and what
it measures is launch and DMA latency."""
from benchmark import costs_sink_window_moe as C
from benchmark.layer_metrics.mla_attn_ms import kernel_s
from benchmark.layer_metrics.sink_swa_attn_ms import KERNEL

COUNTER, KIND = "attn_window_keys", "window"


def read(run, kernel=KERNEL, counter=COUNTER, kind=KIND):
    keys = (run.get("counters_trace") or {}).get(counter)
    secs, n = kernel_s(run, kernel)
    if not keys or not n or run.get("peaks") is None \
            or "swa_num_key_value_heads" not in run["cfg"]:
        return None
    return 100.0 * C.walk_least_s(run["cfg"], keys, kind, run["peaks"]) / secs
