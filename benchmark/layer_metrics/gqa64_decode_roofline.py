"""Kernels: the 64-wide full-attention layers' decode walk as a share of its
roofline: the keys it had to attend (the program's counter ``attn_full_keys``:
context summed over LIVE rows, inner steps and full layers) at the PUBLISHED
2,048 B a key (``benchmark/costs_short_conv_moe.py``; memory bounds it; a pool
that padded 64 to 128 lanes would read twice that and show under its share),
over the device time of ``%gqa_decode_paged`` in the decode program."""
from benchmark import costs_short_conv_moe as C
from benchmark.layer_metrics.gqa_attn_ms import KERNEL
from benchmark.layer_metrics.mla_attn_ms import kernel_s
from benchmark.layer_metrics.whole_experts_ms import mine


def read(run):
    keys = (run.get("counters_trace") or {}).get("attn_full_keys")
    secs, n = kernel_s(run, KERNEL)
    if not mine(run) or not keys or not n or run.get("peaks") is None:
        return None
    return 100.0 * C.walk_least_s(run["cfg"], keys, run["peaks"]) / secs
