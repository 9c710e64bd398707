"""Kernels: the full-attention layers' decode walk as a share of its roofline,
at groups of 16 query heads and keys of 192: the keys it had to attend (the
program's counter ``attn_full_keys``: context summed over LIVE rows, inner
steps and full layers) at the full layers' own published bytes and operations
(``benchmark/costs_sink_window_moe.py``), over the device time of
``%gqa_decode_paged`` in the decode program."""
from benchmark.layer_metrics.gqa_attn_ms import KERNEL
from benchmark.layer_metrics.sink_swa_decode_roofline import read as share


def read(run):
    return share(run, KERNEL, "attn_full_keys", "full")
