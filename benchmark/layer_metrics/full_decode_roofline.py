"""Kernels: the full-attention layers' decode walk as a share of its roofline:
the keys it had to attend (the program's counter ``attn_full_keys``: context
summed over live rows, inner steps and full layers) at their bytes and
operations (``benchmark/costs_window_moe.py``), over the device time of
``%gqa_decode_paged`` in the decode program."""
from benchmark.layer_metrics.gqa_attn_ms import KERNEL
from benchmark.layer_metrics.swa_decode_roofline import read as share


def read(run):
    return share(run, KERNEL, "attn_full_keys")
