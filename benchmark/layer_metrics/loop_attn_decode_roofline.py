"""Kernels: a LOOPED decoder's decode walks as a share of their roofline: the
keys they had to attend (the program's counter ``loop_plane_keys``: context
summed over LIVE rows, the walks x layers planes and inner steps) at 8,192 B a
key and plane (``benchmark/costs_looped.py``; 1 FLOP a byte: memory bounds
it), over the device time of ``%gqa_decode_paged`` in the decode program."""
from benchmark import costs_looped as C
from benchmark.layer_metrics.gqa_attn_ms import KERNEL
from benchmark.layer_metrics.loop_decode_hbm_roofline import mine
from benchmark.layer_metrics.mla_attn_ms import kernel_s


def read(run):
    keys = (run.get("counters_trace") or {}).get("loop_plane_keys")
    secs, n = kernel_s(run, KERNEL)
    if not mine(run) or not keys or not n or run.get("peaks") is None:
        return None
    return 100.0 * C.walk_least_s(run["cfg"], keys, run["peaks"]) / secs
