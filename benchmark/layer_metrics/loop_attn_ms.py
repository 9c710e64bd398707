"""Kernels: device time of a LOOPED decoder's decode walks per decode
token-step: the Pallas calls named ``gqa_decode_paged`` inside the decode
program (``gqa_attn_ms``'s kernel and reduction, at a group of ONE query head
a KV head and a call a (walk, layer): 192 a token-step; that metric is the
dense cells'). Left out by any other configuration, and by a program without
the walks' counter."""
from benchmark.layer_metrics.gqa_attn_ms import read as gqa_attn_ms
from benchmark.layer_metrics.loop_decode_hbm_roofline import mine


def read(run):
    if not mine(run) \
            or "loop_plane_keys" not in (run.get("counters_trace") or {}):
        return None
    return gqa_attn_ms(run)
