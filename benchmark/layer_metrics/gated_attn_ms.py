"""Kernels: device time of the gated FULL-attention layers' decode walk per
decode token-step, in a configuration whose other layers are linear attention
(heads of 256, groups of 8 query heads over 2 KV heads): the Pallas calls
named ``gqa_decode_paged`` inside the decode program (``gqa_attn_ms``'s kernel
and reduction; that metric is the dense cells'). Left out by a configuration
without linear-attention layers."""
from benchmark.layer_metrics.gqa_attn_ms import read as gqa_attn_ms


def mine(run) -> bool:
    return "linear_num_value_heads" in run["cfg"]


def read(run):
    if not mine(run) \
            or "attn_full_keys" not in (run.get("counters_trace") or {}):
        return None
    return gqa_attn_ms(run)
