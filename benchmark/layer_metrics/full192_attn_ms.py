"""Kernels: device time of the FULL-attention layers' decode walk per decode
token-step, in a configuration whose full layers have a shape of their own (4
KV heads under 64 query heads, keys of 192 beside values of 128): the Pallas
calls named ``gqa_decode_paged`` inside the decode program (``gqa_attn_ms``'s
kernel and reduction; that metric is the dense cells'). Left out by a
configuration without the second shape."""
from benchmark.layer_metrics.gqa_attn_ms import read as gqa_attn_ms


def read(run):
    if "swa_num_key_value_heads" not in run["cfg"] \
            or "attn_full_keys" not in (run.get("counters_trace") or {}):
        return None
    return gqa_attn_ms(run)
