"""Kernels: device time of the full-attention layers' decode walk per decode
token-step at HEADS OF 64 (4 query heads a KV head, 8 KV heads, K and V of a
head side by side in one 128-lane pool row), in a configuration whose other
layers are short convolutions: the Pallas calls named ``gqa_decode_paged``
inside the decode program (``gqa_attn_ms``'s kernel and reduction; that metric
is the dense cells'). Left out by any other configuration."""
from benchmark.layer_metrics.gqa_attn_ms import read as gqa_attn_ms
from benchmark.layer_metrics.whole_experts_ms import mine


def read(run):
    if not mine(run) \
            or "attn_full_keys" not in (run.get("counters_trace") or {}):
        return None
    return gqa_attn_ms(run)
