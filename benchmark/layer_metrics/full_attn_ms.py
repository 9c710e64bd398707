"""Kernels: device time of the FULL-attention layers' decode walk per decode
token-step, in a configuration that also has window layers: the Pallas calls
named ``gqa_decode_paged`` inside the decode program (``gqa_attn_ms``'s kernel
and reduction; that metric is the dense cells'), here at 16 query heads a KV
head and contexts of thousands of keys. Left out by a program that does not
count ``attn_full_keys`` (it has no second kind of layer to tell apart)."""
from benchmark.layer_metrics.gqa_attn_ms import read as gqa_attn_ms


def read(run):
    if "attn_full_keys" not in (run.get("counters_trace") or {}):
        return None
    return gqa_attn_ms(run)
