"""Operations and bytes that the algorithm needs, from shapes alone, and the
table of peaks they are held against. A device kind that the table lacks is an
error, never a default."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to "
                       "benchmark/peaks.json with its source")
    return table[device_kind]


def _itemsize(cfg: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]]


def dense_weight_bytes(cfg: dict) -> int:
    """Bytes of weights one decode token-step of a DENSE model must read:
    every layer's projections and FFN, and the LM head (the embedding is a
    gather of a few rows and is left out)."""
    if cfg.get("num_local_experts"):
        raise ValueError("a sparse-expert step reads only the chosen "
                         "experts: not computable from shapes")
    D, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    dh = cfg.get("head_dim") or D // cfg["num_attention_heads"]
    qd, kvd = cfg["num_attention_heads"] * dh, cfg["num_key_value_heads"] * dh
    per_layer = D * qd + 2 * D * kvd + qd * D + 3 * D * F
    return (cfg["num_hidden_layers"] * per_layer + D * V) * _itemsize(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    """Bytes of keys and values one cached token holds over all layers."""
    dh = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * dh
            * _itemsize(cfg))


def decode_min_bytes(cfg: dict, token_steps: int, kv_token_reads: int) -> int:
    """Least bytes read from HBM by ``token_steps`` decode token-steps that
    together attend over ``kv_token_reads`` cached tokens."""
    return (token_steps * dense_weight_bytes(cfg)
            + kv_token_reads * kv_bytes_per_token(cfg))
