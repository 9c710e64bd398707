"""Operations and bytes, from shapes alone, of what a configuration with
window and full GQA attention and a share of routed experts adds (beside
``costs.py`` and ``costs_latent_moe.py``): a paged GQA walk counted in KEYS
(the program's counters ``attn_window_keys`` / ``attn_full_keys``), and the
held experts' weight stream from this family's keys."""

from __future__ import annotations

from benchmark.costs import _itemsize


def walk_bytes(cfg: dict, keys: int) -> int:
    """Bytes a GQA decode walk must read for ``keys`` attended keys (summed
    over rows, inner steps and layers): K and V of every KV head, once."""
    return (keys * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * _itemsize(cfg))


def walk_flops(cfg: dict, keys: int) -> int:
    """Operations of the same walk: per query head a score and a weighted
    sum over ``head_dim``, two operations a multiply-add."""
    return keys * cfg["num_attention_heads"] * cfg["head_dim"] * 2 * 2


def walk_least_s(cfg: dict, keys: int, peaks: dict) -> float:
    """Least time of the walk on a chip with ``peaks``. At 8 KV heads of 128
    under 128 query heads a key is 4,096 B against 65,536 FLOP, 16 FLOP a
    byte, far under the v5e's ridge of 240: memory bounds it."""
    return max(walk_bytes(cfg, keys) / peaks["hbm_bytes_per_s"],
               walk_flops(cfg, keys) / peaks["bf16_flops_per_s"])


def expert_stream_bytes(cfg: dict, experts_touched: int) -> int:
    """Bytes of expert tables a decode token-step must read: the gate, up and
    down tables (hidden x ``intermediate_size``: one expert's width here) of
    every held expert that has at least one row, once each."""
    return (experts_touched * 3 * cfg["hidden_size"]
            * cfg["intermediate_size"] * _itemsize(cfg))
