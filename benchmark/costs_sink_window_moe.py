"""Operations and bytes, from shapes alone, of what a configuration with
sink-window and full GQA layers of DIFFERENT shapes and a share of routed
experts adds (beside ``costs.py`` and ``costs_window_moe.py``): a paged GQA
walk counted in KEYS (the program's counters ``attn_window_keys`` /
``attn_full_keys``) at its KIND's head count and at keys and values of their
own widths, and the held experts' weight stream from this family's keys. The
bytes are the PUBLISHED ones (a key of ``head_dim`` 192, whatever lanes the
pool pads it to): padding shows as a lower share, never a higher one."""

from __future__ import annotations

from benchmark.costs import _itemsize

KV_HEADS = {"window": "swa_num_key_value_heads", "full": "num_key_value_heads"}


def walk_bytes(cfg: dict, keys: int, kind: str) -> int:
    """Bytes a decode walk of ``kind`` layers must read for ``keys`` attended
    keys (summed over rows, inner steps and layers): K (``head_dim``) and V
    (``v_head_dim``) of every KV head of that kind, once."""
    return (keys * cfg[KV_HEADS[kind]] * (cfg["head_dim"] + cfg["v_head_dim"])
            * _itemsize(cfg))


def walk_flops(cfg: dict, keys: int) -> int:
    """Operations of the same walk: per query head a score over ``head_dim``
    and a weighted sum over ``v_head_dim``, two operations a multiply-add."""
    return (keys * cfg["num_attention_heads"]
            * (cfg["head_dim"] + cfg["v_head_dim"]) * 2)


def walk_least_s(cfg: dict, keys: int, kind: str, peaks: dict) -> float:
    """Least time of the walk on a chip with ``peaks``. A full layer's key is
    2,560 B against 40,960 FLOP (16 FLOP a byte), a window layer's 5,120 B
    (8): far under the v5e's ridge of 240, memory bounds both."""
    return max(walk_bytes(cfg, keys, kind) / peaks["hbm_bytes_per_s"],
               walk_flops(cfg, keys) / peaks["bf16_flops_per_s"])


def expert_stream_bytes(cfg: dict, experts_touched: int) -> int:
    """Bytes of expert tables a decode token-step must read: the gate, up and
    down tables (hidden x ``moe_intermediate_size``) of every held expert
    that has at least one row, once each."""
    return (experts_touched * 3 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"] * _itemsize(cfg))
