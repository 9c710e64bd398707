"""Operations and bytes, from shapes alone, of what a configuration of
gated-delta-rule linear-attention layers, gated full-attention layers and a
share of small routed experts adds (beside ``costs.py``): the decode rows'
one-step state update counted in ROWS (the program's counter
``gdn_state_rows``: live rows summed over linear layers and inner steps), the
full layers' paged walk counted in KEYS (``attn_full_keys``), and the held
experts' weight stream counted in TOUCHED experts (``moe_experts_touched``)."""

from __future__ import annotations

from benchmark.costs import _itemsize

STATE_ITEMSIZE = 4          # the recurrent state is float32 (``assumed``)


def _mixer(cfg: dict) -> tuple[int, int, int]:
    """(state elements, conv-state elements, conv channels) of a row and
    linear layer."""
    H, K = cfg["linear_num_value_heads"], cfg["linear_key_head_dim"]
    V, Hk = cfg["linear_value_head_dim"], cfg["linear_num_key_heads"]
    channels = 2 * Hk * K + H * V
    return H * K * V, (cfg["linear_conv_kernel_dim"] - 1) * channels, channels


def update_row_bytes(cfg: dict) -> int:
    """Least bytes one live row's update moves in one linear layer: its state
    read and written back (2 x 2,097,152 B at 32 heads x 128 x 128 in
    float32), its conv rows read and written, its inputs (the row of ``[q; k;
    v]`` and the two scalars a head) and its output ``o`` in float32."""
    state, conv, channels = _mixer(cfg)
    H = cfg["linear_num_value_heads"]
    return (2 * state * STATE_ITEMSIZE + 2 * conv * _itemsize(cfg)
            + channels * _itemsize(cfg) + 2 * H * 4
            + H * cfg["linear_value_head_dim"] * 4)


def update_row_flops(cfg: dict) -> int:
    """Operations of the same update: per state element the two contractions
    (with k and with q: a multiply and an add each), the decay, and the
    rank-1 write (a multiply and an add): 7."""
    return 7 * _mixer(cfg)[0]


def update_least_s(cfg: dict, rows: int, peaks: dict) -> float:
    """Least time of ``rows`` updates on a chip with ``peaks``: 0.85 FLOP a
    byte, far under the v5e's ridge of 240: memory bounds it."""
    return max(rows * update_row_bytes(cfg) / peaks["hbm_bytes_per_s"],
               rows * update_row_flops(cfg) / peaks["bf16_flops_per_s"])


def walk_bytes(cfg: dict, keys: int) -> int:
    """Bytes a full layer's decode walk must read for ``keys`` attended keys
    (summed over rows, inner steps and full layers): K and V of every KV
    head, once: 2 x (256 + 256) x 2 B = 2,048 B a key."""
    return keys * cfg["num_key_value_heads"] * 2 * cfg["head_dim"] \
        * _itemsize(cfg)


def walk_flops(cfg: dict, keys: int) -> int:
    """Operations of the same walk: per query head a score and a weighted sum
    over ``head_dim``, two operations a multiply-add."""
    return keys * cfg["num_attention_heads"] * 2 * cfg["head_dim"] * 2


def walk_least_s(cfg: dict, keys: int, peaks: dict) -> float:
    """Least time of the walk: 2,048 B against 16,384 FLOP a key (8 FLOP a
    byte): memory bounds it."""
    return max(walk_bytes(cfg, keys) / peaks["hbm_bytes_per_s"],
               walk_flops(cfg, keys) / peaks["bf16_flops_per_s"])


def expert_stream_bytes(cfg: dict, experts_touched: int) -> int:
    """Bytes of expert tables a decode token-step must read: the gate, up and
    down tables (hidden x ``moe_intermediate_size``: 3 x 2048 x 512 x 2 B =
    6,291,456 B) of every held expert that has at least one row, once each."""
    return (experts_touched * 3 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"] * _itemsize(cfg))
