"""Operations and bytes, from shapes alone, of what a configuration of gated
short-convolution layers, GQA layers of narrow heads and a WHOLE layer of
routed experts adds (beside ``costs.py``): the experts' weight stream counted
in TOUCHED experts (the program's counter ``moe_experts_touched``) and their
products in ROWS (``moe_local_rows``: every pick), the full layers' paged walk
counted in KEYS (``attn_full_keys``) at the PUBLISHED bytes of a key whatever
the pool pads, and the conv layers' one-step update counted in ROWS
(``conv_state_rows``: live rows summed over conv layers and inner steps)."""

from __future__ import annotations

from benchmark.costs import _itemsize


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def expert_bytes(cfg: dict) -> int:
    """Bytes of one expert's gate, up and down tables: 3 x 2048 x 1536 x 2 B
    = 18,874,368 B."""
    return (3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * _itemsize(cfg))


def expert_stream_bytes(cfg: dict, experts_touched: int) -> int:
    """Bytes of expert tables a decode token-step must read: the three tables
    of every expert that has at least one row, once each."""
    return experts_touched * expert_bytes(cfg)


def expert_flops(cfg: dict, rows: int) -> int:
    """Operations of ``rows`` routed assignments (a row through one expert):
    three products of hidden x ``moe_intermediate_size``, two operations a
    multiply-add: 6 x 2048 x 1536."""
    return rows * 6 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def experts_least_s(cfg: dict, experts_touched: int, rows: int,
                    peaks: dict) -> tuple[float, float]:
    """(least time by bytes, least time by operations) of a whole expert
    layer's grouped GEMMs: at 5 rows an expert memory bounds it (10 FLOP a
    byte under the v5e's ridge of 240), at 128 the two come near."""
    return (expert_stream_bytes(cfg, experts_touched)
            / peaks["hbm_bytes_per_s"],
            expert_flops(cfg, rows) / peaks["bf16_flops_per_s"])


def walk_bytes(cfg: dict, keys: int) -> int:
    """Bytes a full layer's decode walk must read for ``keys`` attended keys
    (summed over rows, inner steps and full layers): K and V of every KV
    head, once, AS PUBLISHED: 8 x 2 x 64 x 2 B = 2,048 B a key. A pool that
    padded 64 to 128 lanes would read twice that and show under its share."""
    return keys * cfg["num_key_value_heads"] * 2 * head_dim(cfg) \
        * _itemsize(cfg)


def walk_flops(cfg: dict, keys: int) -> int:
    """Operations of the same walk: per query head a score and a weighted sum
    over the head's 64, two operations a multiply-add."""
    return keys * cfg["num_attention_heads"] * 2 * head_dim(cfg) * 2


def walk_least_s(cfg: dict, keys: int, peaks: dict) -> float:
    """Least time of the walk: 2,048 B against 8,192 FLOP a key (4 FLOP a
    byte): memory bounds it."""
    return max(walk_bytes(cfg, keys) / peaks["hbm_bytes_per_s"],
               walk_flops(cfg, keys) / peaks["bf16_flops_per_s"])


def conv_row_bytes(cfg: dict) -> int:
    """Least bytes one live row's one-step update moves in one conv layer: its
    carried rows read and written back (2 x 2 x 2,048 x 2 B), its row of ``[B;
    C; u]`` read and its gated output written (4 x 2,048 x 2 B): 32,768 B. The
    update is plain ``jnp`` (no kernel, so no roofline share of its own): at
    84 rows and 8 layers 22 MB a token-step beside 9.7 GB of experts."""
    D = cfg["hidden_size"]
    return (2 * (cfg["conv_L_cache"] - 1) + 4) * D * _itemsize(cfg)
