"""Operations and bytes, from shapes alone, of what a LOOPED dense decoder adds
(beside ``costs.py``): a token-step streams the layers' weights once a WALK
(``total_ut_steps`` times, the same bytes each time) and the head once, and
walks a cache plane a (walk, layer), counted in KEYS (the program's counter
``loop_plane_keys``: context summed over live rows, planes and inner steps) at
the bytes of a key in ONE plane."""

from __future__ import annotations

from benchmark.costs import _itemsize


def walks(cfg: dict) -> int:
    return cfg["total_ut_steps"]


def planes(cfg: dict) -> int:
    """Cache planes: one a (walk, layer): 4 x 48 = 192."""
    return walks(cfg) * cfg["num_hidden_layers"]


def layer_params(cfg: dict) -> int:
    """Parameters of one layer's seven matrices (q, k, v, o; gate, up, down):
    4 x 2,048 x 2,048 + 3 x 2,048 x 5,632 = 51,380,224 (the four norm weights
    are 8,192 more, float32, and are left out)."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    kvd = cfg["num_key_value_heads"] * cfg["head_dim"]
    return D * qd + 2 * D * kvd + qd * D + 3 * D * F


def walk_weight_bytes(cfg: dict) -> int:
    """Bytes of layer weights ONE walk of the stack reads: 48 x 51,380,224 x
    2 B = 4,932,501,504."""
    return cfg["num_hidden_layers"] * layer_params(cfg) * _itemsize(cfg)


def head_bytes(cfg: dict) -> int:
    """Bytes of the untied head: 2,048 x 49,152 x 2 B = 201,326,592 (the
    embedding is a gather of a few rows and is left out)."""
    return cfg["hidden_size"] * cfg["vocab_size"] * _itemsize(cfg)


def key_bytes(cfg: dict) -> int:
    """Bytes of K and V of every KV head for one key in ONE plane: 2 x 16 x
    128 x 2 B = 8,192."""
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * _itemsize(cfg))


def kv_bytes_per_token(cfg: dict) -> int:
    """Bytes a cached token holds over all planes: 192 x 8,192 = 1,572,864."""
    return planes(cfg) * key_bytes(cfg)


def walk_key_bytes(cfg: dict, plane_keys: int) -> int:
    """Bytes the decode walks must read for ``plane_keys`` attended keys
    (summed over live rows, planes and inner steps)."""
    return plane_keys * key_bytes(cfg)


def decode_min_bytes(cfg: dict, token_steps: int, plane_keys: int) -> int:
    """Least bytes ``token_steps`` decode token-steps read from HBM: every
    walk's layer weights and the head once a token-step (whatever the rows),
    and the keys their rows attended: 4 x 4.933 GB + 0.201 GB = 19.93 GB a
    token-step before a key is read."""
    return (token_steps * (walks(cfg) * walk_weight_bytes(cfg)
                           + head_bytes(cfg))
            + walk_key_bytes(cfg, plane_keys))


def chunk_min_bytes(cfg: dict, pages_walked: int, page_size: int) -> int:
    """Least bytes of one chunk execution: every walk's layer weights, the
    head (one row of it is used, all of it is read), and the pages its row
    blocks walked (``chunk_walk_pages`` counts a page once a row block and
    plane)."""
    return (walks(cfg) * walk_weight_bytes(cfg) + head_bytes(cfg)
            + pages_walked * page_size * key_bytes(cfg))


def chunk_flops(cfg: dict, rows: int, keys: int) -> int:
    """Operations of one chunk of ``rows`` rows that together attend ``keys``
    keys a plane: two a multiply-add in every walk's matrices (4 x 48 x
    51,380,224 x 2 = 19.7 GFLOP a row) and, a plane, a score and a weighted
    sum over the head's 128 for every query head."""
    per_key = planes(cfg) * cfg["num_attention_heads"] * cfg["head_dim"] * 4
    return (rows * walks(cfg) * cfg["num_hidden_layers"] * layer_params(cfg)
            * 2 + keys * per_key)


def walk_least_s(cfg: dict, plane_keys: int, peaks: dict) -> float:
    """Least time of the decode walks: 8,192 B against 16 x 128 x 4 = 8,192
    FLOP a key and plane (1 FLOP a byte): memory bounds it."""
    flops = plane_keys * cfg["num_attention_heads"] * cfg["head_dim"] * 4
    return max(walk_key_bytes(cfg, plane_keys) / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])
