"""Operations and bytes, from shapes alone, of the two kernels a
latent-attention, routed-expert configuration adds (beside ``costs.py``, which
counts a dense decoder's weight and K/V streams)."""

from __future__ import annotations

from benchmark.costs import _itemsize


def latent_read_bytes(cfg: dict, cached_tokens: int) -> int:
    """Bytes the latent decode kernel must read for ``cached_tokens`` cached
    rows (summed over rows, inner steps and layers): each is read once, at the
    width it is STORED in (``cache.stored_width``, lane padding included:
    the kernel cannot read less of a row)."""
    return cached_tokens * cfg["cache"]["stored_width"] * _itemsize(cfg)


def latent_attend_flops(cfg: dict, cached_tokens: int) -> int:
    """Operations of absorbed latent attention over ``cached_tokens`` cached
    rows: per head a score over c and the rope key (kv_lora_rank +
    qk_rope_head_dim values) and a weighted sum of c (kv_lora_rank), two
    operations a multiply-add. The lane padding does no useful work and is
    not counted."""
    per = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"] + cfg["kv_lora_rank"]
    return cached_tokens * cfg["num_attention_heads"] * per * 2


def expert_stream_bytes(cfg: dict, experts_touched: int) -> int:
    """Bytes of expert tables a decode token-step must read: the gate, up and
    down tables of every held expert that has at least one row, once each."""
    return (experts_touched * 3 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"] * _itemsize(cfg))


def sparse_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
