"""Kernels: device time of the held experts' grouped GEMMs per decode
token-step, for a share-holding expert layer whose configuration names its
sizes ``n_routed_experts`` / ``moe_intermediate_size`` beside a second
attention shape (``moe_ffn_ms``'s kernels and reduction; that metric lists the
latent family's cell, ``held_experts_ms`` the family of other keys). Left out
by any other configuration."""
from benchmark.layer_metrics.moe_ffn_ms import read as moe_ffn_ms


def mine(run) -> bool:
    cfg = run["cfg"]
    return "swa_num_key_value_heads" in cfg and "published" in cfg \
        and "moe_intermediate_size" in cfg


def read(run):
    return moe_ffn_ms(run) if mine(run) else None
