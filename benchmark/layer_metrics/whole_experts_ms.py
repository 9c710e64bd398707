"""Kernels: device time of a WHOLE expert layer's grouped GEMMs per decode
token-step (all 64 experts of every sparse layer on this chip, each seeing the
rows its deployment sees), beside short-convolution layers (``moe_ffn_ms``'s
kernels and reduction; that metric lists the latent family's cell,
``held_experts_ms`` / ``ep_share_experts_ms`` / ``small_experts_ms`` the share
families of other keys). Left out by any other configuration."""
from benchmark.layer_metrics.moe_ffn_ms import read as moe_ffn_ms


def mine(run) -> bool:
    return "conv_L_cache" in run["cfg"]


def read(run):
    return moe_ffn_ms(run) if mine(run) else None
