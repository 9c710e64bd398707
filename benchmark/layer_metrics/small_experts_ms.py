"""Kernels: device time of the held experts' grouped GEMMs per decode
token-step, for a share of MANY SMALL experts (width 512: a held expert's
three tables are 6.3 MB) beside linear-attention layers (``moe_ffn_ms``'s
kernels and reduction; that metric lists the latent family's cell,
``held_experts_ms`` / ``ep_share_experts_ms`` the families of other keys).
Left out by any other configuration."""
from benchmark.layer_metrics.gated_attn_ms import mine
from benchmark.layer_metrics.moe_ffn_ms import read as moe_ffn_ms


def read(run):
    return moe_ffn_ms(run) if mine(run) else None
