"""Kernels: device time of the held experts' grouped GEMMs per decode
token-step, for a share-holding expert layer whose configuration names its
sizes ``num_experts`` / ``intermediate_size`` (``moe_ffn_ms``'s kernels and
reduction; that metric lists the latent family's cell). Left out by a
configuration of other keys."""
from benchmark.layer_metrics.moe_ffn_ms import read as moe_ffn_ms


def mine(run) -> bool:
    return "num_experts" in run["cfg"] and "published" in run["cfg"]


def read(run):
    return moe_ffn_ms(run) if mine(run) else None
