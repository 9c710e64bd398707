"""Kernels: device time of the routed experts per decode token-step. The
routed-expert scope of a sparse layer (``moe_routed_experts``) is the grouped
GEMMs over the held experts' tables: Pallas calls named ``grouped_gemm_gated``
(gate and up, fused) and ``grouped_gemm`` (down). A trace event carries its HLO
instruction and no scope, so the scope is read by its kernels' names; the
small gathers and the weighting around them are XLA fusions and are left out.
Sum inside the decode program (device 0) over the decode token-steps the
program counted in the traced interval. A program without such kernels in its
decode program reports nothing."""
from benchmark.layer_metrics.mla_attn_ms import kernel_s

KERNELS = r"^%grouped_gemm(_gated)?[.\d]* = "


def read(run):
    secs, n = kernel_s(run, KERNELS)
    steps = (run.get("counters_trace") or {}).get("decode_steps")
    return secs * 1e3 / steps if n and steps else None
