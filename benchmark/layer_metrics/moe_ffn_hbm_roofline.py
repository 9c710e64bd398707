"""Kernels: the routed experts' share of their memory roofline in decode. A
decode token-step must read the three tables of every held expert that has a
row, once: ``moe_experts_touched`` (the program's counter: held experts with
at least one live row, summed over layers and inner steps) x 3 x hidden x
expert width x 2 bytes (``benchmark/costs_latent_moe.py``), over peak bytes/s,
divided by the grouped GEMMs' device time in the decode program. Bound by
memory: an expert sees a row or two a step."""
from benchmark import costs_latent_moe as C
from benchmark.layer_metrics.mla_attn_ms import kernel_s
from benchmark.layer_metrics.moe_ffn_ms import KERNELS


def read(run):
    touched = (run.get("counters_trace") or {}).get("moe_experts_touched")
    secs, n = kernel_s(run, KERNELS)
    if not touched or not n or run.get("peaks") is None:
        return None
    least = C.expert_stream_bytes(run["cfg"], touched) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
