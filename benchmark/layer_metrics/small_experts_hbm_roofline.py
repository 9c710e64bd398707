"""Kernels: the small held experts' share of their memory roofline in decode.
A decode token-step must read the three tables of every held expert that has
a row, once: ``moe_experts_touched`` (the program's counter: TOUCHED experts
of live rows, never the table's height) x 3 x hidden x
``moe_intermediate_size`` x 2 bytes (``benchmark/costs_linear_attn_moe.py``),
over peak bytes/s, divided by the grouped GEMMs' device time in the decode
program. With 1-3 rows an expert the GEMMs' row blocks are mostly padding:
what the share loses to it shows here."""
from benchmark import costs_linear_attn_moe as C
from benchmark.layer_metrics.gated_attn_ms import mine
from benchmark.layer_metrics.mla_attn_ms import kernel_s
from benchmark.layer_metrics.moe_ffn_ms import KERNELS


def read(run):
    touched = (run.get("counters_trace") or {}).get("moe_experts_touched")
    secs, n = kernel_s(run, KERNELS)
    if not mine(run) or not touched or not n or run.get("peaks") is None:
        return None
    least = C.expert_stream_bytes(run["cfg"], touched) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
