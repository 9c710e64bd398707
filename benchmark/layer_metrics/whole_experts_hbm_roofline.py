"""Kernels: a whole expert layer's share of its roofline in decode. A decode
token-step must read the three tables of every expert that has a row, once:
``moe_experts_touched`` (the program's counter: TOUCHED experts of live rows,
never the table's height) x 3 x hidden x ``moe_intermediate_size`` x 2 bytes
(``benchmark/costs_short_conv_moe.py``) over peak bytes/s; the products of its
rows (``moe_local_rows`` x 6 x hidden x ``moe_intermediate_size``) over peak
FLOP/s are the smaller bound at 5 rows an expert, and the line printed beside
the result says so. The larger of the two, divided by the grouped GEMMs'
device time in the decode program."""
import json

from benchmark import costs_short_conv_moe as C
from benchmark.layer_metrics.mla_attn_ms import kernel_s
from benchmark.layer_metrics.moe_ffn_ms import KERNELS
from benchmark.layer_metrics.whole_experts_ms import mine


def read(run):
    c = run.get("counters_trace") or {}
    touched, rows = c.get("moe_experts_touched"), c.get("moe_local_rows")
    secs, n = kernel_s(run, KERNELS)
    if not mine(run) or not touched or not n or run.get("peaks") is None:
        return None
    by_bytes, by_flops = C.experts_least_s(run["cfg"], touched, rows or 0,
                                           run["peaks"])
    print(json.dumps({"whole_experts_roofline": {
        "hbm_share_pct": 100.0 * by_bytes / secs,
        "flop_share_pct": 100.0 * by_flops / secs,
        "bound_by": "memory" if by_bytes >= by_flops else "compute"}}),
        flush=True)
    return 100.0 * max(by_bytes, by_flops) / secs
