"""Kernels: the gated-delta-rule decode state update as a share of its memory
roofline. Least time = the rows whose state the decode program advanced (the
program's counter ``gdn_state_rows``: LIVE rows summed over linear layers and
inner steps, so a parked or frozen row adds nothing) at their bytes
(``benchmark/costs_linear_attn_moe.py``: a state read and written back; memory
bounds it), over the kernel's device time in the decode program."""
from benchmark import costs_linear_attn_moe as C
from benchmark.layer_metrics.gdn_update_ms import KERNEL
from benchmark.layer_metrics.mla_attn_ms import kernel_s


def read(run):
    rows = (run.get("counters_trace") or {}).get("gdn_state_rows")
    secs, n = kernel_s(run, KERNEL)
    if not rows or not n or run.get("peaks") is None:
        return None
    return 100.0 * C.update_least_s(run["cfg"], rows, run["peaks"]) / secs
