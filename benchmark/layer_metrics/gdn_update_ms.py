"""Kernels: device time of the linear-attention layers' decode state update
per decode token-step. The update is a Pallas call named ``gdn_decode_update``
(its ``name=``; the trace shows ``%gdn_decode_update.N = ... custom-call``):
the sum of its executions inside the decode program (device 0) over the decode
token-steps the program counted in the traced interval. A program without the
kernel has no such operation and the metric is left out."""
from benchmark.layer_metrics.mla_attn_ms import kernel_s

KERNEL = r"^%gdn_decode_update[.\d]* = "


def read(run):
    secs, n = kernel_s(run, KERNEL)
    steps = (run.get("counters_trace") or {}).get("decode_steps")
    return secs * 1e3 / steps if n and steps else None
