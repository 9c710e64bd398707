"""Kernels: device time of the decode rows' paged attention kernel (K/V pool)
per decode token-step. The kernel is a Pallas call named ``gqa_decode_paged``
(its ``name=`` since PR 29; the trace shows ``%gqa_decode_paged.N = ...
custom-call``): the sum of its executions inside the decode program (device 0)
over the decode token-steps the program counted in the traced interval. A
program whose kernel has no such name (every tree before PR 29) has no such
operation and the metric is left out."""
from benchmark.layer_metrics.mla_attn_ms import kernel_s

KERNEL = r"^%gqa_decode_paged[.\d]* = "


def read(run):
    secs, n = kernel_s(run, KERNEL)
    steps = (run.get("counters_trace") or {}).get("decode_steps")
    return secs * 1e3 / steps if n and steps else None
