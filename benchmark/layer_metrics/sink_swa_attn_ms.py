"""Kernels: device time of the sink-window layers' decode attention per decode
token-step. The windowed walk with a learned sink is a Pallas call named
``gqa_decode_paged_window_sink`` (its ``name=``; neither ``swa_attn_ms``'s
``%gqa_decode_paged_window.N`` nor ``gqa_attn_ms``'s ``%gqa_decode_paged.N``
matches it): the sum of its executions inside the decode program (device 0)
over the decode token-steps the program counted in the traced interval. A
program without the kernel has no such operation and the metric is left out."""
from benchmark.layer_metrics.mla_attn_ms import kernel_s

KERNEL = r"^%gqa_decode_paged_window_sink[.\d]* = "


def read(run):
    secs, n = kernel_s(run, KERNEL)
    steps = (run.get("counters_trace") or {}).get("decode_steps")
    return secs * 1e3 / steps if n and steps else None
