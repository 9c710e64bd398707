"""Kernels: device time of the WINDOW layers' decode attention per decode
token-step. The windowed walk is a Pallas call named
``gqa_decode_paged_window`` (its ``name=``; the trace shows
``%gqa_decode_paged_window.N = ... custom-call``, which the full layers'
``%gqa_decode_paged.N`` pattern of ``gqa_attn_ms`` does not match): the sum of
its executions inside the decode program (device 0) over the decode
token-steps the program counted in the traced interval. A program without the
kernel has no such operation and the metric is left out."""
from benchmark.layer_metrics.mla_attn_ms import kernel_s

KERNEL = r"^%gqa_decode_paged_window[.\d]* = "


def read(run):
    secs, n = kernel_s(run, KERNEL)
    steps = (run.get("counters_trace") or {}).get("decode_steps")
    return secs * 1e3 / steps if n and steps else None
