"""Kernels: device time of the 64-wide full-attention layers' chunk walk per
execution of the prefill-chunk program: the Pallas calls named
``gqa_prefill_paged`` that start inside the chunk program (device 0; one call
a full layer), summed, over the chunk executions in the trace. Left out by a
configuration without short-convolution layers, and by a program without the
kernel."""
from benchmark import trace as T
from benchmark.layer_metrics.chunk_ms import PATTERN as CHUNK
from benchmark.layer_metrics.whole_experts_ms import mine

KERNEL = r"^%gqa_prefill_paged[.\d]* = "


def read(run):
    tr = run["trace"]
    if tr is None or not mine(run):
        return None
    secs, n = T.op_time_within(tr, KERNEL, CHUNK)
    _, chunks = T.module_time_s(tr, CHUNK)
    return secs * 1e3 / chunks if n and chunks else None
