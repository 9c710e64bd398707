"""Kernels: device time of the latent (MLA) paged attention kernel per
execution of the prefill-chunk program: the walk of the chunk's rows, one call
a layer. The kernel is a Pallas call named ``mla_decode_paged`` (or
``mla_prefill_paged``, should the chunk's walk take a name of its own): the sum
of its executions that start inside the chunk program (device 0; the decode
program's calls of the same kernel are ``mla_attn_ms``) over the chunk
executions in the trace. A program without the kernel has no such operation
and the metric is left out."""
from benchmark import trace as T
from benchmark.layer_metrics.chunk_ms import PATTERN as CHUNK

KERNEL = r"^%mla_(decode|prefill)_paged[.\d]* = "


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    secs, n = T.op_time_within(tr, KERNEL, CHUNK)
    _, chunks = T.module_time_s(tr, CHUNK)
    return secs * 1e3 / chunks if n and chunks else None
