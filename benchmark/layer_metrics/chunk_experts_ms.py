"""Kernels: device time of the routed experts per execution of the
prefill-chunk program: the Pallas calls named ``grouped_gemm_gated`` (gate and
up, fused) and ``grouped_gemm`` (down) that start inside the chunk program
(device 0; two calls a sparse layer), summed, over the chunk executions in the
trace. Keyed on the kernels' names alone, whatever the family: a chunk's rows
give an expert a block or several, and how often its tables stream then is
the kernel's walk (``ops/group_gemm.py``). Left out by a program whose chunk
runs no such kernel."""
from benchmark import trace as T
from benchmark.layer_metrics.chunk_ms import PATTERN as CHUNK
from benchmark.layer_metrics.moe_ffn_ms import KERNELS


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    secs, n = T.op_time_within(tr, KERNELS, CHUNK)
    _, chunks = T.module_time_s(tr, CHUNK)
    return secs * 1e3 / chunks if n and chunks else None
