"""Kernels: device time of a LOOPED decoder's chunk walks per execution of the
prefill-chunk program: the Pallas calls named ``gqa_prefill_paged`` that start
inside the chunk program (device 0; one call a (walk, layer): 192 a chunk, at
a group of ONE query head a KV head), their mean time x the calls a chunk
(``benchmark/costs_looped.planes``). Counted by CALLS and not by the chunk
program's events: the profiler's stop cuts the last execution short (the next
step's chunk is launched behind the decode dispatch), and an event a fifth of
whose calls are missing reads a fifth low. Left out by any other
configuration, and by a program without the kernel."""
from benchmark import costs_looped as C
from benchmark import trace as T
from benchmark.layer_metrics.chunk_ms import PATTERN as CHUNK
from benchmark.layer_metrics.gqa64_chunk_attn_ms import KERNEL
from benchmark.layer_metrics.loop_decode_hbm_roofline import mine


def read(run):
    tr = run["trace"]
    if tr is None or not mine(run):
        return None
    secs, n = T.op_time_within(tr, KERNEL, CHUNK)
    return secs * 1e3 * C.planes(run["cfg"]) / n if n else None
