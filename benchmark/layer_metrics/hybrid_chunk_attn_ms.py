"""Kernels: device time of BOTH kinds' chunk walks per execution of the
prefill-chunk program: the Pallas calls named ``gqa_prefill_paged`` (full
layers) and ``gqa_prefill_paged_window_sink`` (sink-window layers) that start
inside the chunk program (device 0), summed, over the chunk executions in the
trace. Left out by a configuration without the second shape, and by a program
without the kernels."""
from benchmark import trace as T
from benchmark.layer_metrics.chunk_ms import PATTERN as CHUNK

KERNEL = r"^%gqa_prefill_paged(_window_sink)?[.\d]* = "


def read(run):
    tr = run["trace"]
    if tr is None or "swa_num_key_value_heads" not in run["cfg"]:
        return None
    secs, n = T.op_time_within(tr, KERNEL, CHUNK)
    _, chunks = T.module_time_s(tr, CHUNK)
    return secs * 1e3 / chunks if n and chunks else None
