"""Kernels / HBM: the decode program's share of its memory roofline. The
least bytes its token-steps must read (all layer weights and the LM head per
token-step, plus the keys and values of the contexts that were decoding; from
shapes, ``benchmark/costs.py``) over the chip's peak bytes/s, divided by the
decode program's device time in the trace. Bound by memory, not compute: a
decode step does 2 FLOPs per weight byte pair. Dense models only: which experts
a sparse step reads is not visible from outside."""
from benchmark import costs
from benchmark import trace as T
from benchmark.layer_metrics.decode_step_ms import PATTERN


def read(run):
    tr, cfg = run["trace"], run["cfg"]
    if tr is None or cfg.get("num_local_experts"):
        return None
    lo, hi = run["trace_window_s"]
    steps = run["steps"]
    token_steps = kv_reads = 0
    for t, d, kv in zip(steps.t_s, steps.decode_steps, steps.kv_tokens):
        if lo <= t < hi and d:
            token_steps += d
            kv_reads += d * kv
    secs, n = T.module_time_s(tr, PATTERN)
    if not n or not token_steps:
        return None
    least = costs.decode_min_bytes(cfg, token_steps, kv_reads) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / secs
