"""Kernels: the latent decode kernel's share of its roofline. Least time =
the larger of (cached rows read x stored bytes a row / peak bytes/s) and
(cached rows x heads x (576 + 512) x 2 operations / peak bf16 FLOP/s), from
shapes (``benchmark/costs_latent_moe.py``), over the kernel's device time in
the decode program. Cached rows read = for every decode dispatch in the traced
interval, its token-steps x the context tokens of the requests that were
decoding (the benchmark's own step log), x layers. At these widths memory
bounds it: 1,280 bytes against 139 kFLOP a row is 109 FLOP a byte, under the
v5e's ridge of 240."""
from benchmark import costs_latent_moe as C
from benchmark.layer_metrics.mla_attn_ms import kernel_s


def read(run):
    cfg = run["cfg"]
    if "kv_lora_rank" not in cfg or run.get("peaks") is None:
        return None
    secs, n = kernel_s(run)
    if not n:
        return None
    lo, hi = run["trace_window_s"]
    steps = run["steps"]
    rows = sum(d * kv for t, d, kv in zip(steps.t_s, steps.decode_steps,
                                          steps.kv_tokens)
               if lo <= t < hi and d) * cfg["num_hidden_layers"]
    if not rows:
        return None
    least = max(C.latent_read_bytes(cfg, rows)
                / run["peaks"]["hbm_bytes_per_s"],
                C.latent_attend_flops(cfg, rows)
                / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / secs
