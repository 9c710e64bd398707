"""Kernels: device time of the latent (MLA) paged attention kernel per decode
token-step. The kernel is a Pallas call named ``mla_decode_paged`` (its
``name=``; the trace shows ``%mla_decode_paged.N = ... custom-call``): the sum
of its executions inside the decode program (device 0; the chunk program's
calls of the same kernel are left out) over the decode token-steps the program
counted in the traced interval. A program without the kernel has no such
operation and the metric is left out."""
from benchmark import trace as T
from benchmark.layer_metrics.decode_step_ms import PATTERN as DECODE

KERNEL = r"^%mla_decode_paged[.\d]* = "


def kernel_s(run, pattern=KERNEL):
    """(seconds, calls) of the operations named ``pattern`` inside the decode
    program (device 0)."""
    tr = run["trace"]
    if tr is None:
        return 0.0, 0
    return T.op_time_within(tr, pattern, DECODE)


def read(run):
    secs, n = kernel_s(run)
    steps = (run.get("counters_trace") or {}).get("decode_steps")
    return secs * 1e3 / steps if n and steps else None
