"""Wire: device time of the SP pool all-gather per decode token-step. The
sharded engine gathers each layer's page pool over the SP axis before the
paged attention walk; XLA emits it as ``all-gather`` operations whose result
is a layer's pool, [pages, kv heads, page, head dim]. Their time inside the
decode program's executions (device 0), over the decode token-steps the
program counted in the traced interval. A configuration without an SP axis
has no such operation and the metric is left out."""
from benchmark import trace as T
from benchmark.layer_metrics.decode_step_ms import PATTERN as DECODE

GATHER = r"^%all-gather[.\d]* = \w+\[\d+,\d+,\d+,\d+\]"


def read(run):
    tr, c = run["trace"], run["counters_trace"]
    if tr is None or not c.get("decode_steps"):
        return None
    secs, n = T.op_time_within(tr, GATHER, DECODE)
    return secs * 1e3 / c["decode_steps"] if n else None
