"""Model programs: rows an expert sees in one decode token-step, mean over the
window: the program's ``moe_local_rows`` over experts (``num_experts``: all
are held) x sparse layers (those after ``num_dense_layers``) x decode
token-steps. The chip is a pipeline stage that holds its layers whole, so
this IS the deployment's figure (live rows x experts a token / experts)."""
from benchmark.layer_metrics.whole_experts_ms import mine


def read(run):
    c = run["counters_window"]
    rows, steps = c.get("moe_local_rows"), c.get("decode_steps")
    if not mine(run) or rows is None or not steps:
        return None
    cfg = run["cfg"]
    sparse = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    return rows / (cfg["num_experts"] * sparse * steps)
