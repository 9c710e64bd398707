"""Model programs: rows a held expert sees in one decode token-step, mean over
the window: the program's ``moe_local_rows`` over held experts
(``num_experts``) x layers (every layer is sparse) x decode token-steps. The
deployment's figure is (batch x experts a token / routed experts) of a whole
expert-parallel unit; this says how far the cell is from it."""
from benchmark.layer_metrics.gated_attn_ms import mine


def read(run):
    c = run["counters_window"]
    rows, steps = c.get("moe_local_rows"), c.get("decode_steps")
    if not mine(run) or rows is None or not steps:
        return None
    cfg = run["cfg"]
    return rows / (cfg["num_experts"] * cfg["num_hidden_layers"] * steps)
