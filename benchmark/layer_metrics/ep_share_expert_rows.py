"""Model programs: rows a held expert sees in one decode token-step, mean over
the window: the program's ``moe_local_rows`` over held experts
(``n_routed_experts``) x sparse layers (``moe_layer_freq``) x decode
token-steps. The deployment's figure is (batch x experts a token / routed
experts) of a whole expert-parallel unit; this says how far the cell is from
it."""
from benchmark.layer_metrics.ep_share_experts_ms import mine


def read(run):
    c = run["counters_window"]
    rows, steps = c.get("moe_local_rows"), c.get("decode_steps")
    if not mine(run) or rows is None or not steps:
        return None
    cfg = run["cfg"]
    sparse = sum(cfg["moe_layer_freq"][:cfg["num_hidden_layers"]])
    return rows / (cfg["n_routed_experts"] * sparse * steps)
