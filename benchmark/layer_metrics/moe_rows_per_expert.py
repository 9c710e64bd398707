"""Model programs: rows a held expert sees in one decode token-step, mean over
the window: the program's ``moe_local_rows`` (routed assignments of live rows
that landed on held experts, summed over layers and inner steps) over held
experts x sparse layers x decode token-steps. The deployment's figure is
(batch x experts a token / routed experts) of a whole expert-parallel unit;
this says how far the cell is from it."""
from benchmark import costs_latent_moe as C


def read(run):
    c = run["counters_window"]
    rows, steps = c.get("moe_local_rows"), c.get("decode_steps")
    if rows is None or not steps:
        return None
    cfg = run["cfg"]
    return rows / (cfg["n_routed_experts"] * C.sparse_layers(cfg) * steps)
