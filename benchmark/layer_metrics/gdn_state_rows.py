"""Model programs: rows whose matrix state one linear-attention layer advances
in one decode token-step, mean over the window: the program's
``gdn_state_rows`` over linear layers x decode token-steps. It is the batch
the state update streams (each row a state read and written back), beside
``active_slots_mean`` (which counts slots in prefill too). A program without
the counter leaves the metric out."""


def linear_layers(cfg: dict) -> int:
    every = cfg["full_attention_interval"]
    return cfg["num_hidden_layers"] // every * (every - 1)


def read(run):
    c = run["counters_window"]
    rows, steps = c.get("gdn_state_rows"), c.get("decode_steps")
    if rows is None or not steps:
        return None
    return rows / (linear_layers(run["cfg"]) * steps)
