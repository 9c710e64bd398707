"""Model programs: rows whose two carried rows one short-convolution layer
advances in one decode token-step, mean over the window: the program's
``conv_state_rows`` over conv layers x decode token-steps. It is the batch the
one-step conv gathers, shifts and writes back, beside ``active_slots_mean``
(which counts slots in prefill too). A program without the counter leaves the
metric out."""


def conv_layers(cfg: dict) -> int:
    return cfg["layer_types"][:cfg["num_hidden_layers"]].count("conv")


def read(run):
    c = run["counters_window"]
    rows, steps = c.get("conv_state_rows"), c.get("decode_steps")
    if rows is None or not steps:
        return None
    return rows / (conv_layers(run["cfg"]) * steps)
