"""Model programs: rows whose recurrent state one layer advances in one decode
token-step, mean over the window: the program's ``ssm_state_rows`` over layers
x decode token-steps. It is the batch the state update streams (each row a
state read and written back), beside ``active_slots_mean`` (which counts slots
in prefill too). A program without the counter leaves the metric out."""


def read(run):
    c = run["counters_window"]
    rows, steps = c.get("ssm_state_rows"), c.get("decode_steps")
    if rows is None or not steps:
        return None
    return rows / (run["cfg"]["num_hidden_layers"] * steps)
