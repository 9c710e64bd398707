"""Engine dispatch: host work a prefill chunk dispatched (budget, token buffer,
COW guard, the slot's table row, the uploads and the launch of the chunk
program until it returns), from the program's exact histogram
``phase_chunk_prep_s`` over the window: delta total / delta count. Left out
where the program has no such histogram."""


def read(run):
    c = run["counters_window"]
    n = c.get("phase_chunk_prep_s.count", 0)
    return c["phase_chunk_prep_s.total"] * 1e3 / n if n else None
