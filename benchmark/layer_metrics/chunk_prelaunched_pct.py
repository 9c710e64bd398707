"""Engine dispatch: the share of the window's prefill chunks that were
launched AHEAD, behind the previous step's decode dispatch and before the
host blocked on its slab: 100 x delta ``chunks_prelaunched`` / delta
``prefill_chunks`` (``ServingMetrics`` counters). Such a chunk's host phase
and the turn-around in front of it run under a device program. Left out where
no chunk ran in the window or the program has no such counter."""


def read(run):
    c = run["counters_window"]
    n, ahead = c.get("prefill_chunks", 0), c.get("chunks_prelaunched")
    return 100.0 * ahead / n if n and ahead is not None else None
