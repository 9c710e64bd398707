"""Scheduler: the program's stamp of a request's first admission minus the
time it was due, median over the window's requests (those never admitted are
left out; they show as failed). Open-loop mixes only."""
from benchmark.e2e import percentile


def read(run):
    if run["spec"]["arrival"]["process"] != "poisson":
        return None
    wait = [(r.admit_s - r.due_s) * 1e3 for r in run["recs"]
            if r.counted and r.admit_s is not None]
    return percentile(wait, 50) if wait else None
