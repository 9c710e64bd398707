"""How late the benchmark's generator submitted: submit time minus due time,
95th percentile over the window's requests. A starved generator must not be
read as a fast server. Open-loop mixes only (a backlog request is due when it
is taken)."""
from benchmark.e2e import percentile


def read(run):
    if run["spec"]["arrival"]["process"] != "poisson":
        return None
    late = [(r.submit_s - r.due_s) * 1e3 for r in run["recs"] if r.counted]
    return percentile(late, 95) if late else None
