"""Engine dispatch: the host's share of the program's step time WITHOUT either
blocking call: 100 x the seven host-work phases' totals over the window /
``step_s.total``. (``host_share_pct`` books the chunk program's blocking call
as host time; this one leaves ``phase_chunk_wait_s`` and
``phase_decode_wait_s`` out.) Left out where the program has no such
histograms."""

HOST_WORK = ("admit", "chunk_prep", "grow", "sync", "dispatch", "reconcile",
             "post")


def read(run):
    c = run["counters_window"]
    whole = c.get("step_s.total", 0.0)
    if whole <= 0:
        return None
    return 100.0 * sum(c[f"phase_{p}_s.total"] for p in HOST_WORK) / whole
