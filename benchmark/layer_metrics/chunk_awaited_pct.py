"""Engine dispatch: the share of the window's prefill chunks whose token the
step blocked for: 100 x delta ``phase_chunk_wait_s.count`` / delta
``phase_chunk_prep_s.count`` (the exact histograms of
``ServingMetrics.phase``). Only a prompt's last chunk has a token anybody
reads; a program that fences every chunk reads 100. Left out where no chunk
ran in the window or the program has no such histograms."""


def read(run):
    c = run["counters_window"]
    n = c.get("phase_chunk_prep_s.count", 0)
    return 100.0 * c["phase_chunk_wait_s.count"] / n if n else None
