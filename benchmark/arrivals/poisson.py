"""Open loop at a fixed rate, Poisson-shaped: the gaps between arrivals are
the exponential distribution's quantiles on an even grid, put in an order by
the mix's schedule and scaled so that every section holds exactly
round(rate x its length) requests. Not a random Poisson draw: the amount of
work is fixed, only its order is the schedule's.

    "arrival": {"process": "poisson", "rate_rps": 2.0}
"""
import numpy as np


def _gaps(n: int, span_s: float) -> np.ndarray:
    """n exponential gaps at even quantiles, scaled to fill ``span_s``."""
    g = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    return g * (span_s / g.sum())


def schedule(arrival: dict, order, ramp_s: float, seconds: float,
             after_s: float) -> list:
    """[(section, due times)]: the ramp before 0, the window [0, seconds),
    and the same load after it while the window's last requests finish."""
    rate = float(arrival["rate_rps"])
    out = []
    for name, start, span in (("ramp", -ramp_s, ramp_s),
                              ("window", 0.0, seconds),
                              ("after", seconds, after_s)):
        n = int(round(rate * span))
        if n:
            gaps = order.permutation(_gaps(n, span))
            out.append((name, start + np.cumsum(gaps)
                        - gaps[0] * order.random()))
    return out
