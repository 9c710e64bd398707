"""End-to-end metric arithmetic, from request timelines taken by the benchmark's
own clock outside the program. Nothing here reads a number the program made.

A timeline (``Rec``) holds when a request was due, when ``submit()`` was called,
and the return times of the ``step()`` calls that produced its first and its
last token. A request that failed, was refused or did not finish by the drain
cap misses every limit: its latency is censored at the time the run gave up on
it, which is a lower bound on what a user would have seen.
"""

from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass
class Rec:
    idx: int
    section: str                     # "ramp" | "window" | "after"
    due_s: float                     # all times relative to window open
    submit_s: float
    prompt_len: int
    max_new_tokens: int
    handle: object = None            # the program's request object
    admit_s: float | None = None     # first admission (program's stamp)
    first_s: float | None = None     # step() return that held token 1
    last_s: float | None = None      # step() return that held the last token
    n_out: int = 0
    done: bool = False
    failed: bool = False
    gave_up_s: float | None = None   # when the run stopped waiting for it

    @property
    def counted(self) -> bool:
        return self.section == "window"


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (0..100) of a non-empty sequence."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of no samples")
    pos = (len(s) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def ttft_samples_ms(recs) -> list[float]:
    """One per request due in the window: first-token time minus DUE time."""
    out = []
    for r in recs:
        if not r.counted:
            continue
        end = r.first_s if r.first_s is not None and not r.failed \
            else r.gave_up_s
        out.append((end - r.due_s) * 1e3)
    return out


def tpot_samples_ms(recs) -> list[float]:
    """One per finished window request with >= 2 tokens: (last - first) /
    (tokens - 1). Tokens arrive K at a time, so this is a per-request mean
    and not a gap between tokens."""
    return [(r.last_s - r.first_s) / (r.n_out - 1) * 1e3
            for r in recs
            if r.counted and r.done and not r.failed and r.n_out >= 2]


def _ttft_p95(run):
    s = ttft_samples_ms(run["recs"])
    return percentile(s, 95), len(s)


def _tpot(q):
    def f(run):
        s = tpot_samples_ms(run["recs"])
        return percentile(s, q), len(s)
    return f


def _out_tok_s(run):
    return (run["tokens_in_window"] / run["window_s"] / run["chips"],
            run["tokens_in_window"])


def _setup_s(run):
    return run["setup_s"], 1


# name -> function(run) -> (value, sample count)
METRICS = {
    "ttft_p95_ms": _ttft_p95,
    "tpot_p50_ms": _tpot(50),
    "tpot_p95_ms": _tpot(95),
    "out_tok_s": _out_tok_s,
    "setup_s": _setup_s,
}


def compute(names, run: dict) -> tuple[dict, dict]:
    """(values, sample counts) of the named end-to-end metrics."""
    values, counts = {}, {}
    for name in names:
        if name not in METRICS:
            raise KeyError(f"no arithmetic for end-to-end metric {name!r}")
        values[name], counts[name] = METRICS[name](run)
    return values, counts
