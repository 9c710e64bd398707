"""The one traffic generator: a mix file's parameters + a seed -> requests.

A mix is data (``benchmark/workloads/<traffic>.json``): an arrival process with
its rate or queue depth, and length distributions. The process is found by its
name in ``benchmark/arrivals/<process>.py`` (a later process is a new file
there); it says when each request is due. Lengths are the distribution's
quantiles on an even grid (a stratified sample, not a random one); the mix's
own ``schedule_seed`` puts lengths and gaps in an order. So the schedule (when
each request is due, how long its prompt and its answer are) is a pure function
of the mix file and the window's length, the same for every ``--seed``: the
seed draws the token ids (and, in ``run.py``, the weights). Why not a new order
per seed: near its knee a queue's tail is made by the few moments when long
requests bunch up, and with the order drawn from the seed the 95th percentile
of time to first token spread by 75-82 % over six seeds of one mix (PERF.md,
Findings of PR 23): the seed was changing the work. Another order is another
mix file (another ``schedule_seed``), which a later PR can add as a cell of its
own.

Times are seconds relative to the opening of the measured window: the ramp
lies before 0, the window is [0, seconds), and what follows keeps the same
load on the system while the window's last requests finish.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
import statistics

import numpy as np


@dataclasses.dataclass
class Arrival:
    due_s: float | None          # None: due when the backlog takes it
    section: str                 # "ramp" | "window" | "after"
    prompt: np.ndarray           # int32 token ids
    max_new_tokens: int


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """n values of the clipped distribution at the quantiles (i + .5) / n."""
    if n <= 0:
        return np.zeros(0, np.int64)
    p = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        inv = np.array([statistics.NormalDist().inv_cdf(float(x)) for x in p])
        v = np.exp(math.log(dist["median"]) + dist["sigma"] * inv)
    elif kind == "uniform":
        v = dist["min"] + p * (dist["max"] - dist["min"])
    elif kind == "fixed":
        v = np.full(n, dist["value"], float)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = dist.get("min", 1), dist.get("max", float("inf"))
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def generate(spec: dict, seed: int, seconds: float, vocab: int,
             max_context: int) -> list[Arrival]:
    """The run's arrivals in the order they are due (open loop) or taken
    (closed loop: no due times). ``max_context`` is what one sequence may
    hold: a mix that could exceed it is refused here, so no request can fail
    for its size."""
    name = spec["arrival"]["process"]
    try:
        process = importlib.import_module(f"benchmark.arrivals.{name}")
    except ModuleNotFoundError:
        raise ValueError(f"unknown arrival process {name!r}") from None
    longest = (spec["prompt_tokens"].get("max", 0)
               + spec["output_tokens"].get("max", 0) - 1)
    if not 0 < longest <= max_context:
        raise ValueError(
            f"mix can ask for {longest} tokens of context, the engine holds "
            f"{max_context} a sequence (both lengths need a 'max')")
    # ``order`` (from the mix) arranges gaps and lengths; ``ids`` (from the
    # run's seed) draws the tokens
    order = np.random.default_rng([int(spec.get("schedule_seed", 0)), 0x7261])
    ids = np.random.default_rng([int(seed), 0x746F])
    out = []
    for section, due in process.schedule(
            spec["arrival"], order, float(spec["ramp_s"]), float(seconds),
            float(spec["drain_cap_s"])):
        n = len(due)
        prompts = order.permutation(_quantiles(spec["prompt_tokens"], n))
        outputs = order.permutation(_quantiles(spec["output_tokens"], n))
        out += [Arrival(None if d is None else float(d), section,
                        ids.integers(1, vocab, size=int(p), dtype=np.int32),
                        int(o))
                for d, p, o in zip(due, prompts, outputs)]
    return out
