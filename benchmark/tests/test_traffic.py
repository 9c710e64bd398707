"""The generator: a pure function of the mix file and the seed; the seed draws
the tokens and never moves the amount or the timing of the work."""
import json
import os

import numpy as np
import pytest

from benchmark import manifest as mf, traffic

MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(mf.ROOT, "benchmark",
                                                       "workloads")))


def load(name):
    with open(os.path.join(mf.ROOT, "benchmark", "workloads", name + ".json")) as f:
        return json.load(f)


def key(arrs):
    return [(a.due_s, a.section, a.prompt.tolist(), a.max_new_tokens)
            for a in arrs]


@pytest.mark.parametrize("mix", MIXES)
def test_same_file_and_seed_same_arrivals_other_seed_other_tokens(mix):
    spec = load(mix)
    a = traffic.generate(spec, 2**31 + 17, 20, 32000, 1664)
    b = traffic.generate(spec, 2**31 + 17, 20, 32000, 1664)
    c = traffic.generate(spec, 18, 20, 32000, 1664)
    assert key(a) == key(b)
    assert key(a) != key(c)
    # every seed: the same schedule of due times and lengths, other tokens
    shape = lambda arrs: [(x.due_s, x.section, len(x.prompt),     # noqa: E731
                           x.max_new_tokens) for x in arrs]
    assert shape(a) == shape(c)
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in c]
    # another schedule_seed: the same multiset of lengths, in another order
    d = traffic.generate(dict(spec, schedule_seed=99), 18, 20, 32000, 1664)
    assert shape(d) != shape(c)
    for section in {x.section for x in c}:
        assert sorted(len(x.prompt) for x in c if x.section == section) == \
            sorted(len(x.prompt) for x in d if x.section == section)
    lo, hi = spec["prompt_tokens"]["min"], spec["prompt_tokens"]["max"]
    assert all(lo <= len(x.prompt) <= hi for x in a)
    assert all(1 <= t < 32000 for x in a for t in x.prompt[:5])


def test_poisson_sections_and_rate():
    spec = load("chat-steady")
    rate = spec["arrival"]["rate_rps"]
    a = traffic.generate(spec, 3, 40, 32000, 1664)
    win = [x for x in a if x.section == "window"]
    assert len(win) == round(rate * 40)
    assert all(0 <= x.due_s < 40 for x in win)
    assert all(x.due_s < 0 for x in a if x.section == "ramp")
    due = [x.due_s for x in a]
    assert due == sorted(due)
    gaps = np.diff([x.due_s for x in win])
    assert gaps.std() / gaps.mean() > 0.7           # exponential-like, not even


def test_a_mix_that_could_outgrow_a_sequence_is_refused():
    spec = load("rag-steady")
    with pytest.raises(ValueError):
        traffic.generate(spec, 1, 10, 32000, 1024)


def test_the_arrival_process_is_a_module_found_by_name():
    """A later process is a new file under ``benchmark/arrivals``; a name
    with no file is refused. The load generator goes by whether arrivals
    carry due times, not by the process's name."""
    spec = load("chat-steady")
    with pytest.raises(ValueError, match="unknown arrival process"):
        traffic.generate(dict(spec, arrival={"process": "nope"}), 1, 10,
                         32000, 1664)
    closed = traffic.generate(load("chat-backlog"), 1, 10, 32000, 1664)
    assert all(a.due_s is None for a in closed)
    assert len(closed) == load("chat-backlog")["arrival"]["pool_requests"]
