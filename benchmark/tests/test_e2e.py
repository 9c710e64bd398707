"""End-to-end metric arithmetic on hand-made request timelines."""
import math

import pytest

from benchmark import e2e
from benchmark.e2e import Rec


def rec(idx, due, first, last, n, section="window", **kw):
    return Rec(idx=idx, section=section, due_s=due, submit_s=due + 0.01,
               prompt_len=10, max_new_tokens=n, first_s=first, last_s=last,
               n_out=n, done=True, **kw)


def test_ttft_is_timed_from_the_due_time_not_the_submit_time():
    r = rec(0, due=1.0, first=1.5, last=2.0, n=5)
    r.submit_s = 1.4                      # the generator was 0.4 s late
    assert e2e.ttft_samples_ms([r]) == [pytest.approx(500.0)]


def test_tpot_with_k_token_bursts():
    # 9 tokens handed over 4 at a time: token 1 at t=1.0, the last at t=1.4.
    # Raw gaps would be 0,0,0,big; TPOT is (last - first) / (tokens - 1).
    r = rec(0, due=0.0, first=1.0, last=1.4, n=9)
    assert e2e.tpot_samples_ms([r]) == [pytest.approx(50.0)]
    one = rec(1, due=0.0, first=1.0, last=1.0, n=1)
    assert e2e.tpot_samples_ms([one]) == []          # needs >= 2 tokens


def test_failed_request_misses_every_limit():
    ok = [rec(i, due=0.0, first=0.1, last=0.5, n=5) for i in range(9)]
    bad = Rec(idx=9, section="window", due_s=0.0, submit_s=0.0, prompt_len=10,
              max_new_tokens=5, failed=True, gave_up_s=30.0)
    s = e2e.ttft_samples_ms(ok + [bad])
    assert len(s) == 10 and max(s) == pytest.approx(30000.0)
    assert e2e.percentile(s, 95) > 100.0             # the tail sees it
    assert len(e2e.tpot_samples_ms(ok + [bad])) == 9  # and TPOT leaves it out


def test_only_requests_due_in_the_window_count():
    recs = [rec(0, -1.0, 0.2, 0.4, 3, section="ramp"),
            rec(1, 0.5, 0.9, 1.3, 3), rec(2, 9.0, 9.5, 9.9, 3, section="after")]
    assert e2e.ttft_samples_ms(recs) == [pytest.approx(400.0)]


def test_percentiles_and_their_sample_counts():
    assert e2e.percentile([1, 2, 3, 4, 5], 50) == 3
    assert e2e.percentile([0, 10], 95) == pytest.approx(9.5)
    with pytest.raises(ValueError):
        e2e.percentile([], 50)
    recs = [rec(i, due=0.0, first=0.1 * (i + 1), last=1.0 + 0.1 * i, n=10)
            for i in range(20)]
    run = {"recs": recs, "tokens_in_window": 400, "window_s": 10.0,
           "chips": 4, "setup_s": 33.0}
    values, counts = e2e.compute(["ttft_p95_ms", "tpot_p50_ms", "out_tok_s",
                                  "setup_s"], run)
    assert counts == {"ttft_p95_ms": 20, "tpot_p50_ms": 20, "out_tok_s": 400,
                      "setup_s": 1}
    assert values["out_tok_s"] == pytest.approx(10.0)   # per chip
    assert values["setup_s"] == 33.0
    assert math.isfinite(values["ttft_p95_ms"])
    with pytest.raises(KeyError):
        e2e.compute(["no_such_metric"], run)
