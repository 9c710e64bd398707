"""The window mechanics against a scripted system and a scripted clock."""
import numpy as np

from benchmark import loadgen, traffic


class Req:
    def __init__(self, n):
        self.want, self.generated, self.admit, self.bad = n, [], None, False


class FakeSut:
    """Two slots; each step takes 0.1 s of the fake clock and gives every
    running request 2 tokens (the first step of a request gives 1)."""
    decode_horizon = 2

    def __init__(self, clock, slots=2, broken_after=None):
        self.clock, self.slots, self.queue, self.running = clock, slots, [], []
        self.dsteps = 0

    def submit(self, prompt, n):
        r = Req(n)
        self.queue.append(r)
        return r

    def step(self):
        while self.queue and len(self.running) < self.slots:
            r = self.queue.pop(0)
            r.admit = self.clock.t
            self.running.append(r)
        if not self.running:
            return False
        self.clock.t += 0.1
        for r in self.running:
            r.generated += [1] * min(2 if r.generated else 1,
                                     r.want - len(r.generated))
        self.running = [r for r in self.running if len(r.generated) < r.want]
        self.dsteps += 2
        return True

    queue_depth = property(lambda self: len(self.queue))
    n_tokens = staticmethod(lambda r: len(r.generated))
    finished = staticmethod(lambda r: len(r.generated) >= r.want)
    failed = staticmethod(lambda r: r.bad)
    admit_clock = staticmethod(lambda r: r.admit)

    def decode_steps(self):
        return self.dsteps


class Clock:
    t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += max(s, 1e-4)


def arrivals(times, n_out=5):
    sec = lambda t: "ramp" if t < 0 else "window" if t < 2 else "after"  # noqa: E731
    return [traffic.Arrival(t, sec(t), np.ones(8, np.int32), n_out)
            for t in times]


SPEC = {"arrival": {"process": "poisson", "rate_rps": 1.0}, "ramp_s": 1,
        "drain_cap_s": 5}


def test_open_loop_counts_window_requests_and_times_from_due():
    clock = Clock()
    sut = FakeSut(clock)
    fired = []
    out = loadgen.drive(sut, arrivals([-0.5, 0.25, 1.0, 1.95, 2.5]), SPEC, 2.0,
                        hooks={0.0: lambda: fired.append("open"),
                               2.0: lambda: fired.append("close")},
                        clock=clock, sleep=clock.sleep)
    recs = out["recs"]
    assert [r.section for r in recs] == ["ramp", "window", "window", "window",
                                         "after"][:len(recs)]
    win = [r for r in recs if r.counted]
    assert len(win) == 3 and all(r.done and not r.failed for r in win)
    assert all(r.n_out == 5 and r.first_s > r.due_s for r in win)
    assert all(r.submit_s >= r.due_s for r in recs)
    # the run went past the window's end to let its last request finish
    assert out["t_end_s"] >= max(r.last_s for r in win) >= 2.0
    assert fired == ["open", "close"]
    assert loadgen.tokens_in_window(out["steps"], 2.0) <= 15


def test_a_request_that_outlives_the_cap_is_failed():
    clock = Clock()
    sut = FakeSut(clock, slots=1)
    out = loadgen.drive(sut, arrivals([0.1, 0.2], n_out=200), SPEC, 2.0,
                        clock=clock, sleep=clock.sleep)
    win = [r for r in out["recs"] if r.counted]
    assert len(win) == 2 and all(r.failed for r in win if not r.done)
    assert any(not r.done for r in win)
    assert out["t_end_s"] >= 7.0 and all(r.gave_up_s for r in win if not r.done)


def test_backlog_keeps_the_queue_topped_up():
    clock = Clock()
    sut = FakeSut(clock)
    spec = {"arrival": {"process": "backlog", "queue_depth": 3,
                        "pool_requests": 4}, "ramp_s": 1, "drain_cap_s": 5}
    pool = [traffic.Arrival(None, "backlog", np.ones(8, np.int32), 5)
            for _ in range(4)]
    out = loadgen.drive(sut, pool, spec, 2.0, clock=clock, sleep=clock.sleep)
    recs = out["recs"]
    assert len(recs) > 4                              # the pool is cycled
    assert {r.section for r in recs} >= {"ramp", "window"}
    assert all(r.done for r in recs if r.counted)
    assert all(abs(r.submit_s - r.due_s) < 1e-9 for r in recs)
