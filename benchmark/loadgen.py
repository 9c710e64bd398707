"""The measured run: ramp, window, drain. One thread, the benchmark's clock.

The loop is ``submit`` what is due, ``step()``, sleep to the next due time when
the engine is idle. The engine admits between steps, so a request due mid-step
waits for the step to end; timing from the due time charges that wait to the
system. Every time recorded here is ``time.perf_counter()`` read outside the
program's calls, as seconds relative to the opening of the window.

After the window closes the load goes on unchanged (arrivals of the "after"
section, or the backlog kept topped up) until every request due inside the
window has finished or the drain cap is reached, so that the window's last
requests finish under the same load as its first. Only requests due inside
the window are counted.
"""

from __future__ import annotations

import contextlib
import time

from benchmark.e2e import Rec

try:                                    # host spans in the profiler's trace
    from jax.profiler import TraceAnnotation as _span
except ImportError:                     # pragma: no cover
    _span = None


def span(name: str):
    return _span(name) if _span is not None else contextlib.nullcontext()


class StepLog:
    """What each ``step()`` call did, seen from outside: its return time, the
    tokens that appeared, the decode token-steps the program counted, and the
    context tokens held by the requests that were decoding."""

    def __init__(self):
        self.t_s: list[float] = []
        self.dt_s: list[float] = []
        self.new_tokens: list[int] = []
        self.decode_steps: list[int] = []
        self.kv_tokens: list[int] = []

    def add(self, t, dt, toks, dsteps, kv):
        self.t_s.append(t)
        self.dt_s.append(dt)
        self.new_tokens.append(toks)
        self.decode_steps.append(dsteps)
        self.kv_tokens.append(kv)


def drive(sut, arrivals, spec: dict, seconds: float, *, hooks=None,
          clock=time.perf_counter, sleep=time.sleep) -> dict:
    """Run ramp + window + drain. ``sut`` is an adapter (submit/step/...).
    ``hooks`` maps a time (s, relative to window open) to a callable run once
    when the loop first passes it (counter snapshots, trace start and stop).
    Returns the records, the step log and the window's bounds."""
    closed = arrivals[0].due_s is None      # no due times: kept topped up
    ramp, cap = float(spec["ramp_s"]), float(spec["drain_cap_s"])
    depth = int(spec["arrival"].get("queue_depth", 0))
    hooks = sorted((hooks or {}).items())
    t_open = clock() + ramp
    now = lambda: clock() - t_open              # noqa: E731

    recs: list[Rec] = []
    live: list[Rec] = []
    steps = StepLog()
    nxt = 0                                     # next arrival to submit
    dsteps_prev = sut.decode_steps()

    def section_of(t):
        return "ramp" if t < 0 else "window" if t < seconds else "after"

    def submit(arr, due):
        t = now()
        with span("bench.submit"):
            handle = sut.submit(arr.prompt, arr.max_new_tokens)
        r = Rec(idx=len(recs), section=section_of(due), due_s=due,
                submit_s=t, prompt_len=len(arr.prompt),
                max_new_tokens=arr.max_new_tokens, handle=handle)
        if handle is None:
            r.failed, r.gave_up_s = True, t
        else:
            live.append(r)
        recs.append(r)

    while True:
        t = now()
        while hooks and hooks[0][0] <= t:
            hooks.pop(0)[1]()
        # -- what is due -------------------------------------------------
        if closed:
            while sut.queue_depth < depth:
                submit(arrivals[nxt % len(arrivals)], now())
                nxt += 1
        else:
            while nxt < len(arrivals) and arrivals[nxt].due_s <= t:
                submit(arrivals[nxt], arrivals[nxt].due_s)
                nxt += 1
        # -- one engine step ----------------------------------------------
        t_before = now()
        with span("bench.step"):
            progressed = sut.step()
        t_after = now()
        new_tokens, kv = 0, 0
        still = []
        for r in live:
            n = sut.n_tokens(r.handle)
            if n > r.n_out:
                if r.n_out == 0:
                    r.first_s = t_after
                else:
                    kv += r.prompt_len + r.n_out
                new_tokens += n - r.n_out
                r.n_out, r.last_s = n, t_after
            if r.admit_s is None:
                a = sut.admit_clock(r.handle)
                if a is not None:
                    r.admit_s = a - t_open
            if sut.failed(r.handle):
                r.failed, r.gave_up_s = True, t_after
            elif sut.finished(r.handle):
                r.done = True
            else:
                still.append(r)
        live = still
        d = sut.decode_steps()
        if progressed:
            steps.add(t_after, t_after - t_before, new_tokens,
                      d - dsteps_prev, kv)
        dsteps_prev = d
        # -- when to stop --------------------------------------------------
        if t_after >= seconds:
            waiting = any(r.counted for r in live)
            if not waiting or t_after >= seconds + cap:
                break
        if not progressed:
            nxt_due = arrivals[nxt].due_s if (
                not closed and nxt < len(arrivals)) else None
            if hooks:
                nxt_due = min(hooks[0][0], nxt_due) if nxt_due is not None \
                    else hooks[0][0]
            if nxt_due is None:
                if t_after >= seconds:
                    break
                nxt_due = t_after + 0.001
            with span("bench.sleep"):
                sleep(max(0.0, min(nxt_due - now(), 0.05)))
    t_end = now()
    for _, fn in hooks:                          # a hook the run never reached
        fn()
    for r in live:                               # abandoned at the cap
        r.gave_up_s = t_end
        if r.counted:
            r.failed = True
    return {"recs": recs, "steps": steps, "t_open": t_open, "t_end_s": t_end,
            "window_s": float(seconds)}


def tokens_in_window(steps: StepLog, seconds: float) -> int:
    return sum(n for t, n in zip(steps.t_s, steps.new_tokens)
               if 0.0 <= t < seconds)
