"""The program's own spans in the profiler's trace, and the device's idle time
put down to them.

``ServingMetrics.phase`` opens a ``jax.profiler.TraceAnnotation`` named
``engine.<phase>`` around every phase of ``ServingEngine.step()`` (and
``engine.step`` around the whole, ``engine.submit`` around a submit). They land
on the ``/host:CPU`` plane of the same ``.xplane.pb`` as the device's
``XLA Ops`` line, on one clock. ``trace.load`` keeps the benchmark's own
``bench.*`` spans only, and a reader is handed the ``run`` dict, not the file:
so this module finds the file the run wrote (the newest under ``.bench_trace``,
as ``tools/describe_trace.py`` does), reads the ``engine.*`` events once a run,
and intersects INTERVALS: a gap that straddles two phases is split between
them, not charged whole to the span that covers its middle.

A program without the spans (a parent commit) gives no ``engine.step`` and
every reader built on this returns None.
"""

from __future__ import annotations

import glob
import os

from benchmark import trace as T

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
PREFIX = "engine."
STEP, DISPATCH = "engine.step", "engine.dispatch"
WAITS = ("engine.chunk_wait", "engine.decode_wait")
SLEEP = "bench.sleep"
CACHE_KEY = "program_spans"


def newest_xplane(directory: str = TRACE_DIR) -> str | None:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def parse_name(raw: str) -> tuple[str, dict]:
    """``engine.chunk_wait#step=3,rid=7#`` -> (``engine.chunk_wait``,
    {"step": 3, "rid": 7}): the name up to the first ``#``, the ids behind
    it. (The profiler may also hand the ids over as the event's stats.)"""
    name, _, tail = raw.partition("#")
    ids = {}
    for pair in tail.strip("#").split(","):
        k, eq, v = pair.partition("=")
        if eq:
            ids[k] = int(v) if v.lstrip("-").isdigit() else v
    return name, ids


def load_spans(path: str) -> list[tuple[str, dict, float, float]]:
    """[(name, ids, start_s, dur_s)] of the ``engine.*`` host events, by
    start."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != T.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                name, ids = parse_name(e.name)
                ids.update((k, v) for k, v in e.stats
                           if isinstance(v, (int, str)))
                out.append((name, ids, e.start_ns * 1e-9,
                            e.duration_ns * 1e-9))
    out.sort(key=lambda s: s[2])
    return out


# -- interval arithmetic: lists of (start, end), sorted and disjoint ---------

def union(intervals) -> list[tuple[float, float]]:
    out = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def intersect(xs, ys) -> list[tuple[float, float]]:
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(xs, ys) -> list[tuple[float, float]]:
    out, j = [], 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > a:
                out.append((a, ys[k][0]))
            a = max(a, ys[k][1])
            k += 1
        if a < b:
            out.append((a, b))
    return out


def length(xs) -> float:
    return sum(b - a for a, b in xs)


def idle_gaps(ops) -> list[tuple[float, float]]:
    """The intervals between the first and the last operation of one device
    in which none ran."""
    busy = union((s, s + d) for _, s, d in ops)
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:])]


def of(spans, *names) -> list[tuple[float, float]]:
    return union((s, s + d) for name, *_, s, d in spans if name in names)


def split(trace: T.Trace, spans) -> dict | None:
    """The idle seconds of device 0 by where the host was: inside an
    ``engine.step`` and outside its two waits (the host's own work), inside a
    wait (launch and readback latency), asleep in the load generator, or
    outside every step (the load generator's bookkeeping). The four add up to
    the device's idle time. ``steps`` counts the ``engine.step`` spans that
    hold an ``engine.dispatch``. None without a device plane or a step."""
    if not trace.ops:
        return None
    launched = [s for name, _, s, _ in spans if name == DISPATCH]
    n = sum(1 for name, _, s, d in spans if name == STEP
            and any(s <= t < s + d for t in launched))
    if not n:
        return None
    steps = of(spans, STEP)
    gaps = idle_gaps(trace.ops[min(trace.ops)])
    waits = of(spans, *WAITS)
    in_step = intersect(gaps, steps)
    in_wait = intersect(in_step, waits)
    outside = subtract(gaps, steps)
    asleep = intersect(outside, of(trace.spans, SLEEP))
    return {"steps": n, "idle_s": length(gaps),
            "host_work_s": length(in_step) - length(in_wait),
            "in_wait_s": length(in_wait), "asleep_s": length(asleep),
            "outside_s": length(outside) - length(asleep)}


def phase_table(trace: T.Trace, spans) -> list[dict]:
    """One row a span name (``engine.step`` last): how often it ran, its
    seconds, and the device's idle seconds under it. A child's idle time is
    in its parent's row too."""
    gaps = idle_gaps(trace.ops[min(trace.ops)]) if trace.ops else []
    names = sorted({s[0] for s in spans}, key=lambda n: (n == STEP, n))
    return [{"span": name,
             "count": sum(1 for s in spans if s[0] == name),
             "seconds": sum(s[3] for s in spans if s[0] == name),
             "idle_s": length(intersect(gaps, of(spans, name)))}
            for name in names]


def wait_table(trace: T.Trace, spans) -> list[dict]:
    """The idle seconds under each of the two wait spans by where in the wait
    they fall: before the first program that runs inside the wait has begun
    (``launch_s``: the launch reaching the device), between the operations of
    a running program (``in_program_s``: not the host's at all), between two
    programs (``between_s``), after the last program has ended
    (``readback_s``: the result reaching the host thread)."""
    dev = min(trace.ops)
    gaps = idle_gaps(trace.ops[dev])
    programs = [(s, s + d) for _, s, d in trace.modules.get(dev, [])]
    running = union(programs)
    out = []
    for wait in WAITS:
        row = dict.fromkeys(("launch_s", "in_program_s", "between_s",
                             "readback_s"), 0.0)
        for name, _, a, d in spans:
            if name != wait:
                continue
            b = a + d
            idle = intersect(gaps, [(a, b)])
            inside = [p for p in programs if p[0] < b and p[1] > a]
            first = min((p[0] for p in inside), default=b)
            last = max((p[1] for p in inside), default=b)
            bare = subtract(idle, running)
            head = length(intersect(bare, [(a, first)]))
            tail = length(intersect(bare, [(last, b)]))
            row["launch_s"] += head
            row["readback_s"] += tail
            row["between_s"] += length(bare) - head - tail
            row["in_program_s"] += length(idle) - length(bare)
        out.append({"span": wait, **row})
    return out


def read(run: dict) -> dict | None:
    """``split`` of the run's trace, computed once a run and kept on the
    ``run`` dict for the next reader."""
    if CACHE_KEY not in run:
        tr, path = run.get("trace"), None
        if tr is not None and tr.ops:
            path = newest_xplane()
        run[CACHE_KEY] = split(tr, load_spans(path)) if path else None
    return run[CACHE_KEY]


def per_step_ms(run: dict, key: str) -> float | None:
    got = read(run)
    return got[key] * 1e3 / got["steps"] if got else None
