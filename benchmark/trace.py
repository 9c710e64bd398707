"""Profiler trace: capture, and the reduction from ``.xplane.pb`` to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. On a TPU every chip is
a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per device
operation and whose line ``XLA Modules`` one event per program execution; the
host's threads are lines of the plane ``/host:CPU``, where the benchmark's own
``TraceAnnotation`` spans (``bench.*``) land. All planes share one clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    # per device: [(name, start_s, dur_s)] sorted by start
    ops: dict
    modules: dict
    spans: list                       # host spans [(name, start_s, dur_s)]
    t0_s: float                       # first and last instant seen on any
    t1_s: float                       # device line


def start(directory: str) -> None:
    import jax
    os.makedirs(directory, exist_ok=True)
    jax.profiler.start_trace(directory)


def stop(directory: str) -> str:
    """Stops the profiler; returns the newest ``.xplane.pb`` under it."""
    import jax
    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"the profiler wrote no .xplane.pb under "
                                f"{directory}")
    return found[-1]


def _events(line):
    return sorted(((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                   for e in line.events), key=lambda t: t[1])


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules, spans = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[dev] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[dev] = _events(line)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e[0].startswith(SPAN_PREFIX)]
    spans.sort(key=lambda t: t[1])
    edges = [t for evs in list(ops.values()) + list(modules.values())
             for (_, s, d) in evs for t in (s, s + d)]
    return Trace(ops, modules, spans, min(edges, default=0.0),
                 max(edges, default=0.0))


# -- reductions ------------------------------------------------------------

def union_s(events, lo: float | None = None, hi: float | None = None) -> float:
    """Length of the union of the events' intervals, clipped to [lo, hi]."""
    total, end = 0.0, None
    for _, s, d in events:                      # sorted by start
        e = s + d
        if lo is not None:
            s, e = max(s, lo), max(e, lo)
        if hi is not None:
            s, e = min(s, hi), min(e, hi)
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def busy_s(trace: Trace, lo=None, hi=None) -> float:
    """Seconds in which an operation ran on the device, mean over chips."""
    if not trace.ops:
        return 0.0
    return sum(union_s(evs, lo, hi) for evs in trace.ops.values()) \
        / len(trace.ops)


def matching(events, pattern: str):
    rx = re.compile(pattern)
    return [e for e in events if rx.search(e[0])]


def module_time_s(trace: Trace, pattern: str, device: int | None = None,
                  lo=None, hi=None) -> tuple[float, int]:
    """(device seconds, executions) of the programs whose name matches, on
    one device (the lowest by default); executions that began in [lo, hi)."""
    if not trace.modules:
        return 0.0, 0
    dev = min(trace.modules) if device is None else device
    evs = [e for e in matching(trace.modules[dev], pattern)
           if (lo is None or e[1] >= lo) and (hi is None or e[1] < hi)]
    return sum(d for _, _, d in evs), len(evs)


def op_time_within(trace: Trace, op_pattern: str, module_pattern: str,
                   device: int | None = None) -> tuple[float, int]:
    """(device seconds, count) of the operations whose name matches and that
    start inside an execution of a program whose name matches, on one device
    (the lowest by default)."""
    if not trace.ops or not trace.modules:
        return 0.0, 0
    dev = min(trace.ops) if device is None else device
    spans = [(s, s + d) for _, s, d in matching(trace.modules[dev],
                                                module_pattern)]
    total, n, j = 0.0, 0, 0
    for _, s, d in matching(trace.ops[dev], op_pattern):     # sorted by start
        while j < len(spans) and spans[j][1] <= s:
            j += 1
        if j < len(spans) and spans[j][0] <= s:
            total += d
            n += 1
    return total, n


def short_name(name: str, width: int = 96) -> str:
    """The trace names an operation by its whole HLO line
    (``%fusion.7 = bf16[16,4096]{...} fusion(...)``): keep the instruction's
    name without its number (an unrolled loop's sixteen copies of one
    operation are one kind of work), its result's type and the opcode."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:width]
    head = re.sub(r"[.\d]+$", "", head)
    shape = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0])
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + rest)
    kind = m.group(1) if m else ""
    if rest.startswith("("):                       # tuple result: too long
        shape = "(...)"
    return f"{head} {kind} {shape}"[:width]


def self_times(events):
    """[(name, self seconds)] per event: its duration minus the events nested
    inside it on the same line (a ``while`` holds its body's operations)."""
    out, stack = [], []                 # stack of [name, end, self]
    for name, s, d in events:           # sorted by start
        while stack and s >= stack[-1][1] - 1e-12:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= d
        stack.append([name, s + d, d])
    out += [(n, t) for n, _, t in stack]
    return out


def top_ops(trace: Trace, n: int = 10, device: int | None = None):
    """[[name, seconds]]: the device operations with most SELF time."""
    if not trace.ops:
        return []
    dev = min(trace.ops) if device is None else device
    total: dict[str, float] = {}
    for name, t in self_times(trace.ops[dev]):
        key = short_name(name)
        total[key] = total.get(key, 0.0) + max(t, 0.0)
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10, device: int | None = None):
    """[[host span, seconds]]: the device's idle gaps, each charged to the
    benchmark's host span that covers its middle ("(no span)" if none),
    summed by span, longest first."""
    if not trace.ops:
        return []
    dev = min(trace.ops) if device is None else device
    gaps, end = [], None
    for _, s, d in trace.ops[dev]:
        if end is not None and s > end:
            gaps.append((end, s))
        end = s + d if end is None else max(end, s + d)
    total: dict[str, float] = {}
    spans = trace.spans
    j = 0
    for a, b in gaps:
        mid = (a + b) / 2
        while j < len(spans) and spans[j][1] + spans[j][2] < mid:
            j += 1
        name = "(no span)"
        # innermost covering span: the latest-starting one that covers mid
        k = j
        while k < len(spans) and spans[k][1] <= mid:
            if spans[k][1] + spans[k][2] >= mid:
                name = spans[k][0]
            k += 1
        total[name] = total.get(name, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def describe(trace: Trace, n: int = 40) -> dict:
    """What a person looks at first: planes seen, programs by name with their
    counts and time, and the operations by time."""
    out = {"devices": sorted(trace.ops), "span_names": sorted(
        {s[0] for s in trace.spans}), "window_s": trace.t1_s - trace.t0_s,
        "busy_s": busy_s(trace)}
    if trace.modules:
        dev = min(trace.modules)
        mods: dict[str, list] = {}
        for name, _, d in trace.modules[dev]:
            m = mods.setdefault(name, [0, 0.0])
            m[0] += 1
            m[1] += d
        out["modules"] = sorted(([k, c, t] for k, (c, t) in mods.items()),
                                key=lambda r: -r[2])[:n]
    out["ops"] = top_ops(trace, n)
    if trace.ops:                      # kernels and collectives, named in full
        rx = re.compile(r"custom-call|all-gather|all-to-all|collective|"
                        r"all-reduce|send|recv")
        full: dict[str, list] = {}
        for name, _, d in trace.ops[min(trace.ops)]:
            if rx.search(name.partition(" = ")[2][:200] or name):
                m = full.setdefault(name[:600], [0, 0.0])
                m[0] += 1
                m[1] += d
        out["kernels_and_collectives"] = sorted(
            ([k, c, t] for k, (c, t) in full.items()), key=lambda r: -r[2])[:n]
    return out
