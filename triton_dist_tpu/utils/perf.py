"""Perf measurement + tracing harness.

``perf_func`` mirrors the reference's CUDA-event wall-clock harness
(reference python/triton_dist/utils.py:186-198); on TPU we block on the
output buffers instead of recording events. ``group_profile`` mirrors the
reference's merged chrome-trace context (utils.py:254-501); jax's profiler
already merges multi-host traces, so it is a thin wrapper producing a
Perfetto-loadable trace directory.
"""

from __future__ import annotations

import contextlib
import time

import jax


def _block(tree):
    """Synchronize on ``tree``'s buffers: dispatch is asynchronous, so a
    timing that does not wait here measures the enqueue."""
    jax.block_until_ready(tree)


def perf_func(func, iters: int = 10, warmup_iters: int = 3, return_result: bool = False):
    """Return (result, avg_ms_per_iter); ``result`` is the last iteration's
    output when ``return_result=True``, else None. ``func`` should return jax
    arrays (they are blocked on for timing)."""
    result = None
    for _ in range(warmup_iters):
        result = func()
    _block(result)
    start = time.perf_counter()
    for _ in range(iters):
        result = func()
    _block(result)
    elapsed_ms = (time.perf_counter() - start) * 1e3 / max(iters, 1)
    if return_result:
        return result, elapsed_ms
    return None, elapsed_ms


@contextlib.contextmanager
def group_profile(name: str = "trace", do_prof: bool = True,
                  out_dir: str = "prof", merge: bool = True):
    """Profile the enclosed region into ``{out_dir}/{name}`` (TensorBoard /
    Perfetto format).

    Multi-process jobs (``jax.process_count() > 1`` over a shared
    filesystem): each process traces into ``{path}/proc{i}`` (jax names
    trace files by *hostname*, which collides for same-host processes),
    then process 0 merges every process's chrome trace into ONE
    Perfetto-loadable ``{path}/merged.trace.json.gz`` with per-host track
    names — the analog of the reference's gather-and-merge
    ``group_profile`` (reference python/triton_dist/utils.py:282-501,
    which all-gathers per-rank chrome traces over the process group and
    rewrites pids into per-rank tracks)."""
    if not do_prof:
        yield
        return
    path = f"{out_dir}/{name}"
    multi = jax.process_count() > 1
    local = f"{path}/proc{jax.process_index()}" if multi else path
    jax.profiler.start_trace(local)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        if multi and merge:
            from jax.experimental import multihost_utils
            # every process must have flushed its trace before the merge
            multihost_utils.sync_global_devices(f"group_profile:{name}")
            if jax.process_index() == 0:
                merge_process_traces(path)


def merge_process_traces(path: str) -> str | None:
    """Merge ``{path}/proc*/`` chrome traces into
    ``{path}/merged.trace.json.gz``: one timeline, pids offset per process
    and tracks labeled ``host{i}/...``. Returns the merged file path (None
    when no per-process traces were found). Standalone so offline tooling
    can merge traces gathered from real pod hosts by other means."""
    import glob
    import gzip
    import json
    import os

    events = []
    found = False
    for proc_dir in sorted(glob.glob(f"{path}/proc*")):
        # host index from the directory name, NOT enumeration order —
        # lexicographic glob order misassigns labels at 10+ processes
        # (proc10 sorts before proc2)
        try:
            i = int(os.path.basename(proc_dir)[len("proc"):])
        except ValueError:
            continue
        traces = (glob.glob(f"{proc_dir}/**/*.trace.json.gz",
                            recursive=True)
                  + glob.glob(f"{proc_dir}/**/*.trace.json", recursive=True))
        base = (i + 1) * 100000
        for t in sorted(traces):
            opener = gzip.open if t.endswith(".gz") else open
            with opener(t, "rt") as f:
                data = json.load(f)
            found = True
            for ev in data.get("traceEvents", []):
                if "pid" in ev:
                    ev = dict(ev)
                    ev["pid"] = base + int(ev["pid"])
                    if (ev.get("ph") == "M"
                            and ev.get("name") == "process_name"):
                        args = dict(ev.get("args", {}))
                        args["name"] = f"host{i}/{args.get('name', '')}"
                        ev["args"] = args
                events.append(ev)
    if not found:
        return None
    out = os.path.join(path, "merged.trace.json.gz")
    with gzip.open(out, "wt") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ns"}, f)
    return out
