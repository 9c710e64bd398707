"""One run of one cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics are
found BY NAME in ``BENCHMARK.json`` and in the data files beside this one; no
name of any of them appears in this code. The last line of stdout is the
result object; everything else a person wants (sample counts, the numbers of
the output check beside their limits, the split of set-up) goes on earlier
lines, one JSON object each.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()          # as near to process start as we get

import argparse                         # noqa: E402
import gc                               # noqa: E402
import importlib                        # noqa: E402
import importlib.util                   # noqa: E402
import json                             # noqa: E402
import os                               # noqa: E402
import sys                              # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import e2e, loadgen, manifest as mf, traffic  # noqa: E402

REHEARSAL_PREFIX = "cpu_rehearsal."


def say(**line) -> None:
    print(json.dumps(line), flush=True)


def parse(argv):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true",
                   help="CPU walk-through of the harness (tests only): "
                        "every metric is printed under a rehearsal name")
    p.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
                   help=argparse.SUPPRESS)
    return p.parse_args(argv)


def load_cell(root: str, cell_name: str, manifest_path=None) -> dict:
    m = mf.load(manifest_path or os.path.join(root, "BENCHMARK.json"))
    cell = mf.by_name(m["workloads"], cell_name, "cell")
    centry = mf.by_name(m["configs"], cell["config"], "configuration")
    with open(os.path.join(root, centry["file"])) as f:
        cfg = json.load(f)
    with open(mf.traffic_file(root, m["paths"], cell["traffic"])) as f:
        spec = json.load(f)
    return {"manifest": m, "cell": cell, "cfg": cfg, "spec": spec,
            "root": root}


def place_compile_cache(root: str) -> str:
    """One fixed directory inside the checkout, unless the environment names
    one. The path is part of the cache's key."""
    import jax
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        placed = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed


def device_doc(chips: int, rehearsal: bool) -> dict:
    """Refuses anything but the accelerator the cell asks for."""
    import jax
    devs = jax.devices()
    doc = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if rehearsal:
        return doc
    if doc["platform"] != "tpu":
        raise SystemExit(f"no accelerator: jax sees {doc}; the benchmark "
                         "measures on a TPU and falls back to nothing")
    if doc["count"] < chips:
        raise SystemExit(f"cell needs {chips} chips, jax sees {doc}")
    return doc


def seed_key(seed: int):
    import jax
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def make_weights(ref, cfg: dict, seed: int, shardings):
    """On the device, in one jitted call, in the dtype they are served in."""
    import jax
    fn = jax.jit(lambda k: ref.init_weights(k, cfg), out_shardings=shardings)
    w = fn(seed_key(seed))
    jax.block_until_ready(w)
    return w


def warm_up(sut, cfg: dict) -> None:
    """Every shape the window will use: the engines have one decode program
    and one chunk program whatever the traffic, so one request whose prompt
    spans two chunks and which decodes past one dispatch compiles both."""
    import numpy as np
    chunk = int(cfg["engine"].get("prefill_chunk") or 16)
    prompt = (np.arange(chunk + 17) % (cfg["vocab_size"] - 1) + 1).astype(
        np.int32)
    n_new = 2 * sut.decode_horizon + 1
    assert len(prompt) + n_new <= sut.max_context
    sut.submit(prompt, n_new)
    while sut.step():
        pass
    assert sut.idle


def peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def load_reader(root, paths, name):
    spec = importlib.util.spec_from_file_location(
        f"benchmark.layer_metrics.{name}", mf.reader_file(root, paths, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def build_system(c: dict, seed: int, rehearsal: bool, lap=lambda name: None):
    """The system under test, built and warm: (adapter, weights). ``lap`` is
    called after each stage with its name, for the split of set-up."""
    from benchmark import check as ck
    cfg = c["cfg"]
    ref = ck.load_reference(cfg["reference"])
    sut = importlib.import_module(
        f"benchmark.adapters.{cfg['adapter']}").Adapter(cfg)
    sut.open_mesh(rehearsal)
    lap("mesh")
    weights = make_weights(ref, cfg, seed, sut.weight_shardings())
    lap("weights")
    sut.build(weights)
    lap("engine")
    warm_up(sut, cfg)
    lap("warm_up")
    return sut, weights


def delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a.get(k, 0) for k in b
            if isinstance(b[k], (int, float))}


def run_cell(args, c: dict, sut=None, weights=None, *, do_check=True,
             control=None) -> dict:
    """Set-up, ramp, window, drain, output check. Returns the result object
    (and prints the lines a person reads). ``sut``/``weights`` let a tool
    hand in a built system to read several windows in one process."""
    from benchmark import check as ck
    cfg, spec, cell, m = c["cfg"], c["spec"], c["cell"], c["manifest"]
    ref = ck.load_reference(cfg["reference"])
    t = {"imports": time.perf_counter() - T_START}
    mark = time.perf_counter()

    def lap(name):
        nonlocal mark
        now = time.perf_counter()
        t[name] = now - mark
        mark = now

    owns = sut is None
    if owns:
        sut, weights = build_system(c, args.seed, args.rehearsal, lap)
    got, resolved_ok = sut.resolution()
    arrivals = traffic.generate(spec, args.seed, args.seconds,
                                cfg["vocab_size"], sut.max_context)
    lap("traffic")

    # -- the measured run --------------------------------------------------
    snaps = {}
    tracing = bool(args.trace)
    trace_dir = os.path.join(c["root"], ".bench_trace")
    trace_s = min(float(spec.get("trace_s", 4.0)), args.seconds / 2)
    state = {}

    def snap(name):
        def f():
            snaps[name] = (time.perf_counter(), sut.counters())
            state["queue_" + name] = sut.queue_depth
        return f

    def trace_on():
        from benchmark import trace as T
        snap("trace_on")()
        T.start(trace_dir)

    def trace_off():
        from benchmark import trace as T
        state["xplane"] = T.stop(trace_dir)

    hooks = {0.0: snap("open")}
    if tracing:
        hooks[args.seconds - trace_s] = trace_on
        hooks[args.seconds] = lambda: (snap("close")(), trace_off())
    else:
        hooks[args.seconds] = snap("close")
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START + float(spec["ramp_s"])
    out = loadgen.drive(sut, arrivals, spec, args.seconds, hooks=hooks)
    peak = peak_bytes()
    recs = out["recs"]
    t_open = out["t_open"]
    rel = lambda name: snaps[name][0] - t_open        # noqa: E731
    counted = [r for r in recs if r.counted]
    failed = [r for r in counted if r.failed or not r.done]
    win = delta(snaps["open"][1], snaps["close"][1])
    compiles = sum(v for k, v in win.items() if k.startswith("compile."))

    run = {"recs": recs, "steps": out["steps"], "window_s": out["window_s"],
           "chips": cell["chips"], "setup_s": setup_s, "cfg": cfg,
           "spec": spec, "counters_window": win, "trace": None,
           "tokens_in_window": loadgen.tokens_in_window(out["steps"],
                                                        args.seconds)}

    # -- the output check, once the engine's memory is free ------------------
    numbers, sample = {}, []
    if do_check:
        sample = ck.pick_sample(recs, int(spec["check"]["sample_requests"]),
                                args.seed)
        # record i was submitted with arrival i (the backlog cycles its pool)
        prompts = [arrivals[r.idx % len(arrivals)].prompt for r in sample]
        served = [sut.tokens(r.handle) for r in sample]
        vocab = cfg["vocab_size"]
        finished = [(r, sut.tokens(r.handle)) for r in counted
                    if r.done and not r.failed]
        shape_ok = all(r.n_out == r.max_new_tokens == len(toks)
                       and all(0 <= tok < vocab for tok in toks)
                       for r, toks in finished)
        for r in recs:
            r.handle = None
        if owns:
            sut.close()
        gc.collect()
        t_ref = time.perf_counter()
        numbers = ck.compare(sample, prompts, served, ref, weights, cfg,
                             pad_to=sut.max_context, control=control)
        numbers["reference_s"] = time.perf_counter() - t_ref
        limits = dict(cfg["check"]["limits"])
        limits.update(spec["check"].get("limits", {}))
        correct, lines = ck.verdict(numbers, limits, {
            "compiles_in_window": (compiles, 0),
            "finished_have_their_tokens": (shape_ok, True),
            "resolved_as_the_file_states": (resolved_ok, True),
            "sampled_requests": (len(sample) > 0, True)})
        for line in lines:
            say(**line)
    else:
        correct = compiles == 0 and resolved_ok

    # -- metrics -------------------------------------------------------------
    section = "per_layer" if tracing else "end_to_end"
    wanted = [x["name"] for x in mf.metrics_of(m, cell["name"], section)]
    units = {x["name"]: x["unit"] for x in m[section]}
    device = device_doc(cell["chips"], args.rehearsal)
    device["memory_peak_bytes"] = peak
    breakdown = None
    if tracing:
        from benchmark import costs, trace as T
        tr = T.load(state["xplane"])
        run.update(trace=tr, counters_trace=delta(snaps["trace_on"][1],
                                                  snaps["close"][1]),
                   trace_window_s=(rel("trace_on"), rel("close")))
        try:
            run["peaks"] = costs.peaks(device["kind"])
        except KeyError:
            if not args.rehearsal:
                raise
            run["peaks"] = None
        values = {}
        for name in wanted:
            v = load_reader(c["root"], m["paths"], name)(run)
            if v is not None:
                values[name] = v
        device["busy_s"] = T.busy_s(tr)
        device["window_s"] = tr.t1_s - tr.t0_s
        breakdown = {"device_ops": T.top_ops(tr, 10),
                     "idle_gaps": T.idle_gaps(tr, 10)}
        counts = {}
    else:
        values, counts = e2e.compute(wanted, run)

    ttft = e2e.ttft_samples_ms(recs)
    tpot = e2e.tpot_samples_ms(recs)
    if ttft and tpot:
        say(also={"ttft_ms": {q: e2e.percentile(ttft, q) for q in (50, 90, 95)},
                  "tpot_ms": {q: e2e.percentile(tpot, q) for q in (50, 90, 95)},
                  "tpot_mean_ms": sum(tpot) / len(tpot)})
    say(samples=counts, attempted=len(counted), failed=len(failed),
        submitted=len(recs), steps=len(out["steps"].t_s),
        tokens_in_window=run["tokens_in_window"],
        window_s=args.seconds, drained_s=out["t_end_s"] - args.seconds,
        generator_late_max_ms=max(
            ((r.submit_s - r.due_s) * 1e3 for r in recs), default=0.0))
    say(setup_split_s=t, ramp_s=spec["ramp_s"], resolved=got,
        compiles_in_window=compiles, memory_peak_bytes=peak,
        checked=numbers, window_counters={
            k: win[k] for k in ("decode_steps", "prefill_chunks",
                                "dispatches", "preemptions", "host_syncs",
                                "tokens_generated", "requests_finished")
            if k in win})
    pre = REHEARSAL_PREFIX if args.rehearsal else ""
    result = {"correct": bool(correct), "attempted": len(counted),
              "failed": len(failed),
              "metrics": {pre + k: {"value": v, "unit": units[k]}
                          for k, v in values.items()},
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearsal:
        result["rehearsal"] = True
    run.update(numbers=numbers, queue_open=state.get("queue_open"),
               queue_close=state.get("queue_close"), values=values,
               finished_in_window=win.get("requests_finished"))
    result["_run"] = run                  # for tools; dropped before printing
    return result


def main(argv=None) -> int:
    args = parse(sys.argv[1:] if argv is None else argv)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    c = load_cell(ROOT, args.workload, args.manifest)
    place_compile_cache(ROOT)
    device_doc(c["cell"]["chips"], args.rehearsal)      # fail before set-up
    result = run_cell(args, c)
    result.pop("_run")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
