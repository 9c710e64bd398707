#!/usr/bin/env python
"""The quickest proof that the serving path still starts on the chip.

    python chip_smoke.py                   # on a TPU host: exit 0 or it failed
    python chip_smoke.py --cpu-rehearsal   # tiny presets, CPU, interpret mode

It drives what a user would drive — ``scripts/serve_sim.py`` — and checks
what comes out by the repo's own means:

- **one chip**: ``ServingEngine`` at Mistral-7B published widths (depth cut
  to fit 16 GB beside the KV pool; the cut is printed under ``reduced``):
  chunked paged prefill + device-resident multi-step paged decode, more
  requests than slots. Every request must finish, exactly one decode and
  one chunk program may compile, and prefill-then-decode through the paged
  kernels must agree with the kernel-free ``models.llama.forward`` on
  LOGITS (tokens alone are no check: random weights flip argmax on
  rounding).
- **four chips** (run when jax sees >= 4 devices, else reported
  ``"skipped"`` — loudly): ``ShardedServingEngine`` at Mixtral-8x7B widths
  on a ``1x2x2`` TPxSPxEP mesh over the Pallas remote-DMA wire; each
  distributed op the leg uses against its XLA golden; and the ``1x2x2``
  token streams against a ``1x1x1`` run of the same trace.

This parent never imports jax or triton_dist_tpu: a chip belongs to one
process at a time, so every leg is a sequential child process under a
timeout, all sharing one compile cache (``JAX_COMPILATION_CACHE_DIR`` when
set, else ``<checkout>/.jax_cache``). Per-leg logs land in
``chiprun_out/chip_smoke/``. It measures nothing: the seconds it prints say
that it ran, and the summary line ends with ``"claim": null``.

Standard output is one JSON object per line: the probe (device, versions),
one line per leg, the summary, and LAST exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
A platform other than ``tpu`` (without the rehearsal flag), or a probe that
cannot start, prints nothing there and exits != 0.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

# -- what runs, and at what size --------------------------------------------
# One chip: Mistral-7B widths are 0.44 GB of bf16 weights per layer + 0.52 GB
# of embedding/head; 32 layers (14.5 GB) leave no room for a KV pool in
# 16 GB, so DEPTH ONLY is cut. The pool is sized for the traffic (4 slots x
# 9 pages x 128 tokens = 0.5 GiB at 28 layers). The programs carry the pool
# and write it in place, so they hold no copy of it; the depth is PR 21's
# and more would fit (`benchmark/tools/fit.py` is the way to find out).
ONE_CHIP = dict(
    preset="mistral_7b", layers=28,
    argv=["--slots", "4", "--page-size", "128", "--pages", "36",
          "--pages-per-seq", "9", "--prefill-chunk", "256",
          "--decode-horizon", "4", "--workload",
          "n=12,plen=128:1024,mnt=32:64,prefixes=0,chat=0.5,rate=0.5,"
          "seed=21"],
    min_requests=8)
ONE_CHIP_REHEARSAL = dict(
    preset="tiny", layers=2,
    argv=["--slots", "2", "--page-size", "8", "--pages", "12",
          "--pages-per-seq", "6", "--prefill-chunk", "16",
          "--decode-horizon", "2", "--workload",
          "n=4,plen=8:40,mnt=3:6,prefixes=0,chat=0.5,rate=0.5,seed=21"],
    min_requests=4)

# Four chips: Mixtral-8x7B widths are 2.8 GB of experts per layer, 1.4 GB
# per chip at ep=2. The 1x2x2 leg runs 9 layers (XLA memory analysis:
# 13.6 GiB per chip; 10 layers: 15.0); the cross-mesh pair runs at 4, the
# deepest whose experts (11.3 GiB) still fit the single chip of 1x1x1.
_MOE_ARGV = ["--slots", "4", "--page-size", "128", "--pages", "24",
             "--pages-per-seq", "5", "--prefill-chunk", "256",
             "--decode-horizon", "2", "--tokens", "--workload",
             "n=8,plen=128:512,mnt=16:32,prefixes=0,chat=0.5,rate=0.5,"
             "seed=21"]
FOUR_CHIP = dict(preset="mixtral_8x7b", mesh="1x2x2", layers=9,
                 pair_layers=4, argv=_MOE_ARGV, min_requests=8)
# (only reachable leg by leg — the parent's rehearsal sees one CPU device)
FOUR_CHIP_REHEARSAL = dict(
    preset="tiny", mesh="1x2x2", layers=2, pair_layers=1,
    argv=["--slots", "2", "--page-size", "8", "--pages", "12",
          "--pages-per-seq", "6", "--prefill-chunk", "16",
          "--decode-horizon", "2", "--tokens", "--workload",
          "n=3,plen=8:24,mnt=3:5,prefixes=0,chat=0.5,rate=0.5,seed=21"],
    min_requests=3)

# Logits tolerance. The reference is ``models.llama.forward`` on the same
# bf16 weights with float32 activations at "highest" matmul precision. The
# config's own precision floor is measured in the same run: the distance of
# the kernel-free bf16 ``forward`` from that reference (every layer output
# rounds to bf16, 2^-9 relative, and the residual stream carries it through
# all layers — ~3% of the largest logit at 28 layers). The paged path holds
# the same bf16 activations but rounds along a different order (attention
# folded page by page), so it may sit up to LOGITS_FLOOR_X floors away, plus
# one bf16 step of the largest logit (both paths emit bf16 logits). A path
# computing in a narrower type than the config states lands many floors out.
LOGITS_FLOOR_X = 2.0
# fp8 (e4m3: 3 mantissa bits) quantizes each row to 2^-4 relative steps of
# its largest element, once on dispatch and once on combine.
# 0.0625 + 0.0625 * 1.0625 + bf16's 2^-8 on the way out, of the row's amax.
FP8_ROUNDTRIP_TOL = 0.14


# ---------------------------------------------------------------------------
# parent: no jax, no triton_dist_tpu — children only
# ---------------------------------------------------------------------------

def _run_leg(name: str, extra: list[str], rehearsal: bool,
             timeout_s: int) -> dict:
    """Run one leg as a child; return its summary (the LAST stdout line,
    one JSON object). Raises on timeout, non-zero exit or a malformed
    summary — the caller turns that into exit != 0."""
    os.makedirs(OUT_DIR, exist_ok=True)
    log = os.path.join(OUT_DIR, "_".join([name, *extra[1::2]]) + ".log")
    cmd = [sys.executable, os.path.abspath(__file__), "--leg", name, *extra]
    env = dict(os.environ)
    if rehearsal:
        cmd.append("--cpu-rehearsal")
        env["JAX_PLATFORMS"] = "cpu"
    t0 = time.time()
    with open(log, "w") as f:
        # own session: a hung child is killed with everything it started
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=f,
                                text=True, env=env, cwd=HERE,
                                start_new_session=True)
        try:
            out, _ = proc.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, 9)
            proc.wait()
            raise RuntimeError(
                f"leg {name} timed out after {timeout_s}s (log: {log})")
    with open(log, "a") as f:
        f.write("\n----- stdout -----\n" + out)
    lines = [ln for ln in out.splitlines() if ln.strip()]
    try:
        summary = json.loads(lines[-1])
        if not (isinstance(summary, dict) and summary.get("leg") == name):
            summary = None
    except (IndexError, ValueError):
        summary = None
    if proc.returncode != 0 or summary is None:
        if summary is not None:           # a comparison failed: its numbers
            detail = json.dumps(summary)
        else:                             # it died: the end of its log
            with open(log) as f:
                detail = f.read()[-3000:]
        raise RuntimeError(
            f"leg {name} exited {proc.returncode} (log: {log})\n{detail}")
    summary["leg_wall_s"] = round(time.time() - t0, 1)
    return summary


def _compare_streams(a: dict, b: dict) -> dict:
    """Two ``{rid: tokens}`` maps of the same trace: equal, or the first
    diverging (request, position) and how far each stream agreed. The
    request sets and lengths must match (greedy decode to a fixed budget);
    the token values are REPORTED, not gated — see ``parent``."""
    if a.keys() != b.keys() or any(len(a[r]) != len(b[r]) for r in a):
        raise RuntimeError("the two meshes served different request sets "
                           "or stream lengths for the same trace")
    first, agreed, total, equal = None, 0, 0, 0
    for rid in sorted(a, key=int):
        ta, tb = a[rid], b[rid]
        same = next((i for i, (x, y) in enumerate(zip(ta, tb)) if x != y),
                    len(ta))
        if same < len(ta) and first is None:
            first = [int(rid), same]
        equal += same == len(ta)
        agreed += same
        total += len(ta)
    return {"tokens_vs_1x1x1": "equal" if first is None else "diverged",
            "first_divergence_request_position": first,
            "streams_equal": f"{equal}/{len(a)}",
            "tokens_before_first_divergence_per_stream": f"{agreed}/{total}"}


def parent(rehearsal: bool) -> int:
    probe = _run_leg("probe", [], rehearsal, 120)
    device = probe["device"]
    if device["platform"] != "tpu" and not rehearsal:
        # no result on stdout: a CPU run must not pass for the chip
        print(f"{json.dumps(probe)}\nchip_smoke: platform is "
              f"{device['platform']!r}, not 'tpu' — refusing (the tiny CPU "
              "rehearsal is --cpu-rehearsal)", file=sys.stderr)
        return 2
    print(json.dumps(probe), flush=True)       # FIRST line: the device
    try:
        summary = _run_legs(device, rehearsal)
    except RuntimeError as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr)
        summary = None
    if summary is not None:
        print(json.dumps(summary), flush=True)
    # LAST line, these keys and no others: the verdict and the device as jax
    # reports it. Everything else is on the lines above.
    print(json.dumps({"ok": summary is not None, "device": device}),
          flush=True)
    return 0 if summary is not None else 1


def _run_legs(device: dict, rehearsal: bool) -> dict:
    """Every leg in turn, one JSON line each; returns the run's summary.
    Raises ``RuntimeError`` from the first leg that fails."""
    legs = {}
    one = _run_leg("one_chip", [], rehearsal, 850)
    print(json.dumps(one), flush=True)
    legs["one_chip"] = {k: one[k] for k in (
        "preset", "reduced", "requests_finished", "logits_max_abs_err")}

    if device["count"] < 4:
        # loud, never silent: the four-chip leg did NOT run here
        four = {"leg": "four_chip", "skipped": f"{device['count']} device"}
        print(json.dumps(four), flush=True)
    else:
        ops = _run_leg("four_chip_ops", [], rehearsal, 400)
        print(json.dumps(ops), flush=True)
        serve = _run_leg("four_chip_serve", [], rehearsal, 700)
        print(json.dumps(serve), flush=True)
        pair = {}
        for mesh in (FOUR_CHIP["mesh"], "1x1x1"):
            pair[mesh] = _run_leg("four_chip_pair", ["--mesh", mesh],
                                  rehearsal, 600)
            print(json.dumps({k: v for k, v in pair[mesh].items()
                              if k != "tokens"}), flush=True)
        # REPORTED, not gated: the bitwise-across-mesh contract was only
        # ever shown in interpret mode, and on chips it does not hold (PR 21
        # finding, ROADMAP item 6: streams agree for many tokens, then a
        # rounding-level difference flips an argmax of the random-weight
        # model). What IS gated above: every op the leg uses against its
        # golden (bf16 wire and SP attention bit-exact), every request
        # finished on both meshes, same request set, same lengths.
        four = {"leg": "four_chip", "mesh": FOUR_CHIP["mesh"],
                **_compare_streams(pair[FOUR_CHIP["mesh"]]["tokens"],
                                   pair["1x1x1"]["tokens"])}
        print(json.dumps(four), flush=True)
    legs["four_chip"] = four

    return {"leg": "summary", "legs": legs, "rehearsal": rehearsal,
            "claim": None}


# ---------------------------------------------------------------------------
# children: one process per leg, each holds the chip alone
# ---------------------------------------------------------------------------

class _LegClock:
    """What a leg cost, stamped on its summary when it ends: seconds jax
    spent in backend compiles (a persistent-cache hit is inside the same
    event, just short), the cache's hit count, wall seconds, peak HBM."""

    def __init__(self):
        import jax.monitoring as mon
        self.t0, self.seconds, self.cache_hits = time.time(), 0.0, 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def stamp(self) -> dict:
        return {"compile_s": round(self.seconds, 1),
                "compile_cache_hits": self.cache_hits,
                "wall_s": round(time.time() - self.t0, 1),
                "peak_bytes_in_use": _memory_stats()}

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _device_doc():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _memory_stats():
    """Per-device ``peak_bytes_in_use`` (None where the backend reports no
    stats, i.e. the CPU rehearsal)."""
    import jax
    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use"))
    return peaks


def _serve(argv: list[str]) -> dict:
    """Run scripts/serve_sim.py exactly as a user would (it executes at
    import; its globals come back: engine, params, config, results)."""
    import runpy
    script = os.path.join(HERE, "scripts", "serve_sim.py")
    saved = sys.argv
    sys.argv = [script, *argv]
    try:
        return runpy.run_path(script, run_name="__main__")
    finally:
        sys.argv = saved


def _path_doc() -> dict:
    """What stands between the program and the device: interpret mode or
    compiled Pallas, native host ops loaded or their absence printed, and
    the compile cache where the environment placed it."""
    from triton_dist_tpu import csrc
    from triton_dist_tpu.utils.env import (configure_compile_cache,
                                           default_interpret)
    return {"default_interpret": default_interpret() is not False,
            "native_host_ops": ("loaded" if csrc.get_lib() is not None
                                else "ABSENT (g++ missing or "
                                     "TDT_NO_NATIVE=1): jnp twins in use"),
            "compile_cache": configure_compile_cache()}


def _assert_on_path(rehearsal: bool) -> dict:
    """No fallback may stand in for the device: off the rehearsal, kernels
    are compiled Pallas, never interpret mode."""
    doc = _path_doc()
    assert rehearsal or not doc["default_interpret"], (
        "default_interpret() is not False on the chip: kernels would run "
        "interpreted")
    return doc


def leg_probe() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    def version(pkg):
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return None

    return {"leg": "probe", "device": _device_doc(),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": version("libtpu"), **_path_doc()}


def _logits_check(g: dict) -> dict:
    """Prefill then decode through the paged kernels vs the kernel-free
    ``models.llama.forward`` on the same tokens, on LOGITS (tolerance:
    ``LOGITS_FLOOR_X``).

    The longest-prompt request's own token story (prompt + the tokens the
    engine served) is replayed: the prompt goes through the engine's
    compiled chunk program into its page pool (every page is free again
    after the run), then ``decode_step_paged(sample=False)`` consumes the
    served tokens one position at a time and its [vocab] logits are
    compared with the reference's row for the same position."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from triton_dist_tpu.models.llama import decode_step_paged, forward

    eng, cfg = g["eng"], g["cfg"]
    req = max(eng._finished, key=lambda r: len(r.prompt))
    n_dec = min(len(req.generated) - 1, 8)
    assert n_dec >= 1, "the checked request generated a single token"
    seq = list(req.prompt) + list(req.generated)
    plen, ps, C = len(req.prompt), eng.page_size, eng.prefill_chunk
    n_pages = -(-(plen + n_dec) // ps)
    assert n_pages <= eng.pages_per_seq
    bt_row = np.zeros(eng.pages_per_seq, np.int32)
    bt_row[:n_pages] = np.arange(1, n_pages + 1)     # page 0 is scratch

    pool = eng.pool
    for start in range(0, plen, C):
        toks = np.zeros(C, np.int32)
        part = seq[start:min(start + C, plen)]
        toks[:len(part)] = part
        tok0, pool = eng._chunk_step(
            eng.params, jnp.asarray(toks), jnp.asarray(start, jnp.int32),
            jnp.asarray(plen, jnp.int32), pool, jnp.asarray(bt_row))
    first_token_matches = int(tok0) == seq[plen]

    donate = () if jax.default_backend() == "cpu" else (3,)
    step = jax.jit(
        lambda p, t, pos, pages, bt: decode_step_paged(
            p, t, pos, cfg, pages, bt, sample=False), donate_argnums=donate)
    B = eng.num_slots
    bt = np.zeros((B, eng.pages_per_seq), np.int32)  # other rows: scratch
    bt[0] = bt_row
    got = []
    for j in range(n_dec):
        token, pos = np.zeros(B, np.int32), np.zeros(B, np.int32)
        token[0], pos[0] = seq[plen + j], plen + j
        logits, pool = step(eng.params, jnp.asarray(token),
                            jnp.asarray(pos), pool, jnp.asarray(bt))
        got.append(np.asarray(logits[0]))
    got = np.stack(got)                              # [n_dec, vocab]

    tokens = jnp.asarray([seq[:plen + n_dec]], jnp.int32)
    f32 = dataclasses.replace(cfg, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(lambda p, t: forward(p, t, f32))(
            eng.params, tokens)[0])
    dense = np.asarray(jax.jit(lambda p, t: forward(p, t, cfg))(
        eng.params, tokens)[0])
    want = ref[plen:plen + n_dec]
    assert np.isfinite(got).all() and got.shape == want.shape
    err = float(np.max(np.abs(got - want)))
    floor = float(np.max(np.abs(dense[plen:plen + n_dec] - want)))
    scale = float(np.max(np.abs(want)))
    tol = LOGITS_FLOOR_X * floor + 2.0 ** -8 * scale
    # informational only: how often the reference's argmax (teacher-forced
    # on the served tokens) names the token the engine served next
    agree = int(np.sum(np.argmax(ref[plen - 1:plen + n_dec], -1)
                       == np.asarray(seq[plen:plen + n_dec + 1])))
    return {"logits_max_abs_err": err, "logits_tol": tol,
            "logits_ok": bool(err <= tol),
            "logits_ref_max_abs": scale,
            "logits_bf16_forward_max_abs_err": floor,
            "logits_vs_bf16_forward_max_abs": float(np.max(np.abs(
                got - dense[plen:plen + n_dec]))),
            "checked_request": {"rid": req.rid, "prompt_len": plen,
                                "decode_positions": n_dec},
            "first_token_matches_engine": bool(first_token_matches),
            "served_tokens_matching_reference_argmax":
                f"{agree}/{n_dec + 1}"}


def _serve_summary(leg: str, spec: dict, g: dict, layers: int) -> dict:
    eng = g["eng"]
    c = eng.metrics.counters
    submitted, finished = c["requests_submitted"], c["requests_finished"]
    stats = eng.compile_stats
    assert finished == submitted and finished >= spec["min_requests"], (
        f"{finished}/{submitted} requests finished "
        f"(need all, >= {spec['min_requests']})")
    assert (stats["decode_compiles"],
            stats["prefill_chunk_compiles"]) == (1, 1), (
        f"expected ONE decode and ONE chunk program, got {stats}")
    prompts = [len(r.prompt) for r in eng._finished]
    return {"leg": leg, "preset": spec["preset"],
            "reduced": {"n_layers": layers},
            "argv": spec["argv"],
            "prompt_len_min_max": [min(prompts), max(prompts)],
            "requests_finished": finished, "requests_submitted": submitted,
            "tokens_generated": c["tokens_generated"],
            "preemptions": c["preemptions"],
            "compile_stats": stats}


def leg_one_chip(rehearsal: bool) -> dict:
    spec = ONE_CHIP_REHEARSAL if rehearsal else ONE_CHIP
    g = _serve(["--preset", spec["preset"], "--layers", str(spec["layers"]),
                *spec["argv"]])
    out = _serve_summary("one_chip", spec, g, spec["layers"])
    out.update(_logits_check(g))
    out["ok"] = out["logits_ok"]
    return out


def _assert_pallas_wire(ctx) -> dict:
    """Every ICI axis of the mesh rides the Pallas remote-DMA wire."""
    from triton_dist_tpu.ops.all_to_all import _xla_wire
    wire = {ax: _xla_wire(ctx, ax) for ax in ctx.axis_names}
    assert not any(wire.values()), f"XLA wire on an ICI axis: {wire}"
    return {"xla_wire": wire}


def leg_four_chip_serve(rehearsal: bool, mesh: str | None = None) -> dict:
    """``mesh`` None: the deep 1x2x2 leg. A mesh name: one half of the
    cross-mesh pair (shallow, fp8 pinned — ``auto`` resolves per rank
    count — and the token streams returned for the parent to compare)."""
    spec = FOUR_CHIP_REHEARSAL if rehearsal else FOUR_CHIP
    pair = mesh is not None
    layers = spec["pair_layers"] if pair else spec["layers"]
    mesh = mesh or spec["mesh"]
    g = _serve(["--preset", spec["preset"], "--layers", str(layers),
                "--mesh", mesh, *(["--wire", "fp8"] if pair else []),
                *spec["argv"]])
    eng = g["eng"]
    out = _serve_summary("four_chip_pair" if pair else "four_chip_serve",
                         spec, g, layers)
    out.update(mesh=eng.mesh_desc, wire=eng.wire_dtype,
               wire_chunk=eng.wire_dtype_chunk,
               **_assert_pallas_wire(eng.ctx))
    # parameters are SHARDED: no chip may hold the whole model
    import jax
    total = sum(a.nbytes for a in jax.tree.leaves(eng.params))
    per_dev = {}
    for a in jax.tree.leaves(eng.params):
        for s in a.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) + s.data.nbytes
    out["param_bytes_total"] = total
    out["param_bytes_per_device"] = [per_dev[k] for k in sorted(per_dev)]
    if eng.ctx.num_ranks > 1:
        assert max(per_dev.values()) < total, (
            "one chip holds the whole model — params are not sharded")
    if pair:
        out["tokens"] = {str(r.rid): list(r.generated)
                         for r in eng._finished}
    return out


def leg_four_chip_ops(rehearsal: bool) -> dict:
    """Each distributed op the four-chip leg uses against its XLA golden,
    on the serving mesh, at the leg's own shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from triton_dist_tpu.layers.ep_a2a_layer import EPAll2AllLayer
    from triton_dist_tpu.models import preset_config
    from triton_dist_tpu.ops.allgather_gemm import ag_gemm
    from triton_dist_tpu.ops.flash_decode import (gqa_decode_paged,
                                                  paged_kv_write,
                                                  sp_paged_attend_write)
    from triton_dist_tpu.serving import serving_mesh

    spec = FOUR_CHIP_REHEARSAL if rehearsal else FOUR_CHIP
    tp, sp, ep = (int(d) for d in spec["mesh"].split("x"))
    ctx = serving_mesh(tp, sp, ep)
    _, cfg = preset_config(spec["preset"], "moe")
    base = cfg.base
    D, dt = base.d_model, base.dtype
    key = jax.random.key(21)
    rnd = lambda i, shape: jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), shape, jnp.float32).astype(dt)
    out = {"leg": "four_chip_ops", "mesh": spec["mesh"],
           **_assert_pallas_wire(ctx)}

    # ag_gemm vs all_gather + dot (the TP overlap kernel, on the sp axis)
    M = 128 * sp
    a, b = rnd(0, (M, D)), rnd(1, (D, D))
    got = jax.jit(lambda u, v: ag_gemm(ctx, u, v, axis="sp"))(
        ctx.shard(a, P("sp")), ctx.shard(b, P(None, "sp")))
    want = jnp.dot(a, b, preferred_element_type=jnp.float32)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    # bf16 output rounding (2^-9) of sums ~sqrt(D); MXU f32 passes differ
    tol = 2.0 ** -7 * float(jnp.max(jnp.abs(want)))
    out["ag_gemm_max_abs_err"], out["ag_gemm_tol"] = err, tol
    passed = [err <= tol]

    # EP dispatch -> combine round trip, identity experts, both wires, at
    # the chunk program's row count (its capacity takes the in-kernel
    # quantized wire)
    T = 128 * ep
    tok = rnd(2, (T, D))
    ids = jax.random.randint(jax.random.fold_in(key, 3), (T, cfg.topk), 0,
                             cfg.num_experts)
    w = jnp.full((T, cfg.topk), 1.0 / cfg.topk, jnp.float32)
    for name, wire in (("bf16", None), ("fp8", jnp.float8_e4m3fn)):
        layer = EPAll2AllLayer.create(
            ctx, max_tokens=T // ep, hidden=D, topk=cfg.topk,
            num_experts=cfg.num_experts, axis="ep", dtype=dt,
            wire_dtype=wire)

        def roundtrip(t, i, ww, layer=layer):
            recv, _, layout = layer.dispatch(t, i)
            return layer.combine(recv, layout, ww)

        back = jax.jit(roundtrip)(*(ctx.shard(x, P("ep"))
                                    for x in (tok, ids, w)))
        err = float(jnp.max(jnp.abs(back.astype(jnp.float32)
                                    - tok.astype(jnp.float32))))
        amax = float(jnp.max(jnp.abs(tok.astype(jnp.float32))))
        # bf16 wire: 1/k * (x + x) folds back to x exactly in f32
        tol = FP8_ROUNDTRIP_TOL * amax if wire is not None else 0.0
        out[f"ep_roundtrip_{name}_max_abs_err"] = err
        out[f"ep_roundtrip_{name}_tol"] = tol
        passed.append(err <= tol)

    # sp_paged_attend_write vs the single-device write + gqa_decode_paged
    B, ps, n_pages = 4, (8 if rehearsal else 128), 16
    Hq, Hkv, Dh = base.n_heads, base.n_kv_heads, base.head_dim
    q, kn, vn = (rnd(4, (B, Hq, Dh)), rnd(5, (B, Hkv, Dh)),
                 rnd(6, (B, Hkv, Dh)))
    kp, vp = (rnd(7, (n_pages, Hkv, ps, Dh)), rnd(8, (n_pages, Hkv, ps, Dh)))
    # distinct pages per row (0 is scratch), each row straddling SP shards
    bt = jnp.asarray([[1 + r, n_pages - 1 - r, 1 + B + r]
                      for r in range(B)], jnp.int32)
    pos = jnp.asarray([ps + 3, 2 * ps + 1, 5, 3 * ps - 1], jnp.int32)
    kv_len = pos + 1
    pool = jax.sharding.NamedSharding(ctx.mesh, P("sp"))
    got, gk, gv = jax.jit(lambda *a: sp_paged_attend_write(
        ctx, *a, axis="sp"))(q, kn, vn, jax.device_put(kp, pool),
                             jax.device_put(vp, pool), bt, pos, kv_len)

    def single(q, kn, vn, kp, vp, bt, pos, kv_len):
        kp, vp = paged_kv_write(kp, vp, kn, vn, bt, pos)
        return gqa_decode_paged(q, kp, vp, bt, kv_len)[0], kp, vp

    want, wk, wv = jax.jit(single)(q, kn, vn, kp, vp, bt, pos, kv_len)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    pool_equal = bool(jnp.array_equal(gk, wk) and jnp.array_equal(gv, wv))
    out["sp_paged_attend_max_abs_err"] = err
    out["sp_paged_pool_equal"] = pool_equal
    # the pool allgather is a pure concatenation: same kernel, same bytes
    passed.append(err == 0.0 and pool_equal)

    out["ok"] = all(passed)
    return out


def child(leg: str, rehearsal: bool, mesh: str | None) -> int:
    if rehearsal and leg.startswith("four_chip"):
        # the rehearsal of a mesh leg asks for the simulator explicitly
        from triton_dist_tpu.utils.env import force_virtual_cpu_devices
        force_virtual_cpu_devices(4)
    if leg == "probe":
        summary = leg_probe()
    else:
        clock, on_path = _LegClock(), _assert_on_path(rehearsal)
        summary = {
            "one_chip": lambda: leg_one_chip(rehearsal),
            "four_chip_ops": lambda: leg_four_chip_ops(rehearsal),
            "four_chip_serve": lambda: leg_four_chip_serve(rehearsal),
            "four_chip_pair": lambda: leg_four_chip_serve(rehearsal, mesh),
        }[leg]()
        summary.update(clock.stamp(), **on_path)
    print(json.dumps(summary), flush=True)      # LAST stdout line
    # a failed comparison still prints its numbers, then fails the leg
    return 0 if summary.get("ok", True) else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the tiny presets on the CPU in interpret "
                         "mode — the only way a non-TPU platform exits 0")
    ap.add_argument("--leg", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--mesh", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.leg is not None:
        return child(args.leg, args.cpu_rehearsal, args.mesh)
    try:
        return parent(args.cpu_rehearsal)
    except RuntimeError as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
