"""Tune the serving fleet's r6-lever kernels IN CONTEXT and persist the
winners (ISSUE 15: the tuning half of the zero-trace cold-start story).

``contextual_autotune`` picks winners per process; this driver makes the
sweep *representative* and *durable*: it replays a control journal's actual
traffic (prompt lengths, arrival widths) to derive the operand shapes the
fleet really dispatches, sweeps each autotuned overlap op's candidate list
at those shapes on the real serving mesh, and records every winner into a
sigcheck-gated :class:`~triton_dist_tpu.aot.registry.TunedConfigRegistry`
saved as JSON — the file ``tools/compile_aot.py --registry`` embeds into
the artifact and every later replica reads back as its first candidate
(the ``registry_hit`` fast path, no re-sweep).

Usage::

    python -m triton_dist_tpu.tools.tune_serving \
        --journal journal-r0.jsonl --out tuned.json \
        --world 4 --d-model 4096 --d-ff 14336 [--ops ag_gemm,gemm_rs]
"""

from __future__ import annotations

import argparse
import json
import sys


def traffic_shapes(entries, world: int, d_model: int,
                   max_tokens: int = 8192) -> dict:
    """Token-batch geometry from replayed journal traffic: the pow2 bucket
    of the busiest step's submitted tokens (clamped to a tile-friendly
    floor) — the M every swept GEMM sees."""
    per_step: dict[int, int] = {}
    n_reqs = 0
    for e in entries:
        if e.get("kind") != "submit":
            continue
        n_reqs += 1
        per_step[e["step"]] = (per_step.get(e["step"], 0)
                               + len(e.get("prompt", ())))
    peak = max(per_step.values()) if per_step else 0
    floor = world * 32                      # smallest candidate tile per rank
    m = floor
    while m < min(max(peak, floor), max_tokens):
        m *= 2
    # d_model floors at 128: the wire-lane/tile minimum every kernel assumes
    return {"M": m, "K": max(d_model, 128), "requests": n_reqs,
            "peak_step_tokens": peak}


def sweep(ctx, shapes: dict, ops, d_ff: int,
          log=lambda s: None) -> list:
    """Run each requested autotuned wrapper once at the traffic-derived
    shapes; the installed default registry records each winner (or the
    ``registry_hit`` marker when a prior run already persisted one). A
    sweep that raises is a failed tuning run, not a skipped op: the error
    propagates. Returns the list of ops swept."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from triton_dist_tpu.ops import autotuned as at

    n = ctx.num_ranks
    M, K = shapes["M"], shapes["K"]
    N = max(d_ff, 128)
    key = jax.random.key(0)
    done = []

    def attempt(op, thunk, desc):
        if op not in ops:
            return
        thunk()
        done.append(op)
        log(f"{op} swept at {desc}")

    attempt("ag_gemm", lambda: at.ag_gemm_autotuned(
        ctx,
        ctx.shard(jax.random.normal(key, (M, K), jnp.float32), P("x")),
        ctx.shard(jax.random.normal(key, (K, (N // n) * n), jnp.float32),
                  P(None, "x")), "x"),
        f"M={M} K={K} N={(N // n) * n}")
    kk = (K // n) * n
    attempt("gemm_rs", lambda: at.gemm_rs_autotuned(
        ctx,
        ctx.shard(jax.random.normal(key, (M, kk), jnp.float32),
                  P(None, "x")),
        ctx.shard(jax.random.normal(key, (kk, N), jnp.float32), P("x")),
        "x"), f"M={M} K={kk} N={N}")
    s = max(M, n * 512)
    q = jax.random.normal(key, (1, 2, s, 128), jnp.float32)
    attempt("ring_attention", lambda: at.ring_attention_autotuned(
        ctx, ctx.shard(q, P(None, None, "x")),
        ctx.shard(q, P(None, None, "x")),
        ctx.shard(q, P(None, None, "x")), "x"), f"S={s} D=128")

    # local (single-device) grouped-GEMM levers: mesh_shape=() keys, no
    # signal protocol — these execute on every backend including the
    # generic interpreter, so a CPU tuning box still produces a registry
    e_cnt, tokens = 4, jax.random.normal(key, (M, K), jnp.float32)
    ids = jnp.arange(M, dtype=jnp.int32) % e_cnt
    w = jax.random.normal(key, (e_cnt, K, N), jnp.float32)
    attempt("grouped_gemm", lambda: at.grouped_gemm_autotuned(
        tokens, ids, w), f"T={M} H={K} N={N} E={e_cnt}")
    wd = jax.random.normal(key, (e_cnt, N, K), jnp.float32)
    attempt("moe_ffn_gated", lambda: at.moe_ffn_gated_autotuned(
        tokens, ids, w, w, wd), f"T={M} H={K} F={N} E={e_cnt}")
    return done


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Sweep serving-lever kernel configs at journal-derived "
                    "traffic shapes; persist winners to a tuned-config "
                    "registry")
    ap.add_argument("--journal", help="control journal jsonl to replay "
                                      "(omit for the synthetic default "
                                      "trace)")
    ap.add_argument("--out", required=True, help="registry JSON to write")
    ap.add_argument("--world", type=int, default=4,
                    help="ranks on the tuning mesh (live devices; too few "
                         "is an error naming the count)")
    ap.add_argument("--sim", action="store_true",
                    help="tune on a simulated --world-device CPU mesh "
                         "instead of live devices")
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--d-ff", type=int, default=512)
    ap.add_argument("--ops",
                    default="ag_gemm,gemm_rs,grouped_gemm,moe_ffn_gated",
                    help="comma list: ag_gemm,gemm_rs,ring_attention,"
                         "grouped_gemm,moe_ffn_gated")
    ap.add_argument("--no-sigcheck", action="store_true",
                    help="admit winners ungated (NOT for production "
                         "registries)")
    args = ap.parse_args(argv)

    from triton_dist_tpu.utils.env import (configure_compile_cache,
                                           force_virtual_cpu_devices,
                                           require_devices)
    configure_compile_cache()
    if args.sim:
        force_virtual_cpu_devices(args.world)
    else:
        require_devices(args.world, f"--world {args.world}")

    if args.journal:
        from triton_dist_tpu.serving.journal import ControlJournal
        entries = ControlJournal.load(args.journal).entries()
    else:
        # synthetic default: 16 requests, 2/step, 3-16 token prompts
        import numpy as np
        rng = np.random.RandomState(7)
        entries = [{"kind": "submit", "step": i // 2,
                    "prompt": [1] * int(rng.randint(3, 17))}
                   for i in range(16)]

    from triton_dist_tpu.aot.registry import (TunedConfigRegistry,
                                              set_default_registry)
    from triton_dist_tpu.shmem.context import initialize_distributed

    ctx = initialize_distributed(axis_names=("x",),
                                 mesh_shape=(args.world,))
    shapes = traffic_shapes(entries, args.world, args.d_model)
    # incremental tuning: an existing --out is loaded first, so re-runs at
    # already-covered (op, mesh, dtype, bucket) keys take the registry_hit
    # fast path and only NEW shapes pay a sweep
    import os
    reg = (TunedConfigRegistry.load(
               args.out, require_sigcheck=not args.no_sigcheck)
           if os.path.isfile(args.out)
           else TunedConfigRegistry(require_sigcheck=not args.no_sigcheck))
    set_default_registry(reg)
    try:
        done = sweep(ctx, shapes,
                     [o.strip() for o in args.ops.split(",") if o],
                     args.d_ff,
                     log=lambda s: print(f"[tune] {s}", file=sys.stderr))
    finally:
        set_default_registry(None)
    reg.save(args.out)

    print(json.dumps({
        "out": args.out,
        "swept": done,
        "entries": len(reg),
        "keys": [k.to_json() for k in reg.keys()],
        "traffic": shapes,
        "hit_rate": round(reg.hit_rate, 3),
    }, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
