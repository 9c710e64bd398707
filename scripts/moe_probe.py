"""Piecewise on-chip probe of the EP MoE serving block (VERDICT r4 #1).

Breaks `moe_ep_block_us` (router → dispatch → grouped gated FFN → combine,
128 tok/rank, hidden 7168, F=512, E=16, topk=8) into measured stages so the
roofline in docs/benchmarks.md is built from numbers, not guesses:

  align        align_tokens_by_expert (one-hot cumsum routing tables)
  edges        apply_grouped with identity fn (align + gather + scatter)
  gated[bm]    fused gate+up+act grouped GEMM alone, block_m sweep
  down[bm]     down grouped GEMM alone
  ffn_fused    gated + down through apply_grouped (the new serving path)
  ffn_unfused  3-launch gate/up/act/down composition (the round-4 path)
  block        full moe_mlp_ep_overlap (router+dispatch+ffn+combine)
  block_em     same block on the expert-major capacity layout (align
               gather/scatter elided: static block→expert map)

Run on the real chip (one process per chip):
  python scripts/moe_probe.py [--quick]

One JSON line per stage. Timing = the bench differenced scan-chain
(bench.py:_per_iter) — see bench.py's module docstring for why.
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import _per_iter, make_chain_timer  # noqa: E402

T, D, F, E, TOPK = 128, 7168, 512, 16, 8
ROWS = T * TOPK  # routed rows at n=1 (every topk copy lands locally)


def main():
    quick = "--quick" in sys.argv
    stages = [a for a in sys.argv[1:] if not a.startswith("-")]

    def want(name):
        return not stages or any(name.startswith(s) for s in stages)

    i1, i2 = (10, 60) if quick else (10, 210)
    from triton_dist_tpu.ops.group_gemm import (align_tokens_by_expert,
                                                apply_grouped, grouped_gemm,
                                                grouped_gemm_gated)

    key = jax.random.key(0)
    ids = jax.random.randint(jax.random.key(1), (ROWS,), 0, E)
    tokens = jax.random.normal(key, (ROWS, D), jnp.float32
                               ).astype(jnp.bfloat16)
    wg = (jax.random.normal(jax.random.key(2), (E, D, F)) * 0.05
          ).astype(jnp.bfloat16)
    wu = (jax.random.normal(jax.random.key(3), (E, D, F)) * 0.05
          ).astype(jnp.bfloat16)
    wd = (jax.random.normal(jax.random.key(4), (E, F, D)) * 0.05
          ).astype(jnp.bfloat16)

    def emit(stage, seconds, **kw):
        print(json.dumps({"stage": stage, "us": round(seconds * 1e6, 1),
                          **kw}), flush=True)

    def guard(name, fn):
        if not want(name):
            return
        try:
            fn()
        except Exception as e:
            print(json.dumps({"stage": name,
                              "error": f"{type(e).__name__}: {e}"[:160]}),
                  flush=True)

    # --- align tables alone -------------------------------------------------
    def align_step(c, _):
        gi, rv, be, nb = align_tokens_by_expert(
            (ids + c.astype(jnp.int32) * 0) % E, E, 128,
            with_used_count=True)
        return c + (jnp.sum(gi) + nb).astype(jnp.float32) * 1e-20

    guard("align", lambda: emit("align", _per_iter(make_chain_timer(
        align_step, jnp.zeros((), jnp.float32), None), i1, i2)))

    # --- forward gather alone (aligned x build) -----------------------------
    gi0, rv0, be0, nb0 = align_tokens_by_expert(ids, E, 128,
                                                with_used_count=True)

    def gather_step(t, _):
        x = jnp.where(rv0[:, None], t[gi0], 0).astype(t.dtype)
        return t + (jnp.sum(x[:8].astype(jnp.float32)) * 1e-20
                    ).astype(t.dtype)

    guard("gather", lambda: emit("gather", _per_iter(make_chain_timer(
        gather_step, tokens, None), i1, i2)))

    # --- align + gather + scatter (identity fn) -----------------------------
    def edges_step(t, _):
        y = apply_grouped(t, ids, E, lambda x, be, nb: x, block_m=128)
        return t + (y * jnp.asarray(1e-20, y.dtype))

    guard("edges", lambda: emit("edges", _per_iter(make_chain_timer(
        edges_step, tokens, None), i1, i2)))

    # --- kernels alone on pre-aligned rows: tile-config sweep ---------------
    gi, rv, be, nb = {}, {}, {}, {}
    xs = {}
    for bm in (128, 256, 512):
        gi[bm], rv[bm], be[bm], nb[bm] = align_tokens_by_expert(
            ids, E, bm, with_used_count=True)
        xs[bm] = jax.block_until_ready(jnp.where(
            rv[bm][:, None], tokens[gi[bm]], 0).astype(jnp.bfloat16))

    GATED_CFGS = [(128, 128, None), (128, 512, 3584), (256, 256, 3584),
                  (256, 512, 3584), (512, 256, 3584), (256, 256, 1792),
                  (256, 512, 1792)]
    for bm, bn, bk in GATED_CFGS:
        def gated_step(xx, _, bm=bm, bn=bn, bk=bk):
            h = grouped_gemm_gated(xx, wg, wu, be[bm], block_m=bm,
                                   block_n=bn, block_k=bk,
                                   n_blocks_used=nb[bm], masked=False)
            eps = (jnp.sum(h[:128].astype(jnp.float32)) * 1e-20
                   ).astype(xx.dtype)
            return xx + eps

        guard(f"gated_{bm}_{bn}_{bk}", lambda s=gated_step, bm=bm: emit(
            f"gated_{bm}_{bn}_{bk}", _per_iter(
                make_chain_timer(s, xs[bm], None), i1, i2)))

    DOWN_CFGS = [(128, 128), (128, 512), (128, 1024), (128, 1792),
                 (256, 512), (256, 1024)]
    h0 = {}
    for bm in (128, 256):
        if any(c[0] == bm for c in DOWN_CFGS) and want("down"):
            h0[bm] = jax.block_until_ready(
                jax.jit(lambda xx, bm=bm: grouped_gemm_gated(
                    xx, wg, wu, be[bm], block_m=bm, block_k=3584,
                    n_blocks_used=nb[bm]))(xs[bm]))
    for bm, bn in DOWN_CFGS:
        def down_step(hh, _, bm=bm, bn=bn):
            y = grouped_gemm(hh, wd, be[bm], block_m=bm, block_n=bn,
                             n_blocks_used=nb[bm], masked=False)
            eps = (jnp.sum(y[:128].astype(jnp.float32)) * 1e-20
                   ).astype(hh.dtype)
            return hh + eps

        guard(f"down_{bm}_{bn}", lambda s=down_step, bm=bm: emit(
            f"down_{bm}_{bn}", _per_iter(
                make_chain_timer(s, h0[bm], None), i1, i2)))

    # --- full expert-FFN stage (weights ride the chain: closures would
    # bake 350 MB into the compiled program as constants) ------------
    def ffn_timer(cfg):
        bm, bn, bk, dbn = cfg

        def step(c, w):
            wg_, wu_, wd_, toks = w

            def f(x, be_, nb_):
                hh = grouped_gemm_gated(x, wg_, wu_, be_, block_m=bm,
                                        block_n=bn, block_k=bk,
                                        n_blocks_used=nb_, masked=False)
                return grouped_gemm(hh, wd_, be_, block_m=bm, block_n=dbn,
                                    n_blocks_used=nb_, masked=False)

            y = apply_grouped(toks + c.astype(jnp.bfloat16), ids, E, f,
                              block_m=bm)
            return jnp.max(y.astype(jnp.float32)) * 1e-20

        return make_chain_timer(step, jnp.zeros((), jnp.float32),
                                (wg, wu, wd, tokens))

    for cfg in [(128, 128, None, 512), (256, 512, 1792, 512),
                (256, 256, 1792, 512), (128, 128, None, 128)]:
        guard(f"ffn_{'_'.join(str(c) for c in cfg)}",
              lambda c=cfg: emit(f"ffn_{'_'.join(str(x) for x in c)}",
                                 _per_iter(ffn_timer(c), i1, i2)))

    # --- full serving block + dispatch (shared ctx) -------------------------
    if (want("block") or want("disp") or want("block_fp8_post")
            or want("block_fp8_expert") or want("block_em")):
        from bench import bench_a2a, bench_ep_block
        from triton_dist_tpu.shmem.context import initialize_distributed
        ctx = initialize_distributed(axis_names=("x",),
                                     mesh_shape=(len(jax.devices()),))
        if want("disp"):
            def _disp():
                d, r = bench_a2a(ctx, tokens_per_rank=T, hidden=D,
                                 topk=TOPK, num_experts=64,
                                 i1=10, i2=410 if quick else 1610)
                emit("disp_bf16", d)
                emit("roundtrip_bf16", r)
            guard("disp", _disp)
        if want("block"):
            guard("block", lambda: emit("block", bench_ep_block(
                ctx, i1=10, i2=60 if quick else 210)))
        if want("block_em"):
            # expert-major capacity layout: align gather/scatter elided
            # in the serving FFN (static block→expert map)
            guard("block_em", lambda: emit("block_em", bench_ep_block(
                ctx, i1=10, i2=60 if quick else 210, expert_major=True)))
        if want("block_fp8_post") or want("block_fp8_expert"):
            # the expert-edge QuantTokens protocol (reference
            # architecture) vs post-dequant, with the convert-once
            # x-scratch in the gated kernel (ADVICE r4 #3)
            guard("block_fp8_post", lambda: emit(
                "block_fp8_post", bench_ep_block(
                    ctx, i1=10, i2=60 if quick else 210,
                    wire_dtype=jnp.float8_e4m3fn, dequant_edge="post")))
            guard("block_fp8_expert", lambda: emit(
                "block_fp8_expert", bench_ep_block(
                    ctx, i1=10, i2=60 if quick else 210,
                    wire_dtype=jnp.float8_e4m3fn,
                    dequant_edge="expert")))


if __name__ == "__main__":
    main()
