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
  python scripts/moe_probe.py --chunks [--root PARENT_CHECKOUT]

``--chunks`` (PR 44): the grouped GEMMs ALONE at the shapes the backlog cells'
chunk programs call them at (lfm2: 2,048 rows x 4 picks over 64 whole experts
of 2,048 x 1,536; command-a-plus: 2,048 rows x 8 picks of 128, 16 held, 4,096 x
4,096; row block 128), each at an even load and at a skewed one, and at lfm2's
DECODE shape (96 rows x 4 picks, row block 16) as the control: ms a call, the
bytes the walk moves over that time against 819 GB/s, how often a touched
expert's tables cross HBM -> VMEM (a walk by row blocks: once a block; by an
expert's runs: once a run), and a sha256 of the live rows' output.
``--root`` imports the package from another checkout (the parent's, unpacked
by ``git archive``), same process shape; ``--shapes a,b`` keeps those rows;
``--cut N`` reads the walk at another run cut (``_RUN_BLOCKS``, the probe's
own patch: the package has no such option).

One JSON line per stage. Timing = the bench differenced scan-chain
(bench.py:_per_iter) — see bench.py's module docstring for why.
"""

from __future__ import annotations

import hashlib
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if "--root" in sys.argv:        # the package of another checkout, this probe
    sys.path.insert(0, os.path.abspath(sys.argv[sys.argv.index("--root") + 1]))
sys.path.insert(1, _HERE)

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


# (name, rows, picks, routed experts, held, hidden, expert width, row block)
CHUNK_SHAPES = (
    ("lfm2-chunk", 2048, 4, 64, 64, 2048, 1536, 128),
    ("command-a-plus-chunk", 2048, 8, 128, 16, 4096, 4096, 128),
    ("lfm2-decode", 96, 4, 64, 64, 2048, 1536, 16),
    # decode controls of the shapes lfm2's does not cover: Kimi's (H 7,168:
    # two strips, runs of one) and Qwen3-Next's (32 held of 512, 2 MB tables)
    ("kimi-decode", 32, 8, 384, 12, 7168, 2048, 128),
    ("qwen3-next-decode", 128, 10, 512, 32, 2048, 512, 16),
)
with open(os.path.join(_HERE, "benchmark", "peaks.json")) as _f:
    HBM_GBS = json.load(_f)["TPU v5 lite"]["hbm_bytes_per_s"] / 1e9


def draw_picks(rows, picks, routed, held, skew, seed):
    """Local expert ids [rows * picks] (-1: an expert another chip holds):
    the ``picks`` largest of a score a (row, expert), iid noise of std 0.1
    (even) plus, at ``skew``, an expert's own bias of std 0.05: the selection
    bias of the two configurations' routers at half the scores' spread."""
    rng = np.random.default_rng(seed)
    score = rng.normal(0, 0.1, (rows, routed))
    if skew:
        score = score + rng.normal(0, 0.05, (1, routed))
    ids = np.argsort(-score, axis=1)[:, :picks].reshape(-1)
    return np.where(ids < held, ids, -1).astype(np.int32)


def chunks_main():
    from triton_dist_tpu.ops import group_gemm as gg

    i1, i2 = (5, 15) if "--quick" in sys.argv else (5, 45)
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "package": os.path.dirname(gg.__file__)}), flush=True)
    only = (sys.argv[sys.argv.index("--shapes") + 1].split(",")
            if "--shapes" in sys.argv else None)
    if "--cut" in sys.argv:     # a probe's reading of another run cut
        gg._RUN_BLOCKS = int(sys.argv[sys.argv.index("--cut") + 1])
    for name, rows, picks, routed, held, D, F, bm in CHUNK_SHAPES:
        if only and name not in only:
            continue
        keys = jax.random.split(jax.random.key(0), 4)
        wg, wu = ((jax.random.normal(k, (held, D, F)) * 0.05
                   ).astype(jnp.bfloat16) for k in keys[:2])
        wd = (jax.random.normal(keys[2], (held, F, D)) * 0.05
              ).astype(jnp.bfloat16)
        bn, dbn = math.gcd(128, F), math.gcd(512, D)
        for skew in (False, True):
            ids = draw_picks(rows, picks, routed, held, skew, 1)
            gi, rv, be, nb = gg.align_tokens_by_expert(
                jnp.asarray(ids), held, bm, with_used_count=True)
            tokens = jax.random.normal(keys[3], (rows * picks, D)
                                       ).astype(jnp.bfloat16)
            x = jnp.where(rv[:, None], tokens[gi], 0).astype(jnp.bfloat16)
            h = jnp.resize(x, (x.shape[0], F))
            used = np.asarray(be)[:int(nb)]
            touched = len(set(used.tolist()))
            kernels = {
                "gated": (D, F, 2, bn, x, (wg, wu), lambda xx, w: (
                    gg.grouped_gemm_gated(
                        xx, *w, be, block_m=bm, block_n=bn, n_blocks_used=nb,
                        masked=False, block_k=gg.fit_block_k(
                            D, bm, bn, 2, n_weights=2)))),
                "down": (F, D, 1, dbn, h, (wd,), lambda hh, w: (
                    gg.grouped_gemm(
                        hh, *w, be, block_m=bm, block_n=dbn,
                        n_blocks_used=nb, masked=False,
                        block_k=gg.fit_block_k(F, bm, dbn, 2)))),
            }
            for kind, (K, N, n_w, tile, a, w, call) in kernels.items():
                # tables fetched: once a row block, or once a run of an
                # expert's blocks as THIS package's walk cuts them
                fetches, walk = len(used), "row blocks"
                if hasattr(gg, "fit_run_strips"):
                    _, cut = gg.fit_run_strips(K, bm, tile, 2, 2, n_w)
                    fetches, walk = sum(
                        -(-int(n) // cut)
                        for n in np.bincount(used, minlength=held)), "runs"

                def step(c, w, call=call):
                    y = call(c, w)
                    return c + (jnp.sum(y[:bm].astype(jnp.float32)) * 1e-20
                                ).astype(c.dtype)

                # the live rows' bytes: parent and change print one value
                live = np.asarray(jax.jit(call)(a, w))[:len(used) * bm]
                sec = _per_iter(make_chain_timer(step, a, w), i1, i2, 4)
                moved = 2 * (fetches * n_w * K * N + len(used) * bm * (K + N))
                print(json.dumps({
                    "shape": name, "load": "skewed" if skew else "even",
                    "kernel": kind, "walk": walk, "ms": round(sec * 1e3, 4),
                    "live_rows": int(np.sum(ids >= 0)),
                    "blocks_used": len(used), "experts_touched": touched,
                    "tables_an_expert": round(fetches / touched, 3),
                    "moved_GB": round(moved / 1e9, 4),
                    "GB_s": round(moved / 1e9 / sec, 1),
                    "of_819_pct": round(100 * moved / 1e9 / sec / HBM_GBS,
                                        1),
                    "sha256": hashlib.sha256(live.tobytes()).hexdigest()[:16],
                }), flush=True)


if __name__ == "__main__":
    chunks_main() if "--chunks" in sys.argv else main()
