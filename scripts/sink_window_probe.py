"""On-chip probe of the paged GQA kernels at MiMo-V2-Flash widths (ISSUE 37):
64 query heads, keys of 192 held in 256 lanes, values of 128; a WINDOW layer
(8 KV heads, window 128 over a ring of 6 pages, a sink) and a FULL layer (4 KV
heads), the kernels alone, 20 calls chained under one jit, host clock around
``--reps`` of them.

  python scripts/sink_window_probe.py [--reps 10]

One JSON line a case, also appended to ``chiprun_out/sink_window_probe.jsonl``:
``decode``: 24 rows, all live, at ``ctx`` tokens of context each, ``us_call`` a
layer call, ``roofline_pct`` its least time at the PUBLISHED bytes (320 x 2 B a
key and KV head) over that; ``chunk``: a 512-row chunk after ``ctx`` tokens,
``ms_call`` a layer call."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from triton_dist_tpu.ops.flash_decode import (gqa_decode_paged,  # noqa: E402
                                              gqa_prefill_paged)

HQ, DK, KW, DV, PAGE, W, RING, B, C, CALLS = 64, 192, 256, 128, 128, 128, 6, 24, 512, 20
HBM = 819e9


def pools(key, hkv, pages):
    k1, k2 = jax.random.split(key)
    shape = (1, pages, hkv, PAGE)
    return (jax.random.normal(k1, shape + (KW,), jnp.bfloat16),
            jax.random.normal(k2, shape + (DV,), jnp.bfloat16))


def chained(walk):
    """CALLS walks, each fed the one before (nothing can be elided)."""
    def run(q, *args):
        def one(q, _):
            out = walk(q, *args)
            return q.at[..., :DV].add(out * 1e-6), None
        return jax.lax.scan(one, q, None, length=CALLS)[0]
    return jax.jit(run)


def clock(fn, args, reps):
    fn(*args).block_until_ready()
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t) / (reps * CALLS)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=10)
    a = ap.parse_args()
    key = jax.random.PRNGKey(0)
    sinks = jnp.linspace(3.0, 5.0, HQ)
    scale = DK ** -0.5
    lines = []
    for kind, hkv in (("window", 8), ("full", 4)):
        windowed = kind == "window"
        pps = RING if windowed else 108
        kp, vp = pools(key, hkv, 1 + B * pps)
        bt = 1 + jnp.arange(B * pps, dtype=jnp.int32).reshape(B, pps)
        extra = {"window": W, "sinks": sinks} if windowed else {}
        q = jax.random.normal(key, (B, HQ, KW), jnp.bfloat16)
        dec = chained(lambda q, kp, vp, bt, kl: gqa_decode_paged(
            q, kp, vp, bt, kl, sm_scale=scale, layer=0, **extra)[0])
        for ctx in (100, 2048, 12288):
            kl = jnp.full((B,), ctx, jnp.int32)
            s = clock(dec, (q, kp, vp, bt, kl), a.reps)
            keys = B * (min(ctx, W) if windowed else ctx)
            least = keys * hkv * (DK + DV) * 2 / HBM
            lines.append({"probe": "decode", "kind": kind, "rows": B,
                          "ctx": ctx, "us_call": s * 1e6,
                          "roofline_pct": 100 * least / s})
        qc = jax.random.normal(key, (C, HQ, KW), jnp.bfloat16)
        pre = chained(lambda q, kp, vp, row, kl: gqa_prefill_paged(
            q, kp, vp, row, kl, sm_scale=scale, layer=0, rows_per_block=32,
            vmem_limit_bytes=48 << 20, **extra))
        for ctx in (2048, 12288 - C):
            kl = ctx + 1 + jnp.arange(C, dtype=jnp.int32)
            s = clock(pre, (qc, kp, vp, bt[0], kl), max(2, a.reps // 3))
            lines.append({"probe": "chunk", "kind": kind, "rows": C,
                          "ctx": ctx, "ms_call": s * 1e3})
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "sink_window_probe.jsonl"),
              "a") as f:
        for line in lines:
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
