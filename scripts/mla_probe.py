"""On-chip probe of the latent (MLA) paged attention kernel at Kimi-K2 widths
(ISSUE 31, ISSUE 34): ``ops.mla_decode.mla_decode_paged`` alone, 64 heads of
width 640 (latent 512), pages of 128 rows, 32 decode rows or a prefill
chunk's 512 over a 70-page table, an 8-layer stacked pool of 2,241 pages, one
kernel call a layer.

Run on the real chip (one process per chip):
  python scripts/mla_probe.py [--variants loop:7:2,chunk:16:1,chunk:16:4]
      [--context 0,1536,3584,8448] [--reps 5]

One JSON line a (case, variant), also appended to
``chiprun_out/mla_probe.jsonl``: ``kernel_us`` is the kernel's own device time
a layer call from a profiler trace, ``ms_layer`` the host's clock around
``reps`` calls of 8 layers, ``us_page`` ``kernel_us`` over the live pages (for
a chunk: over the live (row block, page) pairs), ``sha1`` of the summed result
(equal where two walks agree bit for bit) and ``gap`` its largest difference
from the case's first variant.

Cases (``--cases``) are live rows x pages of context each, the live rows
spread evenly over the 32: ``0x0`` (nothing live), ``4x8``, ``18x27`` (the
benchmark cell's mean), ``20x28`` and ``32x28`` (PR 26's two points), ``32x70``.

Variants:
  ``loop:G:F[:R]`` the in-kernel loop over live pages, G pages an update, F
                   groups in flight, R rows a q / out block (the tree's own
                   constants where left out; ``loop`` alone = as shipped)
  ``chunk:Rb:G[:F]`` a prefill chunk's 512 rows instead of the cases: one
                   shared table, blocks of Rb rows, at each of ``--context``
                   tokens cached before the chunk (8,448 ends the table):
                   the loop over a block's live pages, G pages an update, F
                   groups in flight (as shipped where left out). On a tree
                   from before ISSUE 34 it is the (row block, page step) grid
                   at G pages a step, whatever F: the same command there is
                   the comparison
A tree from before ISSUE 34 also takes ``grid:N``, the (row, page step) grid
at one row a block, N pages a step: the decode rows' walk before ISSUE 31.
"""

from __future__ import annotations

import argparse
import hashlib
import inspect
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import trace as T  # noqa: E402
from triton_dist_tpu.ops import mla_decode as md  # noqa: E402

L, P, H, W, LATENT, PAGE, PPS, ROWS, CHUNK = 8, 2241, 64, 640, 512, 128, 70, 32, 512
SM_SCALE = 0.13086
CASES = "0x0,4x8,18x27,20x28,32x28,32x70"
VARIANTS = "loop:1:2,loop:4:2,loop:7:2,chunk:16:1,chunk:16:4,chunk:16:6"
LOOP = ("DECODE_PAGES_PER_GROUP", "DECODE_GROUPS_IN_FLIGHT",
        "DECODE_ROWS_PER_BLOCK")
CHUNK_LOOP = ("CHUNK_PAGES_PER_GROUP", "CHUNK_GROUPS_IN_FLIGHT")
SHIPPED = {name: getattr(md, name, None) for name in LOOP + CHUNK_LOOP}
# a tree from before ISSUE 34: the chunk's rows walk a grid
GRID = "pages_per_step" in inspect.signature(md.mla_decode_paged).parameters


def as_shipped(names, nums):
    """Set the walk's constants: what the variant leaves out, as shipped."""
    for name, n in zip(names, nums + [SHIPPED[m] for m in names[len(nums):]]):
        setattr(md, name, n)


def walk(variant: str, args):
    """The compiled 8-layer program of one variant: ``mla_decode_paged`` once
    a layer on the same query, the layers' results summed."""
    kind, *nums = variant.split(":")
    nums = [int(n) for n in nums]
    kw = {}
    if kind == "grid":
        if not GRID:
            raise SystemExit(f"{variant}: a tree from before ISSUE 34 has it")
        kw = {"rows_per_block": 1, "pages_per_step": nums[0]}
    elif kind == "chunk" and GRID:
        kw = {"rows_per_block": nums[0], "pages_per_step": nums[1]}
    elif kind == "chunk":
        kw = {"rows_per_block": nums[0]}
        as_shipped(CHUNK_LOOP, nums[1:])
    else:
        as_shipped(LOOP, nums)

    def run(q, pool, bt, kv):
        def body(acc, layer):
            o = md.mla_decode_paged(q, pool, bt, kv, layer=layer,
                                    latent_dim=LATENT, sm_scale=SM_SCALE, **kw)
            return acc + o.astype(jnp.float32), None
        zero = jnp.zeros(q.shape[:2] + (LATENT,), jnp.float32)
        return jax.lax.scan(body, zero, jnp.arange(L, dtype=jnp.int32))[0]
    # compiled here: the loop's constants are read when it is traced
    return jax.jit(run).lower(*args).compile()


def timed(fn, args, reps, trace_dir):
    """(result, host ms a layer, kernel us a layer call) of ``fn(*args)``."""
    got = np.asarray(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
    r.block_until_ready()
    ms = (time.perf_counter() - t0) / reps / L * 1e3
    T.start(trace_dir)
    fn(*args).block_until_ready()
    tr = T.load(T.stop(trace_dir))
    ops = tr.ops[min(tr.ops)] if tr.ops else []
    kern = sum(t for n, _, t in ops if "mla_decode_paged" in n)
    return got, ms, kern / L * 1e6


def decode_case(case: str):
    parked = case.endswith("p")
    live, pages = (int(x) for x in case.rstrip("p").split("x"))
    rng = np.random.default_rng(live * 100 + pages)
    bt = (rng.permutation(P - 1)[:ROWS * PPS] + 1).reshape(ROWS, PPS)
    kv = np.zeros(ROWS, np.int64)
    if live:
        kv[(np.arange(live) * ROWS) // live] = pages * PAGE - PAGE // 4
    if parked:                         # an idle row attends its one token
        kv[kv == 0] = 1
    return bt, kv, live * pages + (ROWS - live) * parked


def chunk_case(context: int, rows_per_block: int):
    bt = np.random.default_rng(0).permutation(P - 1)[:PPS] + 1
    kv = context + np.arange(CHUNK) + 1
    live = -(-kv.reshape(-1, rows_per_block).max(axis=1) // PAGE)
    return np.broadcast_to(bt, (CHUNK, PPS)), kv, int(live.sum())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variants", default=VARIANTS)
    ap.add_argument("--cases", default=CASES)
    ap.add_argument("--context", default="0,1536,3584,8448",
                    help="chunk variants: tokens cached before the chunk")
    ap.add_argument("--reps", type=int, default=5)
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"a chip run: found {dev.platform}")
    kq, kc, kp = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (ROWS, H, W), jnp.bfloat16)
    q_chunk = jax.random.normal(kc, (CHUNK, H, W), jnp.bfloat16)
    pool = jax.jit(lambda k: jax.random.normal(
        k, (L, P, PAGE, W), jnp.bfloat16))(kp)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    trace_dir = os.path.join(ROOT, ".bench_trace", "mla_probe")
    variants = a.variants.split(",")
    lines = []
    for chunk in (False, True):
        names = [v for v in variants if v.startswith("chunk") == chunk]
        cases = ([f"chunk@{c}" for c in a.context.split(",")] if chunk
                 else a.cases.split(","))
        fns = {}
        for case in cases if names else []:
            want = None
            for v in names:
                bt, kv, live = chunk_case(
                    int(case.split("@")[1]), int(v.split(":")[1])) \
                    if chunk else decode_case(case)
                args = (q_chunk if chunk else q, pool,
                        jnp.asarray(bt, jnp.int32),
                        jnp.asarray(kv, jnp.int32))
                if v not in fns:
                    fns[v] = walk(v, args)
                got, ms, kern = timed(fns[v], args, a.reps, trace_dir)
                want = got if want is None else want
                lines.append({
                    "case": case, "variant": v, "kernel_us": kern,
                    "ms_layer": ms, "live_pages": live,
                    "us_page": kern / live if live else None,
                    "gap": float(np.abs(got - want).max()),
                    "sha1": hashlib.sha1(got.tobytes()).hexdigest()[:12],
                    "device": dev.device_kind})
                print(json.dumps(lines[-1]), flush=True)
    with open(os.path.join(out_dir, "mla_probe.jsonl"), "a") as f:
        f.writelines(json.dumps(ln) + "\n" for ln in lines)


if __name__ == "__main__":
    main()
