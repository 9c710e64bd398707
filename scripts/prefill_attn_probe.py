"""On-chip probe of the paged attention kernels at Mistral-7B widths.

A prefill chunk's attention (ISSUE 27, the default):
``gqa_prefill_paged`` (the chunk's rows share one walk of the sequence's pages)
at several ``rows_per_block``, beside the 256 rows of ``gqa_decode_paged`` it
replaces, over the benchmark cell's pool (20 layers, 209 pages of 128, 13 pages
a sequence), one kernel call a layer with the query carried through the layers.

Run on the real chip (one process per chip):
  python scripts/prefill_attn_probe.py [--rows 16,32,64] [--reps 10]

One JSON line a (context, variant): ``ms_layer`` is the host's clock around
``reps`` calls of 20 layers (the head-major transposes included), ``kernel_us``
the kernel's own device time a layer from a profiler trace, ``gap`` the largest
difference from the decode rows' result. The lines are also appended to
``chiprun_out/prefill_attn_probe.jsonl``.

``--decode`` probes the decode rows' walk instead (ISSUE 29): 16 slots of which
4 / 1 / 16 decode at 3 / 10 / 13 pages of context (``--cases`` for others),
``gqa_decode_paged`` as the tree it runs in ships it, so that the same command
in a checkout of another commit is the comparison. ``dmas_live`` is the number
of page blocks (K and V) that are live, which is what the loop over live pages
fetches; ``dmas_clamped`` what the (row, page) grid before ISSUE 29 fetched (a
dead step clamped to the row's last live page, an idle row to its entry 0: a
block is fetched when its index changes); ``sha1`` is of the result after 20
layers, equal across trees where the kernels agree bit for bit.
``--in-flight 1,2,3`` tries several depths of the page prefetch.

Other widths and a sliding window (ISSUE 30): ``--group 16`` is 16 query heads
a KV head (128 x 128 under 8 KV heads), ``--chunk 2048`` the chunk's rows,
``--layers`` / ``--pages`` / ``--pps`` the pool, ``--contexts 4096:2048,...``
(tokens before the chunk : real tokens in it), ``--window 4096`` the kernels'
``window=`` over a RING of ``--pps`` pages (the context's pages wrap into it),
``--vmem-mb`` the chunk kernel's scoped-VMEM limit, ``--no-baseline`` leaves
out the rows-of-decode variant (``gap`` is then against the first variant).

The chunk walk as one in-kernel loop (ISSUE 41): ``--pages-per-group 1,2,4``
tries several ``PREFILL_PAGES_PER_GROUP`` beside every ``--rows`` (a tree
without the constant, the (row block, page) grid before ISSUE 41, runs as it
ships whatever the flag says, so the same command in a checkout of another
commit is the comparison). A line's ``pages_live`` / ``pages_edge`` are the
pages the call's row blocks walk and those of them that take the masked
update (``ops.flash_decode.chunk_walk_counts``; None in a tree without it),
``grid_steps_before`` the (row block, page) steps of the grid that was,
``sha1`` the result's. ``--longdoc`` is the long-document cell's chunk
(command-a-plus: 16 heads a KV head, 2,048 rows, 6k and 20k tokens before the
chunk) under window 4,096 over a ring of 49 pages and under none over a table
of 200.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import trace as T  # noqa: E402
from triton_dist_tpu.ops.flash_decode import (gqa_decode_paged,  # noqa: E402
                                              gqa_prefill_paged)

L, P, HKV, HQ, D, PAGE, PPS, C = 20, 209, 8, 32, 128, 128, 13, 256
# (tokens of context before the chunk, real tokens in the chunk)
CONTEXTS = [(0, 256), (512, 256), (1280, 256), (677, 200)]


def layers(attend):
    """20 calls of ``attend(q, layer)``, each fed the one before."""
    def run(q, kp, vp, bt, kv):
        def body(q, layer):
            return attend(q, kp, vp, bt, kv, layer), None
        return jax.lax.scan(body, q, jnp.arange(L, dtype=jnp.int32))[0]
    return jax.jit(run)


def measure(fn, args, reps, trace_dir):
    """(result, host ms a layer, kernel us a layer) of ``fn(*args)``."""
    got = np.asarray(fn(*args).astype(jnp.float32))
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
    r.block_until_ready()
    ms = (time.perf_counter() - t0) / reps / L * 1e3
    T.start(trace_dir)
    fn(*args).block_until_ready()
    tr = T.load(T.stop(trace_dir))
    ops = tr.ops[min(tr.ops)] if tr.ops else []
    kern = sum(t for n, _, t in ops if "custom-call" in n and (
        "closed_call" in n or "gqa_" in n))
    return got, ms, kern / L * 1e6


# (slots decoding of 16, pages of context each)
DECODE_CASES = [(4, 3), (1, 10), (16, 13)]
SLOTS = 16


def clamped_dmas(bt, kv):
    """Page blocks (K and V) a layer call of the one-page-a-step walk fetches
    when a dead step revisits its row's last live page."""
    last = np.maximum(-(-kv // PAGE) - 1, 0)
    idx = [bt[b, min(s, last[b])] for b in range(len(kv)) for s in range(PPS)]
    return 2 * (1 + int(np.sum(np.diff(idx) != 0)))


def decode_probe(a, dev, kp, vp, trace_dir):
    from triton_dist_tpu.ops import flash_decode as fd
    win = {"window": a.window} if a.window else {}
    q = jax.random.normal(jax.random.PRNGKey(1), (SLOTS, HQ, D), jnp.bfloat16)
    cases = [tuple(int(x) for x in c.split("x"))
             for c in a.cases.split(",")] if a.cases else DECODE_CASES
    # pages in flight ahead of the one attended: the tree's own, or several
    depths = [int(x) for x in a.in_flight.split(",")] if a.in_flight else [
        getattr(fd, "DECODE_PAGES_IN_FLIGHT", None)]
    lines = []
    for depth in depths:
        if a.in_flight:
            fd.DECODE_PAGES_IN_FLIGHT = depth
        fn = layers(lambda q, kp, vp, bt, kv, ly: gqa_decode_paged(
            q, kp, vp, bt, kv, layer=ly, **win)[0])
        for live, pages in cases:
            rng = np.random.default_rng(live)
            # under a window every slot has a ring of PPS pages of its own
            bt = (rng.permutation(P - 1)[:SLOTS * PPS] + 1).reshape(SLOTS, PPS)
            kv = np.zeros(SLOTS, np.int64)
            if live:
                kv[np.arange(live) * (SLOTS // live)
                   + (5 if live == 1 else 0)] = pages * PAGE - 37
            got, ms, kern = measure(
                fn, (q, kp, vp, jnp.asarray(bt, jnp.int32),
                     jnp.asarray(kv, jnp.int32)), a.reps, trace_dir)
            lines.append({"case": "decode", "live_slots": live,
                          "pages": pages, "in_flight": depth, "group": HQ // HKV,
                          "window": a.window, "ms_layer": ms,
                          "kernel_us": kern, "dmas_live": 2 * live * pages,
                          "dmas_clamped": clamped_dmas(bt, kv),
                          "sha1": hashlib.sha1(got.tobytes()).hexdigest(),
                          "device": dev.device_kind})
            print(json.dumps(lines[-1]), flush=True)
    return lines


def walk_pages(fd, start, real, rb, window):
    """(pages the call's row blocks walk, edge pages among them, steps of the
    (row block, page) grid before ISSUE 41) of one chunk call."""
    from triton_dist_tpu.ops.flash_decode import _window_pages
    rb = math.gcd(C, rb)
    # (by the function both trees have)
    grid = C // rb * (min(PPS, _window_pages(window, PAGE, rb)) if window
                      else PPS)
    if not hasattr(fd, "chunk_walk_counts"):
        return None, None, grid
    return (*fd.chunk_walk_counts(start, real, C, rb, PAGE, window, PPS), grid)


def chunk_probe(a, dev, kq, kp, vp, window, trace_dir):
    from triton_dist_tpu.ops import flash_decode as fd
    q = jax.random.normal(kq, (C, HQ, D), jnp.bfloat16)
    bt = jnp.asarray(np.random.default_rng(0).permutation(P - 1)[:PPS] + 1,
                     jnp.int32)
    win = {"window": window} if window else {}
    variants = {} if a.no_baseline else {"decode_rows": (None, None, layers(
        lambda q, kp, vp, bt, kv, ly: gqa_decode_paged(
            q, kp, vp, jnp.broadcast_to(bt, (C, PPS)), kv, layer=ly,
            **win)[0]))}
    if a.vmem_mb:
        win = dict(win, vmem_limit_bytes=a.vmem_mb << 20)
    # pages a group: the tree's own, or several (a tree without the constant
    # has one walk, a page a grid step)
    own = getattr(fd, "PREFILL_PAGES_PER_GROUP", None)
    groups = [int(g) for g in a.pages_per_group.split(",")] if (
        a.pages_per_group and own) else [own]
    for rb in (int(r) for r in a.rows.split(",")):
        for g in groups:
            variants[f"prefill_rb{rb}" + (f"_g{g}" if own else "")] = (
                rb, g, layers(
                    lambda q, kp, vp, bt, kv, ly, rb=rb: gqa_prefill_paged(
                        q, kp, vp, bt, kv, layer=ly, rows_per_block=rb,
                        **win)))
    lines = []
    for start, real in CONTEXTS:
        idx = start + np.arange(C)
        kv = jnp.asarray(np.where(idx < start + real, idx + 1, 0), jnp.int32)
        want = None
        for name, (rb, g, fn) in variants.items():
            if g and a.pages_per_group:
                # a trace-time constant: each variant is traced at its first
                # call, and the score budget must not cut the group asked for
                fd.PREFILL_PAGES_PER_GROUP = g
                fd.PREFILL_GROUP_SCORE_BYTES = 1 << 30
            got, ms, kern = measure(fn, (q, kp, vp, bt, kv), a.reps,
                                    trace_dir)
            want = got if want is None else want
            live, edge, grid = walk_pages(fd, start, real, rb, window) \
                if rb else (None, None, None)
            lines.append({
                "context": start, "real": real, "variant": name,
                "group": a.group, "window": window, "chunk": C,
                "pages_per_group": g, "ms_layer": ms, "kernel_us": kern,
                "pages_live": live, "pages_edge": edge,
                "grid_steps_before": grid,
                "gap": float(np.abs(got - want).max()),
                "sha1": hashlib.sha1(got.tobytes()).hexdigest(),
                "device": dev.device_kind})
            print(json.dumps(lines[-1]), flush=True)
    return lines


def main():
    global L, P, HQ, PPS, C, SLOTS, CONTEXTS
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="16,32,64")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--decode", action="store_true",
                    help="probe the decode rows' walk, not a chunk's")
    ap.add_argument("--cases", default=None,
                    help="--decode: live slots x pages, e.g. 4x3,1x10,16x13")
    ap.add_argument("--in-flight", default=None,
                    help="--decode: DECODE_PAGES_IN_FLIGHT values to try")
    ap.add_argument("--group", type=int, default=HQ // HKV)
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=C)
    ap.add_argument("--layers", type=int, default=L)
    ap.add_argument("--pages", type=int, default=P, help="pages of the pool")
    ap.add_argument("--pps", type=int, default=PPS,
                    help="pages a sequence (the ring, under --window)")
    ap.add_argument("--slots", type=int, default=SLOTS)
    ap.add_argument("--contexts", default=None,
                    help="tokens before the chunk : real tokens in it, ...")
    ap.add_argument("--vmem-mb", type=int, default=None)
    ap.add_argument("--pages-per-group", default=None,
                    help="PREFILL_PAGES_PER_GROUP values to try, e.g. 1,2,4")
    ap.add_argument("--longdoc", action="store_true",
                    help="the long-document cell's chunk: --group 16 --chunk "
                    "2048, 6k and 20k of context, window 4096 and none")
    ap.add_argument("--no-baseline", action="store_true")
    a = ap.parse_args()
    if a.longdoc:
        a.group, a.chunk, a.no_baseline = 16, 2048, True
        a.contexts = a.contexts or "6144:2048,20480:2048"
        a.vmem_mb = a.vmem_mb or 48
    L, P, HQ, PPS, C, SLOTS = (a.layers, a.pages, HKV * a.group, a.pps,
                               a.chunk, a.slots)
    if a.contexts:
        CONTEXTS = [tuple(int(x) for x in c.split(":"))
                    for c in a.contexts.split(",")]
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"a chip run: found {dev.platform}")
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (L, P, HKV, PAGE, D)
    kp = jax.jit(lambda k: jax.random.normal(k, shape, jnp.bfloat16))(kk)
    vp = jax.jit(lambda k: jax.random.normal(k, shape, jnp.bfloat16))(kv_)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    trace_dir = os.path.join(ROOT, ".bench_trace", "prefill_attn_probe")
    if a.decode:
        lines = decode_probe(a, dev, kp, vp, trace_dir)
        with open(os.path.join(out_dir, "decode_attn_probe.jsonl"), "a") as f:
            f.writelines(json.dumps(ln) + "\n" for ln in lines)
        return
    lines = []
    # (window, pages a sequence): what was asked for, or the long-document
    # cell's two kinds of layer
    for window, pps in ([(4096, 49), (None, 200)] if a.longdoc
                        else [(a.window, PPS)]):
        PPS = pps
        lines += chunk_probe(a, dev, kq, kp, vp, window, trace_dir)
    with open(os.path.join(out_dir, "prefill_attn_probe.jsonl"), "a") as f:
        f.writelines(json.dumps(ln) + "\n" for ln in lines)


if __name__ == "__main__":
    main()
