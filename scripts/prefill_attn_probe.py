"""On-chip probe of a prefill chunk's attention at Mistral-7B widths (ISSUE 27):
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
"""

from __future__ import annotations

import argparse
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="16,32,64")
    ap.add_argument("--reps", type=int, default=10)
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"a chip run: found {dev.platform}")
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (L, P, HKV, PAGE, D)
    kp = jax.jit(lambda k: jax.random.normal(k, shape, jnp.bfloat16))(kk)
    vp = jax.jit(lambda k: jax.random.normal(k, shape, jnp.bfloat16))(kv_)
    q = jax.random.normal(kq, (C, HQ, D), jnp.bfloat16)
    bt = jnp.asarray(np.random.default_rng(0).permutation(P - 1)[:PPS] + 1,
                     jnp.int32)
    variants = {"decode_rows": layers(
        lambda q, kp, vp, bt, kv, ly: gqa_decode_paged(
            q, kp, vp, jnp.broadcast_to(bt, (C, PPS)), kv, layer=ly)[0])}
    for rb in (int(r) for r in a.rows.split(",")):
        variants[f"prefill_rb{rb}"] = layers(
            lambda q, kp, vp, bt, kv, ly, rb=rb: gqa_prefill_paged(
                q, kp, vp, bt, kv, layer=ly, rows_per_block=rb))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    trace_dir = os.path.join(ROOT, ".bench_trace", "prefill_attn_probe")
    lines = []
    for start, real in CONTEXTS:
        idx = start + np.arange(C)
        kv = jnp.asarray(np.where(idx < start + real, idx + 1, 0), jnp.int32)
        want = None
        for name, fn in variants.items():
            got = np.asarray(fn(q, kp, vp, bt, kv).astype(jnp.float32))
            want = got if want is None else want
            t0 = time.perf_counter()
            for _ in range(a.reps):
                r = fn(q, kp, vp, bt, kv)
            r.block_until_ready()
            ms = (time.perf_counter() - t0) / a.reps / L * 1e3
            T.start(trace_dir)
            fn(q, kp, vp, bt, kv).block_until_ready()
            tr = T.load(T.stop(trace_dir))
            ops = tr.ops[min(tr.ops)] if tr.ops else []
            kern = sum(t for n, _, t in ops if "custom-call" in n and (
                "closed_call" in n or "gqa_prefill" in n))
            lines.append({
                "context": start, "real": real, "variant": name,
                "ms_layer": ms, "kernel_us": kern / L * 1e6,
                "gap": float(np.abs(got - want).max()),
                "device": dev.device_kind})
            print(json.dumps(lines[-1]), flush=True)
    with open(os.path.join(out_dir, "prefill_attn_probe.jsonl"), "a") as f:
        f.writelines(json.dumps(ln) + "\n" for ln in lines)


if __name__ == "__main__":
    main()
