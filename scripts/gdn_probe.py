"""On-chip probe of the gated-delta-rule state kernels at Qwen3-Next widths
(ISSUE 39): 32 value heads over 16 key heads of 128 x 128, a pool leaf of 12
layers x 129 slots (3.3 GB float32); ``gdn_decode_update`` alone, 12 layer
calls chained under one jit with the leaf donated, host clock around ``--reps``
of them; then ``gdn_chunk_scan`` at a 2,048-row chunk.

  python scripts/gdn_probe.py [--reps 10] [--check]

One JSON line a case, also appended to ``chiprun_out/gdn_probe.jsonl``:
``decode``: ``rows`` live rows of 128, ``us_call`` a layer call,
``roofline_pct`` the rows' bytes (a state read and written back, 4.19 MB)
over 819 GB/s over that, for each block of heads (``--variants``);
``scan``: ``ms_call``. ``--check`` first
holds both kernels' VALUES on the chip to the plain recurrence (``check``
lines: the largest absolute differences)."""
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

from triton_dist_tpu.ops import gdn  # noqa: E402

L, S, H, HK, K, V, R, T = 12, 129, 32, 16, 128, 128, 128, 2048
HBM = 819e9


def inputs(key, rows):
    ks = jax.random.split(key, 5)
    l2 = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)  # noqa: E731
    return (l2(jax.random.normal(ks[0], (rows, HK, K))) * K ** -0.5,
            l2(jax.random.normal(ks[1], (rows, HK, K))),
            jax.random.normal(ks[2], (rows, H, V)),
            jax.nn.sigmoid(jax.random.normal(ks[3], (rows, H)) + 2.0),
            jax.nn.sigmoid(jax.random.normal(ks[4], (rows, H))))


def check(q, k, v, alpha, beta):
    """The kernels' values against the plain recurrence, on this device."""
    lines = []
    small = jax.random.normal(jax.random.PRNGKey(2), (2, 9, H, K, V))
    rows = 8
    slots = jnp.asarray([3, 1, 8, 5, 2, 7, 4, 6], jnp.int32)
    live = jnp.asarray([True, True, False, True, True, True, False, True])
    args = [x[:rows] for x in (q, k, v, alpha, beta)]
    o, new = jax.jit(lambda s: gdn.gdn_decode_update(
        s, 1, slots, live, *args))(small)
    o_want, s_want = gdn.gdn_step_reference(small[1][slots], *args)
    want = small.at[1, slots].set(jnp.where(live[:, None, None, None], s_want,
                                            small[1][slots]))
    lines.append({"case": "check", "op": "gdn_decode_update",
                  "state_err": float(jnp.abs(new - want).max()),
                  "o_err": float(jnp.abs(o - jnp.where(
                      live[:, None, None], o_want, 0.0)).max()),
                  "o_max": float(jnp.abs(o_want).max())})
    print(json.dumps(lines[-1]), flush=True)
    qc, kc, vc, ac, bc = inputs(jax.random.PRNGKey(1), T)
    s0 = jax.random.normal(jax.random.PRNGKey(3), (H, K, V))
    o, sT = jax.jit(lambda s0: gdn.gdn_chunk_scan(
        qc, kc, vc, jnp.log(ac), bc, s0, block=64))(s0)

    def token(s, t):
        o, s = gdn.gdn_step_reference(s[None], *(x[None] for x in t))
        return s[0], o[0]
    s_want, o_want = jax.jit(lambda s0: jax.lax.scan(
        token, s0, (qc, kc, vc, ac, bc)))(s0)
    lines.append({"case": "check", "op": "gdn_chunk_scan",
                  "state_err": float(jnp.abs(sT - s_want).max()),
                  "o_err": float(jnp.abs(o - o_want).max()),
                  "o_max": float(jnp.abs(o_want).max())})
    print(json.dumps(lines[-1]), flush=True)
    return lines


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--variants", default="16",
                    help="heads a block of the decode kernel, e.g. 16,8")
    ap.add_argument("--check", action="store_true")
    a = ap.parse_args()
    key = jax.random.PRNGKey(0)
    q, k, v, alpha, beta = inputs(key, R)
    slots = 1 + jnp.arange(R, dtype=jnp.int32)
    lines = []
    if a.check:
        lines += check(q, k, v, alpha, beta)
    for hb in a.variants.split(","):
        gdn.DECODE_HEADS_PER_BLOCK = int(hb)

        def layers(state, live, q):
            def one(carry, layer):
                state, q = carry
                o, state = gdn.gdn_decode_update(state, layer, slots, live, q,
                                                 k, v, alpha, beta)
                # each call is fed the one before (nothing can be elided)
                return (state, q + 1e-6 * o[:, ::2]), None
            return jax.lax.scan(one, (state, q), jnp.arange(L))[0]

        run = jax.jit(layers, donate_argnums=(0,))
        for rows in (128, 64, 0):
            live = jnp.arange(R) < rows
            state = jnp.zeros((L, S, H, K, V), jnp.float32)
            state, _ = run(state, live, q)
            jax.block_until_ready(state)
            t = time.perf_counter()
            for _ in range(a.reps):
                state, out = run(state, live, q)
            jax.block_until_ready((state, out))
            s = (time.perf_counter() - t) / (a.reps * L)
            least = rows * 2 * H * K * V * 4 / HBM
            lines.append({"case": "decode", "heads_per_block": int(hb),
                          "rows": rows,
                          "us_call": s * 1e6,
                          "roofline_pct": 100 * least / s})
            print(json.dumps(lines[-1]), flush=True)
            del state
    qc, kc, vc, ac, bc = inputs(jax.random.PRNGKey(1), T)
    scan = jax.jit(lambda s0: gdn.gdn_chunk_scan(qc, kc, vc, jnp.log(ac), bc,
                                                 s0, block=64))
    s0 = jnp.zeros((H, K, V), jnp.float32)
    o, sT = scan(s0)
    jax.block_until_ready(sT)
    t = time.perf_counter()
    for _ in range(a.reps):
        o, sT = scan(sT)
    jax.block_until_ready(sT)
    lines.append({"case": "scan", "rows": T,
                  "ms_call": (time.perf_counter() - t) / a.reps * 1e3})
    print(json.dumps(lines[-1]), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "gdn_probe.jsonl"), "a") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
