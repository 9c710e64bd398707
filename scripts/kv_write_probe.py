"""On-chip probe of the K/V write ALONE (PR 47): ``ops.flash_decode.
paged_kv_write`` at the cells' pool shapes, a chunk's run of rows written by
the row scatter (``shared_table=False``, the parent's only form) and page by
page (``shared_table=True``), the pool donated and carried through a
``lax.scan`` over its planes with a traced ``layer`` as the programs carry it.

    python scripts/kv_write_probe.py [--shapes ouro,mistral] [--check]

One JSON line a (shape, start, form): DEVICE us a (plane, call) of both
pools, from a profiler trace of ``iters`` dispatches of the whole scan (the
program's time on the device over planes x dispatches: a dispatch costs the
host ~4 ms here, which at two planes a dispatch would be all a host clock
reads), and with ``--check`` whether every page but page 0 is the scatter's
bit for bit (compared on the device: the pools are gigabytes).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import trace as T  # noqa: E402
from triton_dist_tpu.ops.flash_decode import paged_kv_write  # noqa: E402
from triton_dist_tpu.utils.env import configure_compile_cache  # noqa: E402

PAGE = 128
# name: planes held here, pages, KV heads, key width, value width (None: one
# pool of [K | V] rows), rows of the chunk, pages of the sequence's table
SHAPES = {
    "ouro": (24, 42, 16, 128, 128, 256, 5),
    "mistral": (20, 209, 8, 128, 128, 256, 13),
    "command-a-plus": (2, 1201, 8, 128, 128, 2048, 200),
    "mimo-full": (2, 2593, 4, 256, 128, 512, 108),
    "lfm2": (2, 6914, 8, 128, None, 2048, 72),
}


def build(name):
    L, P, H, Dk, Dv, C, W = SHAPES[name]
    key = jax.random.PRNGKey(0)
    pool = lambda d: jax.random.normal(                        # noqa: E731
        key, (L, P, H, PAGE, d), jnp.bfloat16)
    pools = (pool(Dk), None if Dv is None else pool(Dv))
    rows = (jax.random.normal(key, (C, H, Dk if Dv else Dk // 2),
                              jnp.bfloat16),
            jax.random.normal(key, (C, H, Dv or Dk // 2), jnp.bfloat16))
    table = jnp.arange(1, 1 + W, dtype=jnp.int32)
    return pools, rows, table, C, L


def program(shared, C, L):
    def run(pools, rows, table, start, n):
        idx = start + jnp.arange(C, dtype=jnp.int32)
        valid = idx < n
        pos = jnp.where(valid, idx, 0)
        bt = jnp.broadcast_to(table[None], (C, table.shape[0]))

        def body(pools, layer):
            scale = (layer + 1).astype(jnp.bfloat16)
            return paged_kv_write(pools[0], pools[1], rows[0] * scale,
                                  rows[1] * scale, bt, pos, active=valid,
                                  layer=layer, shared_table=shared), None
        return jax.lax.scan(body, pools, jnp.arange(L, dtype=jnp.int32))[0]
    return jax.jit(run, donate_argnums=(0,))


def live_pages(pools):
    """Every page but the scratch page, the leaves that are there."""
    return [p[:, 1:] for p in pools if p is not None]


def measure(name, start_at, shared, iters):
    """(device us a plane, dispatches traced, the pools after one write)."""
    pools, rows, table, C, L = build(name)
    fn = program(shared, C, L)
    start, n = jnp.int32(start_at), jnp.int32(start_at + C - 5)
    pools = fn(pools, rows, table, start, n)              # compiles
    jax.block_until_ready(pools)
    once = live_pages(pools)
    with tempfile.TemporaryDirectory() as where:
        T.start(where)
        for _ in range(iters):
            pools = fn(pools, rows, table, start, n)
        jax.block_until_ready(pools)
        secs, runs = T.module_time_s(T.load(T.stop(where)),
                                     r"^jit_run(\(|$)")
    if not runs:
        raise SystemExit(f"{name}: the trace holds no execution of jit_run")
    return secs * 1e6 / (runs * L), runs, once


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="ouro,mistral,command-a-plus,"
                    "mimo-full,lfm2")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--check", action="store_true")
    a = ap.parse_args()
    configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"kv_write_probe times the device and needs a TPU; "
                         f"this is {dev.platform}")
    for name in a.shapes.split(","):
        L, C = SHAPES[name][0], SHAPES[name][5]
        for start_at in (0, 77):
            seen = {}
            for form, shared in (("scatter", False), ("pages", True)):
                us, runs, once = measure(name, start_at, shared, a.iters)
                if a.check:
                    seen[form] = once
                print(json.dumps({
                    "shape": name, "start": start_at, "form": form,
                    "us_a_plane": round(us, 2), "planes": L, "rows": C,
                    "dispatches_traced": runs,
                    "device": dev.device_kind}), flush=True)
            if a.check:
                same = all(bool(jnp.array_equal(x, y)) for x, y in zip(
                    seen["scatter"], seen["pages"], strict=True))
                print(json.dumps({"shape": name, "start": start_at,
                                  "live_pages_same": same}), flush=True)
                assert same, name


if __name__ == "__main__":
    main()
