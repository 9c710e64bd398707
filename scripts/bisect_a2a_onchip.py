"""On-chip bisection runbook for an EP A2A hang — runnable form.

The 2-tier graphs compile clean against an abstract topology with the
local libtpu (tests/test_aot_topology.py), so a hang on silicon is in
execution, not lowering. This script executes the recipe stage by stage in
SEPARATE subprocesses, each under a timeout, so one hung stage cannot take
the parent down with it. The parent never imports jax: a chip belongs to
one process at a time, and each stage's child releases it on exit.

    python scripts/bisect_a2a_onchip.py            # all stages
    python scripts/bisect_a2a_onchip.py put serial_push   # specific ones

Each kernel stage has a TDT_SERIAL=1 twin that runs first —
serial-passes/pipelined-hangs ⇒ protocol sync bug; both hang ⇒
lowering/runtime:
    put                known-good single-chip ring put (chip sanity)
    serial_push/push   bare all_to_all_push, 2-axis (1,1) mesh
    serial_d2d/d2d     dispatch_2d, (1,1)
    serial_roundtrip/roundtrip   dispatch_2d + combine_2d
    serial_d2d_fp8/d2d_fp8       quantized wire variant

Every stage asserts it runs on an accelerator: interpreter results must
never read as on-chip bisection evidence.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

STAGE_BODIES = {
    "put": """
from triton_dist_tpu.shmem.context import initialize_distributed
from triton_dist_tpu.shmem import device as shd
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P
import jax, jax.numpy as jnp
ctx = initialize_distributed(axis_names=("x",), mesh_shape=(1,))
def kernel(i_ref, o_ref, s_sem, r_sem):
    rdma = shd.putmem_nbi(o_ref, i_ref, s_sem, r_sem, shd.my_pe("x"))
    shd.quiet(rdma)
    shd.wait_recv(o_ref, r_sem)
f = lambda x: pl.pallas_call(
    kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
    in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
    out_specs=pl.BlockSpec(memory_space=pl.ANY),
    scratch_shapes=[pltpu.SemaphoreType.DMA(()), pltpu.SemaphoreType.DMA(())],
    compiler_params=pltpu.CompilerParams(has_side_effects=True),
    interpret=__import__("triton_dist_tpu.utils", fromlist=["x"]
                         ).default_interpret())(x)
x = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
y = jax.jit(ctx.shard_map(f, in_specs=P("x"), out_specs=P("x")))(x)
assert jnp.allclose(y, x), "self-put mismatch"
""",
    "push": """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from triton_dist_tpu.shmem.context import initialize_distributed
from triton_dist_tpu.ops.all_to_all import all_to_all_push
ctx = initialize_distributed(axis_names=("o", "i"), mesh_shape=(1, 1))
spec = P(("o", "i"))
x = jnp.arange(1 * 32 * 128, dtype=jnp.bfloat16).reshape(1, 32, 128)
(y,) = all_to_all_push(ctx, ctx.shard(x, spec), axis="i", spec=spec)
jax.block_until_ready(y)
assert jnp.allclose(y.astype(jnp.float32), x.astype(jnp.float32))
""",
    "d2d": """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from triton_dist_tpu.shmem.context import initialize_distributed
from triton_dist_tpu.ops.all_to_all import (create_all_to_all_context_2d,
                                            dispatch_2d)
ctx = initialize_distributed(axis_names=("o", "i"), mesh_shape=(1, 1))
T, H, topk, E = 8, 128, 2, 4
a2a = create_all_to_all_context_2d(ctx, max_tokens=T, hidden=H, topk=topk,
                                   num_experts=E, dtype=jnp.bfloat16{wire})
spec = P(("o", "i"))
t = jax.random.normal(jax.random.key(0), (T, H), jnp.float32).astype(jnp.bfloat16)
i = jax.random.randint(jax.random.key(1), (T, topk), 0, E)
rt, ri, lay = dispatch_2d(a2a, ctx.shard(t, spec), ctx.shard(i, spec))
jax.block_until_ready(rt)
""",
    "roundtrip": """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from triton_dist_tpu.shmem.context import initialize_distributed
from triton_dist_tpu.ops.all_to_all import (combine_2d,
                                            create_all_to_all_context_2d,
                                            dispatch_2d)
ctx = initialize_distributed(axis_names=("o", "i"), mesh_shape=(1, 1))
T, H, topk, E = 8, 128, 2, 4
a2a = create_all_to_all_context_2d(ctx, max_tokens=T, hidden=H, topk=topk,
                                   num_experts=E, dtype=jnp.bfloat16)
spec = P(("o", "i"))
t = jax.random.normal(jax.random.key(0), (T, H), jnp.float32).astype(jnp.bfloat16)
i = jax.random.randint(jax.random.key(1), (T, topk), 0, E)
w = jnp.full((T, topk), 1.0 / topk)
rt, ri, lay = dispatch_2d(a2a, ctx.shard(t, spec), ctx.shard(i, spec))
back = combine_2d(a2a, rt, lay, ctx.shard(w, spec))
jax.block_until_ready(back)
import numpy as np
np.testing.assert_allclose(np.asarray(back, np.float32),
                           np.asarray(t, np.float32), rtol=3e-2, atol=3e-2)
""",
}

# prepended to every stage: refuse to produce "bisection evidence" off-chip
_ON_CHIP = """
import jax
assert jax.devices()[0].platform == "tpu", (
    "bisection stage needs a TPU, jax sees " + str(jax.devices()))
"""

# (name, body_key, env overrides, wire-dtype code suffix)
FP8 = ", wire_dtype=jnp.float8_e4m3fn"
STAGES = [
    ("put", "put", {}, ""),
    ("serial_push", "push", {"TDT_SERIAL": "1"}, ""),
    ("push", "push", {}, ""),
    ("serial_d2d", "d2d", {"TDT_SERIAL": "1"}, ""),
    ("d2d", "d2d", {}, ""),
    ("serial_roundtrip", "roundtrip", {"TDT_SERIAL": "1"}, ""),
    ("roundtrip", "roundtrip", {}, ""),
    ("serial_d2d_fp8", "d2d", {"TDT_SERIAL": "1"}, FP8),
    ("d2d_fp8", "d2d", {}, FP8),
]


def run_stage(name: str, body_key: str, env_extra: dict, wire: str,
              timeout_s: int = 1200) -> str:
    body = _ON_CHIP + STAGE_BODIES[body_key].replace("{wire}", wire)
    env = {**os.environ, **env_extra}
    t0 = time.time()
    try:
        r = subprocess.run([sys.executable, "-c", body], env=env,
                           timeout=timeout_s, capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        return f"TIMEOUT after {timeout_s}s"
    dt = time.time() - t0
    if r.returncode == 0:
        return f"OK in {dt:.0f}s"
    tail = (r.stderr or r.stdout).strip().splitlines()[-6:]
    return f"rc={r.returncode} in {dt:.0f}s\n    " + "\n    ".join(tail)


def main() -> int:
    want = set(sys.argv[1:])
    known = {name for name, _, _, _ in STAGES}
    unknown = want - known
    if unknown:
        print(f"unknown stage(s) {sorted(unknown)}; "
              f"choose from {sorted(known)}", file=sys.stderr)
        return 2
    results = {}
    for name, body_key, env_extra, wire in STAGES:
        if want and name not in want:
            continue
        print(f"[bisect] {name} ...", flush=True)
        results[name] = run_stage(name, body_key, dict(env_extra), wire)
        print(f"[bisect] {name}: {results[name]}", flush=True)
        if not results[name].startswith("OK"):
            print("[bisect] stopping at first failure (run remaining "
                  "stages explicitly to continue)", flush=True)
            break
    print("\n=== summary ===")
    for k, v in results.items():
        print(f"{k:14s} {v.splitlines()[0]}")
    return 0 if (results
                 and all(v.startswith("OK") for v in results.values())) else 1


if __name__ == "__main__":
    sys.exit(main())
