"""Tutorial 05 — overlapping AllGather-GEMM (the first overlap op).

Analog of reference tutorials/07 + allgather_gemm.py. One kernel per
device: non-blocking puts of the local activation shard to every peer run
on the ICI DMA engines while the MXU computes segments in start-local
swizzled order, waiting each remote segment's arrival semaphore exactly
once. The persistent-workspace form (ag_gemm_ws) reuses a context-owned
symmetric buffer across calls.

Run:  python -m tutorials.t05_ag_gemm [--sim 4] [--case correctness|perf]
"""

from tutorials.common import (perf_report, register_case, time_op,
                              tutorial_main, world_context)


def _shapes(ctx, M=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    n = ctx.num_ranks
    M = M or 128 * n
    K, N = 256, 128 * n
    a = jax.random.normal(jax.random.key(0), (M, K), jnp.float32
                          ).astype(jnp.bfloat16)
    b = jax.random.normal(jax.random.key(1), (K, N), jnp.float32
                          ).astype(jnp.bfloat16)
    return a, b, ctx.shard(a, P("x")), ctx.shard(b, P(None, "x"))


@register_case("correctness")
def correctness():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from triton_dist_tpu.ops import ag_gemm
    from triton_dist_tpu.ops.gemm import GemmConfig
    ctx = world_context()
    n = ctx.num_ranks
    a, b, a_s, b_s = _shapes(ctx)
    cfg = GemmConfig(128, 128)
    c = jax.jit(lambda u, v: ag_gemm(ctx, u, v, axis="x", cfg=cfg))(a_s, b_s)
    gold = (a.astype(jnp.float32) @ b.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(c, np.float32), gold, rtol=5e-2,
                               atol=5e-1)
    print(f"overlapped AG-GEMM over {n} PEs == all_gather+dot golden")


@register_case("correctness_persistent")
def correctness_persistent():
    """Context-owned symmetric workspace reused across 3 calls."""
    import jax.numpy as jnp
    import numpy as np

    from triton_dist_tpu.ops import create_ag_gemm_context
    from triton_dist_tpu.ops.gemm import GemmConfig
    ctx = world_context()
    n = ctx.num_ranks
    a, b, a_s, b_s = _shapes(ctx)
    agc = create_ag_gemm_context(ctx, a.shape[0] // n, a.shape[1],
                                 jnp.bfloat16, axis="x")
    gold = a.astype(jnp.float32) @ b.astype(jnp.float32)
    for _ in range(3):
        c = agc(a_s, b_s, cfg=GemmConfig(128, 128))
        np.testing.assert_allclose(np.asarray(c, np.float32), gold,
                                   rtol=5e-2, atol=5e-1)
    print("persistent-workspace AG-GEMM: 3 calls, zero per-call workspace "
          "allocation")


@register_case("perf")
def perf():
    import jax

    from triton_dist_tpu.ops import ag_gemm
    from triton_dist_tpu.ops.gemm import GemmConfig
    ctx = world_context()
    n = ctx.num_ranks
    _, _, a_s, b_s = _shapes(ctx, M=512 * n)
    cfg = GemmConfig(128, 128)
    f = jax.jit(lambda u, v: ag_gemm(ctx, u, v, axis="x", cfg=cfg))
    s = time_op(lambda: f(a_s, b_s))
    M, K = a_s.shape
    N = b_s.shape[1]
    perf_report("ag_gemm", s,
                f"~{2 * M * N * K / s / max(n, 1) / 1e12:.1f} TFLOP/s/chip "
                "(wall-clock; see bench.py for dispatch-corrected numbers)")


if __name__ == "__main__":
    tutorial_main(__doc__)
