"""Tutorial 10 — hierarchical (multi-tier) GEMM-ReduceScatter.

Analog of reference tutorials/06 + 08's inter-node tier (the 2-D RS
pipeline, reduce_scatter.py:430-785). Stage 1 fuses the producer GEMM into
a fast-tier (inner-axis) reduce-scatter whose segments are strided in
outer-major block order; stage 2 ring-reduces the surviving chunk along the
slow outer axis — every row crosses the slow tier exactly once, already
reduced over the fast tier (see ops.gemm_reduce_scatter._gemm_rs_2d).

Run:  python -m tutorials.t10_gemm_rs_multitier [--sim 6]
      [--case correctness|perf]
"""

from tutorials.common import (perf_report, register_case, time_op,
                              tutorial_main, world_context_2d)


def _shapes(ctx, M=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    n = ctx.num_ranks
    axes = ("node", "x")
    M = M or 128 * n
    K, N = 128 * n, 128
    a = jax.random.normal(jax.random.key(0), (M, K), jnp.float32
                          ).astype(jnp.bfloat16)
    b = jax.random.normal(jax.random.key(1), (K, N), jnp.float32
                          ).astype(jnp.bfloat16)
    return a, b, ctx.shard(a, P(None, axes)), ctx.shard(b, P(axes, None))


@register_case("correctness")
def correctness():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from triton_dist_tpu.ops import gemm_rs
    from triton_dist_tpu.ops.gemm import GemmConfig
    ctx = world_context_2d()
    a, b, a_s, b_s = _shapes(ctx)
    cfg = GemmConfig(128, 128)
    c = jax.jit(lambda u, v: gemm_rs(ctx, u, v, axis=("node", "x"),
                                     cfg=cfg, out_dtype=jnp.float32)
                )(a_s, b_s)
    gold = a.astype(jnp.float32) @ b.astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(c, np.float32), gold, rtol=5e-2,
                               atol=5e-1)
    no, ni = ctx.axis_size("node"), ctx.axis_size("x")
    print(f"2-tier GEMM-RS over ({no} nodes x {ni} PEs) == "
          "dot+psum_scatter golden")


@register_case("perf")
def perf():
    import jax
    import jax.numpy as jnp

    from triton_dist_tpu.ops import gemm_rs
    from triton_dist_tpu.ops.gemm import GemmConfig
    ctx = world_context_2d()
    n = ctx.num_ranks
    _, _, a_s, b_s = _shapes(ctx, M=256 * n)
    cfg = GemmConfig(128, 128)
    f = jax.jit(lambda u, v: gemm_rs(ctx, u, v, axis=("node", "x"),
                                     cfg=cfg, out_dtype=jnp.bfloat16))
    s = time_op(lambda: f(a_s, b_s))
    M, K = a_s.shape
    N = b_s.shape[1]
    perf_report("gemm_rs_2d", s,
                f"~{2 * M * N * K / s / max(n, 1) / 1e12:.1f} TFLOP/s/chip "
                "(wall-clock; see bench.py for dispatch-corrected numbers)")


if __name__ == "__main__":
    tutorial_main(__doc__)
