"""Tutorial 09 — hierarchical (multi-tier) AllGather-GEMM.

Analog of reference tutorials/03 + 07's inter-node tier (ag_gemm_inter_node,
allgather_gemm.py:938-975): the mesh has a slow outer axis ("node" — DCN /
inter-slice) and a fast inner axis (ICI). Each device is the relay for its
own inner index: the local shard rides the outer ring between same-inner-
index peers while being pushed to inner peers, and the GEMM consumes rows
nearest-first — so the slow tier's transfers hide behind compute on rows
already present (see ops.allgather_gemm.ag_overlap_protocol_2d).

Run:  python -m tutorials.t09_ag_gemm_multitier [--sim 6]
      [--case correctness|correctness_persistent|perf]
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
    K, N = 256, 128 * n
    a = jax.random.normal(jax.random.key(0), (M, K), jnp.float32
                          ).astype(jnp.bfloat16)
    b = jax.random.normal(jax.random.key(1), (K, N), jnp.float32
                          ).astype(jnp.bfloat16)
    return a, b, ctx.shard(a, P(axes)), ctx.shard(b, P(None, axes))


@register_case("correctness")
def correctness():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from triton_dist_tpu.ops import ag_gemm
    from triton_dist_tpu.ops.gemm import GemmConfig
    ctx = world_context_2d()
    a, b, a_s, b_s = _shapes(ctx)
    cfg = GemmConfig(128, 128)
    c = jax.jit(lambda u, v: ag_gemm(ctx, u, v, axis=("node", "x"),
                                     cfg=cfg))(a_s, b_s)
    gold = a.astype(jnp.float32) @ b.astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(c, np.float32), gold, rtol=5e-2,
                               atol=5e-1)
    no, ni = ctx.axis_size("node"), ctx.axis_size("x")
    print(f"2-tier AG-GEMM over ({no} nodes x {ni} PEs) == "
          "all_gather+dot golden")


@register_case("correctness_persistent")
def correctness_persistent():
    """Persistent symmetric workspace threaded across repeated calls."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from triton_dist_tpu.ops import ag_gemm_ws, create_ag_gemm_workspace
    from triton_dist_tpu.ops.gemm import GemmConfig
    ctx = world_context_2d()
    n = ctx.num_ranks
    axes = ("node", "x")
    a, b, a_s, b_s = _shapes(ctx)
    ws = create_ag_gemm_workspace(ctx, a.shape[0] // n, a.shape[1],
                                  jnp.bfloat16, axis=axes)
    f = jax.jit(lambda u, v, w: ag_gemm_ws(ctx, u, v, w, axis=axes,
                                           cfg=GemmConfig(128, 128)))
    gold = a.astype(jnp.float32) @ b.astype(jnp.float32)
    for _ in range(3):
        c, ws = f(a_s, b_s, ws)
        np.testing.assert_allclose(np.asarray(c, np.float32), gold,
                                   rtol=5e-2, atol=5e-1)
    print("persistent-workspace 2-tier AG-GEMM: 3 calls")


@register_case("perf")
def perf():
    import jax

    from triton_dist_tpu.ops import ag_gemm
    from triton_dist_tpu.ops.gemm import GemmConfig
    ctx = world_context_2d()
    n = ctx.num_ranks
    _, _, a_s, b_s = _shapes(ctx, M=256 * n)
    cfg = GemmConfig(128, 128)
    f = jax.jit(lambda u, v: ag_gemm(ctx, u, v, axis=("node", "x"), cfg=cfg))
    s = time_op(lambda: f(a_s, b_s))
    M, K = a_s.shape
    N = b_s.shape[1]
    perf_report("ag_gemm_2d", s,
                f"~{2 * M * N * K / s / max(n, 1) / 1e12:.1f} TFLOP/s/chip "
                "(wall-clock; see bench.py for dispatch-corrected numbers)")


if __name__ == "__main__":
    tutorial_main(__doc__)
