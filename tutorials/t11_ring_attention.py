"""Tutorial 11 — differentiable ring attention (context parallelism).

Beyond the reference's scope (its sequence story is decode-only, SURVEY
§5.7): blockwise attention over a sequence-sharded KV cache where KV
blocks travel a ring (2-slot relay + ack credits — the reduce_scatter
transport) behind the per-step flash inner loop, with a backward ring in
which each block's (dk ‖ dv) accumulator arrives home after a full circle.

Run:  python -m tutorials.t11_ring_attention [--sim 4]
      [--case correctness|grad|perf]
"""

from tutorials.common import (perf_report, register_case, time_op,
                              tutorial_main, world_context)


def _inputs(ctx, s_loc=256, B=1, Hq=8, Hkv=2, D=128):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    n = ctx.num_ranks
    S = n * s_loc
    ks = jax.random.split(jax.random.key(0), 3)
    mk = lambda k, h: (jax.random.normal(k, (B, h, S, D), jnp.float32)
                       * 0.5).astype(jnp.bfloat16)
    q, k, v = mk(ks[0], Hq), mk(ks[1], Hkv), mk(ks[2], Hkv)
    spec = P(None, None, "x")
    return q, k, v, (ctx.shard(q, spec), ctx.shard(k, spec),
                     ctx.shard(v, spec))


def _dense(q, k, v):
    import jax
    import jax.numpy as jnp
    import numpy as np
    Hq, Hkv, S, D = q.shape[1], k.shape[1], q.shape[2], q.shape[3]
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    kf = jnp.repeat(kf, Hq // Hkv, axis=1)
    vf = jnp.repeat(vf, Hq // Hkv, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) / np.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vf)


@register_case("correctness")
def correctness():
    import jax
    import numpy as np

    from triton_dist_tpu.ops import ring_attention
    ctx = world_context()
    q, k, v, (qs, ks, vs) = _inputs(ctx)
    out = jax.jit(lambda a, b, c: ring_attention(ctx, a, b, c, axis="x",
                                                 causal=True))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(_dense(q, k, v)), rtol=4e-2,
                               atol=4e-2)
    print(f"ring attention over {ctx.num_ranks} PEs == dense causal golden")


@register_case("grad")
def grad():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from triton_dist_tpu.ops import ring_attention
    ctx = world_context()
    q, k, v, (qs, ks, vs) = _inputs(ctx, s_loc=128)
    tgt = jax.random.normal(jax.random.key(7), q.shape, jnp.float32)

    def loss_ring(a, b, c):
        o = ring_attention(ctx, a, b, c, axis="x", causal=True)
        return jnp.sum((o.astype(jnp.float32) - tgt) ** 2)

    def loss_dense(a, b, c):
        return jnp.sum((_dense(a, b, c) - tgt) ** 2)

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(qs, ks, vs)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for got, want, nm in zip(gr, gd, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=6e-2, atol=6e-1)
    print("backward ring == jax.grad of dense golden (dq, dk, dv)")


@register_case("perf")
def perf():
    import jax

    from triton_dist_tpu.ops import ring_attention
    ctx = world_context()
    n = ctx.num_ranks
    q, k, v, (qs, ks, vs) = _inputs(ctx, s_loc=1024, Hq=16, Hkv=4)
    f = jax.jit(lambda a, b, c: ring_attention(ctx, a, b, c, axis="x",
                                               causal=True))
    s = time_op(lambda: f(qs, ks, vs))
    B, Hq, S, D = q.shape
    flops = 2 * 2 * B * Hq * S * S * D / 2  # causal halves the work
    perf_report("ring_attention", s,
                f"~{flops / s / max(n, 1) / 1e12:.1f} TFLOP/s/chip "
                "(wall-clock; see bench.py for dispatch-corrected numbers)")


if __name__ == "__main__":
    tutorial_main(__doc__)
