"""Grouped GEMM + MoE overlap op tests (parity targets: reference
test/nvidia/test_ag_moe.py, test_moe_reduce_rs.py — dense goldens)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from conftest import TEST_WORLD
from triton_dist_tpu.ops.group_gemm import (align_tokens_by_expert,
                                            apply_grouped, grouped_gemm,
                                            grouped_gemm_gated,
                                            moe_ffn_local)
from triton_dist_tpu.ops.moe import ag_moe_group_gemm, moe_reduce_rs
from triton_dist_tpu.shmem.context import initialize_distributed
from triton_dist_tpu.utils import assert_allclose


@pytest.fixture(scope="module")
def ctx():
    return initialize_distributed(axis_names=("x",), mesh_shape=(TEST_WORLD,))


def test_grouped_gemm_dense_golden():
    E, H, N, bm = 4, 64, 128, 16
    T = 64
    ids = jax.random.randint(jax.random.key(0), (T,), 0, E)
    tokens = jax.random.normal(jax.random.key(1), (T, H), jnp.float32)
    weights = jax.random.normal(jax.random.key(2), (E, H, N), jnp.float32)
    gather_idx, row_valid, block_expert = align_tokens_by_expert(ids, E, bm)
    x = tokens[np.asarray(gather_idx)] * np.asarray(row_valid)[:, None]
    y = jax.jit(lambda x, w, be: grouped_gemm(x, w, be, block_m=bm, block_n=64))(
        x, weights, block_expert)
    # golden: each aligned row through its block's expert
    yn = np.asarray(y)
    be = np.asarray(block_expert)
    for blk in range(len(be)):
        rows = slice(blk * bm, (blk + 1) * bm)
        golden = np.asarray(x)[rows] @ np.asarray(weights)[be[blk]]
        assert_allclose(yn[rows], golden, atol=1e-3, rtol=1e-3)


def test_grouped_gemm_gated_matches_unfused():
    """The fused gate+up+act kernel == the two-launch composition it
    replaces, on both the static and runtime-bounded paths."""
    E, H, F, bm = 4, 64, 128, 16
    T = 56
    ids = jax.random.randint(jax.random.key(0), (T,), 0, E)
    tokens = jax.random.normal(jax.random.key(1), (T, H), jnp.float32)
    wg = jax.random.normal(jax.random.key(2), (E, H, F), jnp.float32) * 0.1
    wu = jax.random.normal(jax.random.key(3), (E, H, F), jnp.float32) * 0.1
    gi, rv, be, nb = align_tokens_by_expert(ids, E, bm, with_used_count=True)
    x = tokens[np.asarray(gi)] * np.asarray(rv)[:, None]

    def unfused(x, wg, wu, be, nb):
        g = grouped_gemm(x, wg, be, block_m=bm, block_n=64,
                         n_blocks_used=nb)
        u = grouped_gemm(x, wu, be, block_m=bm, block_n=64,
                         n_blocks_used=nb)
        return jax.nn.silu(g) * u

    want = jax.jit(unfused)(x, wg, wu, be, nb)
    got_static = jax.jit(lambda *a: grouped_gemm_gated(
        *a, block_m=bm, block_n=64))(x, wg, wu, be)
    got_bounded = jax.jit(lambda *a, n=nb: grouped_gemm_gated(
        *a, block_m=bm, block_n=64, n_blocks_used=n))(x, wg, wu, be)
    valid = np.asarray(rv)[:, None]
    assert_allclose(np.asarray(got_bounded), np.asarray(want),
                    atol=1e-4, rtol=1e-4)
    # static path computes every block (padding included) — compare on
    # valid rows
    assert_allclose(np.asarray(got_static) * valid,
                    np.asarray(want) * valid, atol=1e-4, rtol=1e-4)


def test_grouped_gemm_gated_row_scale():
    """Quantized-wire rows: the per-row scale folded into both f32
    accumulators equals dequantize-then-compute."""
    E, H, F, bm = 2, 32, 64, 8
    P_rows = 4 * bm
    be = jnp.array([0, 1, 0, 1], jnp.int32)
    q = jax.random.randint(jax.random.key(0), (P_rows, H), -64, 64
                           ).astype(jnp.int8)
    scale = jax.random.uniform(jax.random.key(1), (P_rows,), jnp.float32,
                               0.01, 0.1)
    wg = jax.random.normal(jax.random.key(2), (E, H, F), jnp.float32) * 0.1
    wu = jax.random.normal(jax.random.key(3), (E, H, F), jnp.float32) * 0.1
    got = jax.jit(lambda *a: grouped_gemm_gated(
        *a[:4], block_m=bm, block_n=64, row_scale=a[4],
        out_dtype=jnp.float32))(q, wg, wu, be, scale)
    xf = np.asarray(q, np.float32) * np.asarray(scale)[:, None]
    want = np.zeros((P_rows, F), np.float32)
    for blk in range(4):
        rows = slice(blk * bm, (blk + 1) * bm)
        g = xf[rows] @ np.asarray(wg)[be[blk]]
        u = xf[rows] @ np.asarray(wu)[be[blk]]
        want[rows] = g / (1 + np.exp(-g)) * u
    assert_allclose(np.asarray(got), want, atol=1e-3, rtol=1e-3)


def test_grouped_gemm_ksplit_matches():
    """block_k (K-split accumulation through the f32 VMEM scratch) matches
    the full-K strip path on both ops, row_scale included."""
    E, H, F, bm = 4, 128, 128, 16
    T = 56
    ids = jax.random.randint(jax.random.key(0), (T,), 0, E)
    tokens = jax.random.normal(jax.random.key(1), (T, H), jnp.float32)
    w = jax.random.normal(jax.random.key(2), (E, H, F), jnp.float32) * 0.1
    wu = jax.random.normal(jax.random.key(3), (E, H, F), jnp.float32) * 0.1
    gi, rv, be, nb = align_tokens_by_expert(ids, E, bm, with_used_count=True)
    x = tokens[np.asarray(gi)] * np.asarray(rv)[:, None]
    scale = jax.random.uniform(jax.random.key(4), (x.shape[0],),
                               jnp.float32, 0.5, 1.5)

    full = jax.jit(lambda *a: grouped_gemm(
        *a[:3], block_m=bm, block_n=64, n_blocks_used=nb,
        row_scale=a[3]))(x, w, be, scale)
    split = jax.jit(lambda *a: grouped_gemm(
        *a[:3], block_m=bm, block_n=64, n_blocks_used=nb,
        row_scale=a[3], block_k=32))(x, w, be, scale)
    assert_allclose(np.asarray(split), np.asarray(full), atol=1e-4,
                    rtol=1e-4)

    full_g = jax.jit(lambda *a: grouped_gemm_gated(
        *a, block_m=bm, block_n=64, n_blocks_used=nb))(x, w, wu, be)
    split_g = jax.jit(lambda *a: grouped_gemm_gated(
        *a, block_m=bm, block_n=64, n_blocks_used=nb, block_k=32))(
        x, w, wu, be)
    assert_allclose(np.asarray(split_g), np.asarray(full_g), atol=1e-4,
                    rtol=1e-4)


@pytest.mark.quick
def test_gated_packed_matches():
    """packed=True (interleaved [g_j|u_j] single weight stream) matches
    the two-stream bounded path, with and without K-split/row_scale."""
    from triton_dist_tpu.ops.group_gemm import pack_gated_weights

    E, H, F, bm, bn = 4, 64, 128, 16, 32
    T = 56
    ids = jax.random.randint(jax.random.key(0), (T,), 0, E)
    tokens = jax.random.normal(jax.random.key(1), (T, H), jnp.float32)
    wg = jax.random.normal(jax.random.key(2), (E, H, F), jnp.float32) * 0.1
    wu = jax.random.normal(jax.random.key(3), (E, H, F), jnp.float32) * 0.1
    gi, rv, be, nb = align_tokens_by_expert(ids, E, bm, with_used_count=True)
    x = tokens[np.asarray(gi)] * np.asarray(rv)[:, None]
    scale = jax.random.uniform(jax.random.key(4), (x.shape[0],),
                               jnp.float32, 0.5, 1.5)
    wgu = pack_gated_weights(wg, wu, block_n=bn)

    want = jax.jit(lambda *a: grouped_gemm_gated(
        *a[:4], block_m=bm, block_n=bn, n_blocks_used=nb,
        row_scale=a[4]))(x, wg, wu, be, scale)
    got = jax.jit(lambda *a: grouped_gemm_gated(
        a[0], a[1], None, a[2], block_m=bm, block_n=bn, n_blocks_used=nb,
        row_scale=a[3], packed=True))(x, wgu, be, scale)
    assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                    rtol=1e-4)
    got_ks = jax.jit(lambda *a: grouped_gemm_gated(
        a[0], a[1], None, a[2], block_m=bm, block_n=bn, n_blocks_used=nb,
        row_scale=a[3], packed=True, block_k=32))(x, wgu, be, scale)
    assert_allclose(np.asarray(got_ks), np.asarray(want), atol=1e-4,
                    rtol=1e-4)


def test_gated_quantized_convert_once():
    """Quantized-wire rows through the BOUNDED gated kernel with multiple
    n-steps (and with K-split): the per-m-step x-conversion scratch path
    must match the per-tile-convert unbounded path bit-for-bit-ish."""
    E, H, F, bm = 2, 64, 128, 8
    P_rows = 4 * bm
    be = jnp.array([0, 1, 0, 1], jnp.int32)
    nb = jnp.int32(4)
    q = jax.random.randint(jax.random.key(0), (P_rows, H), -64, 64
                           ).astype(jnp.int8)
    scale = jax.random.uniform(jax.random.key(1), (P_rows,), jnp.float32,
                               0.01, 0.1)
    wg = (jax.random.normal(jax.random.key(2), (E, H, F)) * 0.1
          ).astype(jnp.float32)
    wu = (jax.random.normal(jax.random.key(3), (E, H, F)) * 0.1
          ).astype(jnp.float32)
    want = jax.jit(lambda *a: grouped_gemm_gated(
        *a[:4], block_m=bm, block_n=32, row_scale=a[4],
        out_dtype=jnp.float32))(q, wg, wu, be, scale)
    got = jax.jit(lambda *a: grouped_gemm_gated(
        *a[:4], block_m=bm, block_n=32, row_scale=a[4],
        out_dtype=jnp.float32, n_blocks_used=nb))(q, wg, wu, be, scale)
    assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                    rtol=1e-4)
    got_ks = jax.jit(lambda *a: grouped_gemm_gated(
        *a[:4], block_m=bm, block_n=32, row_scale=a[4],
        out_dtype=jnp.float32, n_blocks_used=nb, block_k=32))(
        q, wg, wu, be, scale)
    assert_allclose(np.asarray(got_ks), np.asarray(want), atol=1e-4,
                    rtol=1e-4)


def test_apply_grouped_unmasked_ffn():
    """The masked=False fast path through apply_grouped (undefined rows
    past the bound are dropped by scatter index) matches moe_ffn_local's
    masked composition, invalid ids included."""
    E, H, F, bm = 4, 64, 128, 16
    T = 48
    ids = jax.random.randint(jax.random.key(0), (T,), -1, E)
    tokens = jax.random.normal(jax.random.key(1), (T, H), jnp.float32)
    wg = jax.random.normal(jax.random.key(2), (E, H, F), jnp.float32) * 0.1
    wd = jax.random.normal(jax.random.key(3), (E, F, H), jnp.float32) * 0.1

    def ffn(x, be, nb):
        h = grouped_gemm_gated(x, wg, wg, be, block_m=bm, block_n=64,
                               n_blocks_used=nb, masked=False)
        return grouped_gemm(h, wd, be, block_m=bm, n_blocks_used=nb,
                            masked=False)

    got = jax.jit(lambda t, i: apply_grouped(t, i, E, ffn, block_m=bm))(
        tokens, ids)
    t, idn = np.asarray(tokens), np.asarray(ids)
    golden = np.zeros_like(t)
    for r in range(T):
        if idn[r] >= 0:
            g = t[r] @ np.asarray(wg)[idn[r]]
            h = g / (1 + np.exp(-g)) * g
            golden[r] = h @ np.asarray(wd)[idn[r]]
    assert_allclose(np.asarray(got), golden, atol=1e-3, rtol=1e-3)


def test_moe_ffn_local_golden():
    E, H, F, bm = 4, 64, 128, 16
    T = 48
    ids = jax.random.randint(jax.random.key(0), (T,), -1, E)  # some invalid
    tokens = jax.random.normal(jax.random.key(1), (T, H), jnp.float32)
    w_up = jax.random.normal(jax.random.key(2), (E, H, F), jnp.float32) * 0.1
    w_down = jax.random.normal(jax.random.key(3), (E, F, H), jnp.float32) * 0.1
    out = jax.jit(lambda t, i, wu, wd: moe_ffn_local(t, i, wu, wd, block_m=bm))(
        tokens, ids, w_up, w_down)
    t, idn = np.asarray(tokens), np.asarray(ids)
    golden = np.zeros_like(t)
    for r in range(T):
        if idn[r] >= 0:
            h = t[r] @ np.asarray(w_up)[idn[r]]
            h = h / (1 + np.exp(-h))  # silu
            golden[r] = h @ np.asarray(w_down)[idn[r]]
    assert_allclose(np.asarray(out), golden, atol=1e-3, rtol=1e-3)


def test_ag_moe_group_gemm(ctx):
    n = ctx.num_ranks
    E, H, N, T = 4, 64, n * 64, n * 32
    tokens = jax.random.normal(jax.random.key(0), (T, H), jnp.float32)
    ids = jax.random.randint(jax.random.key(1), (T,), 0, E)
    weights = jax.random.normal(jax.random.key(2), (E, H, N), jnp.float32) * 0.1
    out = jax.jit(lambda t, i, w: ag_moe_group_gemm(
        ctx, ctx.shard(t, P("x")), ctx.shard(i, P("x")),
        ctx.shard(w, P(None, None, "x")), block_m=32))(tokens, ids, weights)
    t, idn, wn = np.asarray(tokens), np.asarray(ids), np.asarray(weights)
    golden = np.stack([t[r] @ wn[idn[r]] for r in range(T)])
    assert_allclose(np.asarray(out), golden, atol=1e-3, rtol=1e-3)


def test_moe_reduce_rs_ragged_n(ctx):
    """N=192 is not a multiple of the 128-lane tile — the reduction and the
    grouped pipeline must fall back to a divisor, not drop columns."""
    n = ctx.num_ranks
    E, K, N, T, topk = 4, n * 32, 192, n * 8, 2
    tokens = jax.random.normal(jax.random.key(0), (T * topk, K), jnp.float32)
    ids = jax.random.randint(jax.random.key(1), (T * topk,), 0, E)
    tw = jax.nn.softmax(jax.random.normal(jax.random.key(2), (T, topk)), -1)
    weights = jax.random.normal(jax.random.key(3), (E, K, N), jnp.float32) * 0.1
    out = jax.jit(lambda t, i, w, ww: moe_reduce_rs(
        ctx, ctx.shard(t, P(None, "x")), i, ww,
        ctx.shard(w, P(None, "x", None)), block_m=16))(tokens, ids, weights, tw)
    t, idn, wn = np.asarray(tokens), np.asarray(ids), np.asarray(weights)
    rows = np.stack([t[r] @ wn[idn[r]] for r in range(T * topk)])
    golden = (rows.reshape(T, topk, N) * np.asarray(tw)[..., None]).sum(axis=1)
    assert_allclose(np.asarray(out), golden, atol=1e-3, rtol=1e-3)


def test_moe_reduce_rs(ctx):
    n = ctx.num_ranks
    E, K, N, T, topk = 4, n * 32, 64, n * 8, 2
    tokens = jax.random.normal(jax.random.key(0), (T * topk, K), jnp.float32)
    ids = jax.random.randint(jax.random.key(1), (T * topk,), 0, E)
    tw = jax.nn.softmax(jax.random.normal(jax.random.key(2), (T, topk)), -1)
    weights = jax.random.normal(jax.random.key(3), (E, K, N), jnp.float32) * 0.1
    out = jax.jit(lambda t, i, w, ww: moe_reduce_rs(
        ctx, ctx.shard(t, P(None, "x")), i, ww,
        ctx.shard(w, P(None, "x", None)), block_m=16))(tokens, ids, weights, tw)
    t, idn, wn = np.asarray(tokens), np.asarray(ids), np.asarray(weights)
    twn = np.asarray(tw)
    rows = np.stack([t[r] @ wn[idn[r]] for r in range(T * topk)])
    golden = (rows.reshape(T, topk, N) * twn[..., None]).sum(axis=1)
    assert_allclose(np.asarray(out), golden, atol=1e-3, rtol=1e-3)


def test_gated_packed_prefetch_depths():
    """The deep weight-stream DMA ring (prefetch_depth >= 2) must be
    bit-identical to the emit_pipeline weight stream it replaces
    (prefetch_depth=1 falls back) at every depth, with and without
    K-split — the ring only changes WHEN weight tiles are fetched, never
    what is computed."""
    from triton_dist_tpu.ops.group_gemm import pack_gated_weights

    E, H, F, bm, bn = 4, 64, 128, 16, 32
    ids = jax.random.randint(jax.random.key(0), (56,), 0, E)
    tokens = jax.random.normal(jax.random.key(1), (56, H), jnp.float32)
    wg = jax.random.normal(jax.random.key(2), (E, H, F), jnp.float32) * 0.1
    wu = jax.random.normal(jax.random.key(3), (E, H, F), jnp.float32) * 0.1
    gi, rv, be, nb = align_tokens_by_expert(ids, E, bm, with_used_count=True)
    x = tokens[np.asarray(gi)] * np.asarray(rv)[:, None]
    wgu = pack_gated_weights(wg, wu, block_n=bn)

    ref = np.asarray(jax.jit(lambda *a: grouped_gemm_gated(
        a[0], a[1], None, a[2], block_m=bm, block_n=bn, n_blocks_used=nb,
        packed=True, prefetch_depth=1))(x, wgu, be))
    for depth in (2, 3):
        got = np.asarray(jax.jit(lambda *a, d=depth: grouped_gemm_gated(
            a[0], a[1], None, a[2], block_m=bm, block_n=bn,
            n_blocks_used=nb, packed=True, prefetch_depth=d))(x, wgu, be))
        np.testing.assert_array_equal(got, ref)
        got_ks = np.asarray(jax.jit(lambda *a, d=depth: grouped_gemm_gated(
            a[0], a[1], None, a[2], block_m=bm, block_n=bn,
            n_blocks_used=nb, packed=True, prefetch_depth=d,
            block_k=32))(x, wgu, be))
        ref_ks = np.asarray(jax.jit(lambda *a: grouped_gemm_gated(
            a[0], a[1], None, a[2], block_m=bm, block_n=bn,
            n_blocks_used=nb, packed=True, prefetch_depth=1,
            block_k=32))(x, wgu, be))
        np.testing.assert_array_equal(got_ks, ref_ks)


def test_packed_gated_weights_wrapper_contract():
    """PackedGatedWeights carries the pack width in the type: the kernel
    accepts a matching wrapper and REJECTS a mismatched one (a bare array
    only gets the divisibility check — the reason the wrapper exists)."""
    from triton_dist_tpu.ops.group_gemm import (PackedGatedWeights,
                                                pack_gated_weights)

    E, H, F, bm, bn = 2, 64, 128, 16, 32
    x = jax.random.normal(jax.random.key(0), (2 * bm, H), jnp.float32)
    wg = jax.random.normal(jax.random.key(1), (E, H, F), jnp.float32) * 0.1
    wu = jax.random.normal(jax.random.key(2), (E, H, F), jnp.float32) * 0.1
    be = jnp.zeros((2,), jnp.int32)
    nb = jnp.int32(2)
    wgu = pack_gated_weights(wg, wu, block_n=bn)
    assert isinstance(wgu, PackedGatedWeights) and wgu.block_n == bn
    # pytree roundtrip keeps the pack width (static aux data under jit)
    leaves, tree = jax.tree_util.tree_flatten(wgu)
    assert jax.tree_util.tree_unflatten(tree, leaves).block_n == bn

    ok = grouped_gemm_gated(x, wgu, None, be, block_m=bm, block_n=bn,
                            n_blocks_used=nb, packed=True)
    assert ok.shape == (2 * bm, F)
    with pytest.raises(AssertionError, match="block_n"):
        grouped_gemm_gated(x, wgu, None, be, block_m=bm, block_n=64,
                           n_blocks_used=nb, packed=True)


def test_moe_ep_overlap_expert_major(ctx):
    """The expert-major serving block: recv blocks arrive expert-segmented,
    so moe_mlp_ep_overlap takes the static block→expert fast path (no
    align gather / inverse scatter) — and must match the rank-major
    align path, with the packed weight stream and on the int8 wire."""
    from triton_dist_tpu.layers import EPAll2AllLayer
    from triton_dist_tpu.models.moe import moe_mlp_ep_overlap
    from triton_dist_tpu.ops.group_gemm import pack_gated_weights

    n = ctx.num_ranks
    T_local, D, F, E, k = 16, 128, 128, 2 * n, 2
    T = n * T_local
    x = (jax.random.normal(jax.random.key(0), (T, D), jnp.float32) * 0.3
         ).astype(jnp.bfloat16)
    router_w = jax.random.normal(jax.random.key(1), (D, E), jnp.float32) * 0.3
    wg = (jax.random.normal(jax.random.key(2), (E, D, F)) * 0.1
          ).astype(jnp.bfloat16)
    wu = (jax.random.normal(jax.random.key(3), (E, D, F)) * 0.1
          ).astype(jnp.bfloat16)
    wd = (jax.random.normal(jax.random.key(4), (E, F, D)) * 0.1
          ).astype(jnp.bfloat16)
    xs = ctx.shard(x, P("x"))

    outs = {}
    for em in (False, True):
        layer = EPAll2AllLayer.create(ctx, max_tokens=T_local, hidden=D,
                                      topk=k, num_experts=E, axis="x",
                                      expert_major=em)
        outs[em] = np.asarray(jax.jit(lambda v, l=layer: moe_mlp_ep_overlap(
            ctx, l, v, router_w, wg, wu, wd, axis="x", block_m=16))(xs),
            np.float32)
    assert_allclose(outs[True], outs[False], atol=1e-5, rtol=1e-5)

    # packed double-width weight stream on the fast path
    layer = EPAll2AllLayer.create(ctx, max_tokens=T_local, hidden=D, topk=k,
                                  num_experts=E, axis="x", expert_major=True)
    wgu = pack_gated_weights(wg, wu, block_n=64)
    got_p = np.asarray(jax.jit(lambda v: moe_mlp_ep_overlap(
        ctx, layer, v, router_w, wg, wu, wd, axis="x", block_m=16,
        block_n=64, we_gate_up_packed=wgu))(xs), np.float32)
    assert_allclose(got_p, outs[True], atol=2e-2, rtol=2e-2)

    # int8 wire, both dequant edges, still on the fast path
    for de in ("expert", "post"):
        layer = EPAll2AllLayer.create(ctx, max_tokens=T_local, hidden=D,
                                      topk=k, num_experts=E, axis="x",
                                      wire_dtype=jnp.int8, dequant_edge=de,
                                      expert_major=True)
        o = np.asarray(jax.jit(lambda v, l=layer: moe_mlp_ep_overlap(
            ctx, l, v, router_w, wg, wu, wd, axis="x", block_m=16))(xs),
            np.float32)
        assert_allclose(o, outs[True], atol=6e-2, rtol=6e-2)


# -- the bounded grouped GEMMs walk an expert's RUN of row blocks (PR 44) --------

def _parent_walk(tokens, ws, be, *, block_m, block_n, n_blocks_used,
                 masked=True, block_k=None, row_scale=None, gated=False):
    """The walk the bounded grouped GEMMs made before they walked runs, kept
    here as the oracle: ``emit_pipeline`` over (row block, column tile[, k]),
    the weight tile of ``be[i]`` fetched at every step. Same products, same
    epilogue (``_gemm_block`` / ``_gated_math``), same cast."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from triton_dist_tpu.ops.group_gemm import _gated_math
    from triton_dist_tpu.utils import default_interpret

    P, H = tokens.shape
    N, n_w = ws[0].shape[2], len(ws)
    nk = 1 if block_k is None else H // block_k
    n_sc = 0 if row_scale is None else 1
    dt = tokens.dtype

    def finish(accs, sc_row):
        if gated:
            return _gated_math(*accs, sc_row, dt, jax.nn.silu)
        return (accs[0] if sc_row is None
                else accs[0] * sc_row[:, None]).astype(dt)

    def kernel(be_ref, nb_ref, t_ref, *refs):
        w_refs, sc_refs = refs[:n_w], refs[n_w:n_w + n_sc]
        o_ref, accs = refs[n_w + n_sc], refs[n_w + n_sc + 1:]

        def body(t_blk, *rest):
            o_blk = rest[-1]
            sc_row = rest[n_w][0] if n_sc else None
            parts = [jnp.dot(t_blk[...], w[0],
                             preferred_element_type=jnp.float32)
                     for w in rest[:n_w]]
            if nk == 1:
                o_blk[...] = finish(parts, sc_row)
                return
            k = pl.program_id(2)

            @pl.when(k == 0)
            def _():
                for a, p in zip(accs, parts):
                    a[...] = p

            @pl.when(k > 0)
            def _():
                for a, p in zip(accs, parts):
                    a[...] = a[...] + p

            @pl.when(k == nk - 1)
            def _():
                o_blk[...] = finish([a[...] for a in accs], sc_row)

        pltpu.emit_pipeline(
            body, grid=(jnp.minimum(nb_ref[0], P // block_m), N // block_n, nk),
            in_specs=[pl.BlockSpec((block_m, H // nk), lambda i, j, k: (i, k))]
            + [pl.BlockSpec((1, H // nk, block_n),
                            lambda i, j, k: (be_ref[i], k, j))] * n_w
            + [pl.BlockSpec((1, block_m), lambda i, j, k: (i, 0))] * n_sc,
            out_specs=[pl.BlockSpec((block_m, block_n),
                                    lambda i, j, k: (i, j))],
        )(t_ref, *w_refs, *sc_refs, o_ref)

    nb = jnp.asarray(n_blocks_used, jnp.int32).reshape(1)
    out = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 2
        + [pl.BlockSpec(memory_space=pl.ANY)] * (1 + n_w + n_sc),
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=([pltpu.VMEM((block_m, block_n), jnp.float32)] * n_w
                        if nk > 1 else []),
        out_shape=jax.ShapeDtypeStruct((P, N), dt),
        interpret=default_interpret(),
    )(be, nb, tokens, *ws,
      *([row_scale.reshape(P // block_m, block_m)] if n_sc else []))
    if not masked:
        return out
    live = jnp.arange(P, dtype=jnp.int32) // block_m < nb[0]
    return jnp.where(live[:, None], out, jnp.zeros((), dt))


def _parent_gemm(tokens, weights, be, **kw):
    return _parent_walk(tokens, [weights], be, **kw)


def _parent_gated(tokens, w_gate, w_up, be, **kw):
    return _parent_walk(tokens, [w_gate, w_up], be, gated=True, **kw)


# rows an expert (a 16-row block): runs of 1, 2, 3 and 5 blocks (5 crosses the
# cut at 4), an expert with no rows between two with many
_RUN_LOADS = {
    "runs-of-1": (16, 16, 16, 16, 16, 16),
    "runs-of-2": (32, 32, 32),
    "runs-of-3": (48, 41),
    "runs-of-5": (80, 16),
    "mixed": (16, 32, 48, 80, 5),
    "none-between-many": (40, 0, 70, 3),
}
# (load, blocks the bound leaves off, masked, row_scale, block_k, x strips
# the VMEM budget leaves: None = the 8 these small shapes get)
_RUN_CASES = {
    **{name: (name, 0, True, False, None, None) for name in _RUN_LOADS},
    "bound-below-zeroed": ("none-between-many", 2, True, False, None, None),
    "bound-below-untouched": ("mixed", 3, False, False, None, None),
    "row-scale": ("mixed", 0, True, True, None, None),
    "k-split": ("mixed", 0, True, False, 64, None),
    "k-split-row-scale": ("none-between-many", 1, True, True, 64, None),
    # runs cut at 2, and a next run's second strip fetched behind this one's
    "three-strips": ("mixed", 0, True, True, None, 3),
    "two-strips": ("none-between-many", 0, True, False, None, 2),
}


def _bits(a):
    return np.asarray(a).view(np.uint16)


@pytest.mark.parametrize("kernel, case", [
    (k, c) for c in _RUN_CASES for k in ("grouped_gemm", "grouped_gemm_gated")
] + [("held_experts", "skewed-128-rows")])
def test_run_walk_is_the_parents_walk_bit_for_bit(kernel, case, monkeypatch):
    """A weight tile stays in VMEM over the consecutive row blocks of one
    expert; every output block is the product the walk by row blocks made."""
    from triton_dist_tpu.ops import group_gemm as gg
    if kernel == "held_experts":
        from triton_dist_tpu.models.expert_share import held_experts
        R, k, D, Fe, held = 128, 4, 128, 128, 4
        keys = jax.random.split(jax.random.key(7), 5)
        # 128 rows an expert on average, skewed: 3 + 1 + 2 + 1 blocks of 128
        lid = jnp.asarray(np.random.default_rng(0).permutation(
            np.repeat(np.arange(held), (300, 10, 150, 52))).reshape(R, k),
            jnp.int32)
        h = jax.random.normal(keys[0], (R, D)).astype(jnp.bfloat16)
        w = jax.random.uniform(keys[1], (R, k), jnp.float32)
        tables = tuple((jax.random.normal(kk, (2, held) + s) * 0.1
                        ).astype(jnp.bfloat16) for kk, s in zip(
            keys[2:], ((D, Fe), (D, Fe), (Fe, D))))
        run = jax.jit(lambda: held_experts(h, lid, w, tables, held, held))
        got = np.asarray(run())
        monkeypatch.setattr(gg, "grouped_gemm", _parent_gemm)
        monkeypatch.setattr(gg, "grouped_gemm_gated", _parent_gated)
        want = np.asarray(jax.jit(lambda: held_experts(
            h, lid, w, tables, held, held))())
        assert np.isfinite(got).all()
        assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
        return
    load, off, masked, scaled, block_k, strips = _RUN_CASES[case]
    counts = _RUN_LOADS[load]
    E, bm, H, N = len(counts), 16, 128, 256
    if strips:
        n_w = 1 if kernel == "grouped_gemm" else 2
        monkeypatch.setattr(gg, "_VMEM_TILE_BUDGET",
                            2 * (2 * n_w * H * 128 + strips * bm * H))
        assert gg.fit_run_strips(H, bm, 128, 2, 2, n_w)[0] == strips
    ids = np.random.default_rng(1).permutation(
        np.repeat(np.arange(E), counts)).astype(np.int32)
    keys = jax.random.split(jax.random.key(3), 4)
    tokens = jax.random.normal(keys[0], (len(ids), H)).astype(jnp.bfloat16)
    ws = [(jax.random.normal(kk, (E, H, N)) * 0.1).astype(jnp.bfloat16)
          for kk in keys[1:3]]
    gi, rv, be, nb = align_tokens_by_expert(jnp.asarray(ids), E, bm,
                                            with_used_count=True)
    x = jnp.where(rv[:, None], tokens[gi], 0).astype(jnp.bfloat16)
    kw = dict(block_m=bm, block_n=128, n_blocks_used=nb - off, masked=masked,
              block_k=block_k,
              row_scale=jax.random.uniform(keys[3], (x.shape[0],),
                                           jnp.float32, 0.5, 1.5)
              if scaled else None)
    new, old, w = ((grouped_gemm, _parent_gemm, ws[:1])
                   if kernel == "grouped_gemm"
                   else (grouped_gemm_gated, _parent_gated, ws))
    got = jax.jit(lambda: new(x, *w, be, **kw))()
    want = jax.jit(lambda: old(x, *w, be, **kw))()
    live = (int(nb) - off) * bm
    assert np.isfinite(np.asarray(got[:live], np.float32)).all()
    assert np.array_equal(_bits(got[:live]), _bits(want[:live]))
    # past the bound: zeros where masked, else whatever the buffer held (the
    # interpreter fills it with NaN): the walk wrote nothing there
    tail = np.asarray(got[live:], np.float32)
    assert (not tail.any()) if masked else np.isnan(tail).all()
