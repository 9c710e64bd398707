"""Decode/serving-path tests: paged attention kernel vs dense golden
(parity: reference ref_paged_attn, test_sp_decode_attn.py:81-134) and the
prefill→decode_step→generate loop vs the full forward."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TEST_WORLD  # noqa: F401
from triton_dist_tpu.models.llama import (LlamaConfig, decode_step, forward,
                                          generate, init_kv_cache,
                                          init_params, prefill)
from triton_dist_tpu.ops.flash_decode import (gqa_decode_paged,
                                              gqa_prefill_paged,
                                              paged_kv_write)


def _ref_paged_attn(q, k_pages, v_pages, block_table, kv_len):
    """Dense golden: gather pages into a contiguous cache, plain softmax
    attention (mirrors the reference's ref_paged_attn)."""
    B, Hq, D = q.shape
    _, Hkv, ps, _ = k_pages.shape
    G = Hq // Hkv
    outs = []
    for b in range(B):
        k = np.concatenate([np.asarray(k_pages[p]) for p in
                            np.asarray(block_table[b])], axis=1)  # [Hkv,S,D]
        v = np.concatenate([np.asarray(v_pages[p]) for p in
                            np.asarray(block_table[b])], axis=1)
        L = int(kv_len[b])
        k, v = k[:, :L].astype(np.float32), v[:, :L].astype(np.float32)
        qb = np.asarray(q[b]).astype(np.float32).reshape(Hkv, G, D)
        s = np.einsum("hgd,htd->hgt", qb, k) / math.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        o = np.einsum("hgt,htd->hgd", p, v).reshape(Hq, D)
        outs.append(o)
    return np.stack(outs)


# the paged walk against the dense golden, by path and by query group: the
# decode rows' kernel, a chunk's rows through ``gqa_prefill_paged``, and rows
# written by ``paged_kv_write`` then walked; 2 query heads a KV head, and a
# group of ONE (as many KV heads as query heads: ISSUE 46's looped decoder)
@pytest.mark.parametrize("heads", [(4, 2), (4, 4)],
                         ids=["group-of-2", "group-of-1"])
@pytest.mark.parametrize("path", ["decode", "chunk", "written"])
def test_paged_decode_matches_dense(path, heads):
    B, (Hq, Hkv), D, ps, pages_per_seq = 2, heads, 64, 16, 4
    pool = B * pages_per_seq
    key = jax.random.key(0)
    k_pages = jax.random.normal(jax.random.key(1), (pool, Hkv, ps, D),
                                jnp.float32)
    v_pages = jax.random.normal(jax.random.key(2), (pool, Hkv, ps, D),
                                jnp.float32)
    # non-trivial page assignment + ragged lengths
    bt = jnp.asarray(np.random.default_rng(0).permutation(pool)
                     .reshape(B, pages_per_seq).astype(np.int32))
    if path == "chunk":
        # 16 rows of ONE sequence at positions 21 .. 36 (three pages), the
        # last three padding: rows that share a table share the walk
        C = 16
        q = jax.random.normal(key, (C, Hq, D), jnp.float32)
        kv_len = jnp.where(jnp.arange(C) < 13, 22 + jnp.arange(C), 0)
        out = jax.jit(lambda *a: gqa_prefill_paged(*a, rows_per_block=8))(
            q, k_pages, v_pages, bt[1], kv_len)
        rows = jnp.broadcast_to(bt[1], (C, pages_per_seq))
        ref = _ref_paged_attn(q[:13], k_pages, v_pages, rows[:13],
                              kv_len[:13])
        np.testing.assert_allclose(np.asarray(out[:13]), ref, atol=2e-5,
                                   rtol=2e-5)
        np.testing.assert_array_equal(np.asarray(out[13:]), 0.0)
        return
    q = jax.random.normal(key, (B, Hq, D), jnp.float32)
    kv_len = jnp.asarray([3 * ps + 5, 2 * ps], jnp.int32)
    if path == "written":
        # the rows' own keys and values land at kv_len - 1 through the table
        k_new = jax.random.normal(jax.random.key(3), (B, Hkv, D), jnp.float32)
        v_new = jax.random.normal(jax.random.key(4), (B, Hkv, D), jnp.float32)
        k_pages, v_pages = jax.jit(paged_kv_write)(
            k_pages, v_pages, k_new, v_new, bt, kv_len - 1)
        for b in range(B):
            page, row = bt[b, (kv_len[b] - 1) // ps], (kv_len[b] - 1) % ps
            np.testing.assert_array_equal(k_pages[page, :, row], k_new[b])
            np.testing.assert_array_equal(v_pages[page, :, row], v_new[b])
    out, lse = jax.jit(gqa_decode_paged)(q, k_pages, v_pages, bt, kv_len)
    ref = _ref_paged_attn(q, k_pages, v_pages, bt, kv_len)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)
    assert np.all(np.isfinite(np.asarray(lse[:, :, 0])))


def test_decode_step_matches_forward():
    """Incremental decode logits must match the full-sequence forward at
    every position (KV-cache correctness)."""
    cfg = dataclasses.replace(LlamaConfig.tiny(n_layers=2),
                              dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    B, S = 2, 8
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    full = jax.jit(lambda p, t: forward(p, t, cfg))(params, tokens)

    cache = init_kv_cache(cfg, B, 16)
    logits_p, cache = jax.jit(
        lambda p, t, c: prefill(p, t, cfg, c))(params, tokens[:, :4], cache)
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(full[:, 3]),
                               atol=2e-3, rtol=2e-3)
    step = jax.jit(lambda p, t, pos, c: decode_step(p, t, pos, cfg, c))
    for i in range(4, S):
        logits_d, cache = step(params, tokens[:, i], i, cache)
        np.testing.assert_allclose(np.asarray(logits_d),
                                   np.asarray(full[:, i]),
                                   atol=2e-3, rtol=2e-3)


def test_generate_greedy_consistent():
    """generate()'s first emitted token equals the forward argmax."""
    cfg = dataclasses.replace(LlamaConfig.tiny(n_layers=2),
                              dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    prompt = jax.random.randint(jax.random.key(1), (2, 6), 0, cfg.vocab_size)
    toks = jax.jit(lambda p, t: generate(p, t, cfg, max_new_tokens=3,
                                         max_seq=16))(params, prompt)
    assert toks.shape == (2, 3)
    full = forward(params, prompt, cfg)
    np.testing.assert_array_equal(np.asarray(toks[:, 0]),
                                  np.asarray(jnp.argmax(full[:, -1], -1)))


@pytest.mark.quick
def test_sp_decode_step_matches_single():
    """decode_step_sp over a 4-way KV-sharded cache == single-device
    decode_step (the model-level SP serving loop; reference
    sp_flash_decode_layer.py:78-184)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from conftest import TEST_WORLD
    from triton_dist_tpu.models.llama import decode_step_sp
    from triton_dist_tpu.shmem.context import initialize_distributed

    ctx = initialize_distributed(axis_names=("x",), mesh_shape=(TEST_WORLD,))
    cfg = LlamaConfig(vocab_size=256, d_model=256, n_layers=2, n_heads=2,
                      n_kv_heads=2, d_ff=256, max_seq_len=4 * 32)
    params = init_params(jax.random.key(0), cfg)
    B, S = 4, cfg.max_seq_len  # B*Hq = 8 rows (sublane-safe merge buffer)
    cache = init_kv_cache(cfg, B, S)
    spec = P(None, None, None, "x", None)
    cache = {k: jax.device_put(v, NamedSharding(ctx.mesh, spec))
             for k, v in cache.items()}

    token = jax.random.randint(jax.random.key(1), (B,), 0, cfg.vocab_size)
    logits_ref = None
    pos = 0
    # a few steps so later steps read cache entries written by earlier ones
    step_sp = jax.jit(lambda p, t, pos, c: decode_step_sp(
        ctx, p, t, pos, cfg, c, axis="x"))
    step_1d = jax.jit(lambda p, t, pos, c: decode_step(p, t, pos, cfg, c))
    cache_1d = init_kv_cache(cfg, B, S)
    for pos in range(3):
        l_sp, cache = step_sp(params, token, pos, cache)
        l_1d, cache_1d = step_1d(params, token, pos, cache_1d)
        # bf16 activations + a different partial-merge order: ~5e-3 noise
        np.testing.assert_allclose(np.asarray(l_sp), np.asarray(l_1d),
                                   rtol=1e-2, atol=1e-2)
        # host round-trip: a mesh-sharded token input would drag the SPMD
        # partitioner into the single-device path's scanned interpret kernel
        token = jnp.asarray(np.argmax(np.asarray(l_sp), axis=-1),
                            jnp.int32)


def test_moe_sp_decode_step_matches_dense():
    """moe_decode_step_sp (SP flash-decode attention + EP A2A MoE FFN in
    one jitted step — the DeepSeek-style serving composition) == a
    single-device dense reference step, over several steps so the cache
    round-trips."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from conftest import TEST_WORLD
    from triton_dist_tpu.layers.ep_a2a_layer import EPAll2AllLayer
    from triton_dist_tpu.models.moe import (MoEConfig, init_moe_params,
                                            moe_decode_step_sp)
    from triton_dist_tpu.shmem.context import initialize_distributed

    ctx = initialize_distributed(axis_names=("x",), mesh_shape=(TEST_WORLD,))
    n = ctx.num_ranks
    base = LlamaConfig(vocab_size=256, d_model=128, n_layers=2, n_heads=2,
                       n_kv_heads=2, d_ff=128, max_seq_len=4 * 32)
    cfg = MoEConfig(base=base, num_experts=2 * n, topk=2, moe_d_ff=128)
    params = init_moe_params(jax.random.key(0), cfg)
    B, S = 4, base.max_seq_len
    layer = EPAll2AllLayer.create(ctx, max_tokens=B // n, hidden=base.d_model,
                                  topk=cfg.topk, num_experts=cfg.num_experts,
                                  axis="x", dtype=base.dtype)

    cache = init_kv_cache(base, B, S)
    spec = P(None, None, None, "x", None)
    cache = {k: jax.device_put(v, NamedSharding(ctx.mesh, spec))
             for k, v in cache.items()}
    cache_1d = init_kv_cache(base, B, S)

    def dense_moe_ffn(h, p):
        """Dense per-expert golden FFN — plugged into decode_step's ffn
        hook so the attention/cache plumbing is the shared one."""
        h32 = h.astype(jnp.float32)
        gv, gi = jax.lax.top_k(
            jax.nn.softmax(h32 @ p["w_router"], -1), cfg.topk)
        gv = gv / jnp.sum(gv, -1, keepdims=True)
        act = jax.nn.silu(jnp.einsum("td,edf->tef", h32,
                                     p["we_gate"].astype(jnp.float32))) \
            * jnp.einsum("td,edf->tef", h32,
                         p["we_up"].astype(jnp.float32))
        ye = jnp.einsum("tef,efd->ted",
                        act.astype(cfg.base.dtype).astype(jnp.float32),
                        p["we_down"].astype(jnp.float32))
        sel = jnp.take_along_axis(ye, gi[..., None], axis=1)
        return jnp.sum(sel * gv[..., None], axis=1)

    def dense_step(params, token, pos, cache):
        return decode_step(params, token, pos, cfg.base, cache,
                           ffn=dense_moe_ffn)

    # XLA:CPU's concurrency-optimized schedule lets one device enter the SP
    # all-gather while its peers sit in the A2A kernel's interpreter barrier:
    # each waits for the other and after 40 s the rendezvous aborts the
    # process (17 of 92 runs under load, 0 of 112 with the plain schedule;
    # ROADMAP C9). On this program only: what it does to the rest of the
    # suite was not established.
    step_sp = jax.jit(
        lambda p, t, pos, c: moe_decode_step_sp(
            ctx, layer, p, t, pos, cfg, c, sp_axis="x"),
        compiler_options={
            "xla_cpu_enable_concurrency_optimized_scheduler": False})
    step_1d = jax.jit(dense_step)

    token = jax.random.randint(jax.random.key(1), (B,), 0, base.vocab_size)
    for pos in range(3):
        l_sp, cache = step_sp(params, token, pos, cache)
        l_1d, cache_1d = step_1d(params, token, pos, cache_1d)
        np.testing.assert_allclose(np.asarray(l_sp), np.asarray(l_1d),
                                   rtol=3e-2, atol=3e-2)
        token = jnp.asarray(np.argmax(np.asarray(l_sp), axis=-1), jnp.int32)
