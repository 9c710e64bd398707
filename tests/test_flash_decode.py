"""Flash-decode tests vs dense attention goldens (parity targets: reference
test/nvidia/test_decode_attn.py and test_sp_decode_attn.py — the latter
checks the full SP pipeline against a paged-attention reference)."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from conftest import TEST_WORLD
from triton_dist_tpu.ops.flash_decode import (NEG_INF, _as_stack,
                                              _softmax_finish,
                                              _softmax_init, _softmax_update,
                                              decode_combine,
                                              gqa_decode_paged,
                                              gqa_decode_partial,
                                              gqa_prefill_paged,
                                              sp_gqa_flash_decode)
from triton_dist_tpu.shmem.context import initialize_distributed
from triton_dist_tpu.utils import assert_allclose


@pytest.fixture(scope="module")
def ctx():
    return initialize_distributed(axis_names=("x",), mesh_shape=(TEST_WORLD,))


def _dense_golden(q, k, v, kv_len):
    """Dense GQA attention golden in numpy."""
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    B, Hq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    out = np.zeros((B, Hq, D))
    for b in range(B):
        L = int(kv_len[b])
        for h in range(Hq):
            kh = h // G
            s = (k[b, kh, :L] @ q[b, h]) / math.sqrt(D)
            p = np.exp(s - s.max())
            p /= p.sum()
            out[b, h] = p @ v[b, kh, :L]
    return out


def test_gqa_decode_partial_full_cache():
    B, S, Hq, Hkv, D = 2, 256, 8, 2, 128
    q = jax.random.normal(jax.random.key(0), (B, Hq, D), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (B, Hkv, S, D), jnp.float32)
    kv_len = jnp.array([256, 100], jnp.int32)  # one full, one ragged
    out, lse = jax.jit(lambda *a: gqa_decode_partial(*a))(q, k, v, kv_len)
    golden = _dense_golden(q, k, v, np.asarray(kv_len))
    assert_allclose(np.asarray(out), golden, atol=1e-3, rtol=1e-3)
    # lse sanity: finite where kv_len > 0, lane-broadcast
    lse = np.asarray(lse)
    assert np.all(lse[..., 0] == lse[..., 1])
    assert np.all(lse[0, :, 0] > -1e29)


def test_decode_combine_matches_monolithic():
    """Splitting a cache into R chunks, decoding each, then combining must
    equal decoding the whole cache."""
    B, S, Hq, Hkv, D, R = 1, 512, 4, 1, 128, 4
    q = jax.random.normal(jax.random.key(0), (B, Hq, D), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (B, Hkv, S, D), jnp.float32)
    kv_len = jnp.array([S], jnp.int32)
    chunk = S // R
    outs, lses = [], []
    for r in range(R):
        o, l = jax.jit(lambda *a: gqa_decode_partial(*a))(
            q, k[:, :, r * chunk:(r + 1) * chunk], v[:, :, r * chunk:(r + 1) * chunk],
            jnp.array([chunk], jnp.int32))
        outs.append(o)
        lses.append(l)
    merged = jax.jit(decode_combine)(jnp.stack(outs), jnp.stack(lses))
    golden = _dense_golden(q, k, v, np.asarray(kv_len))
    assert_allclose(np.asarray(merged), golden, atol=1e-3, rtol=1e-3)


def _paged_golden(q, k_pages, v_pages, block_table, kv_len):
    """Dense paged golden: gather each row's live pages contiguously, then
    plain softmax attention. Only pages [0, ceil(kv_len/ps)) are touched —
    garbage block-table entries past that must not matter."""
    q = np.asarray(q, np.float64)
    kp = np.asarray(k_pages, np.float64)
    vp = np.asarray(v_pages, np.float64)
    bt = np.asarray(block_table)
    B, Hq, D = q.shape
    Hkv, ps = kp.shape[1], kp.shape[2]
    G = Hq // Hkv
    out = np.zeros((B, Hq, D))
    for b in range(B):
        L = int(kv_len[b])
        if L == 0:
            continue
        n_pages = -(-L // ps)
        k = np.concatenate([kp[p] for p in bt[b, :n_pages]], axis=1)[:, :L]
        v = np.concatenate([vp[p] for p in bt[b, :n_pages]], axis=1)[:, :L]
        for h in range(Hq):
            kh = h // G
            s = (k[kh] @ q[b, h]) / math.sqrt(D)
            p = np.exp(s - s.max())
            p /= p.sum()
            out[b, h] = p @ v[kh]
    return out


@pytest.mark.parametrize("rows", ["k-and-v-apart", "k-v-side-by-side"])
def test_paged_decode_garbage_block_table_entries(rows):
    """Block-table entries past ceil(kv_len/page_size) may be ARBITRARY —
    even out-of-range page ids — without changing the result or faulting
    (the index map clamps and never dereferences them). ``k-v-side-by-side``:
    ONE pool whose rows are ``[K | V]`` of a head (``v_pages`` None; heads of
    64 as one 128-lane row) against the same golden on the halves."""
    B, Hq, Hkv, D, ps, pps, pool = 2, 4, 2, 64, 8, 6, 16
    q = jax.random.normal(jax.random.key(0), (B, Hq, D), jnp.float32)
    kp = jax.random.normal(jax.random.key(1), (pool, Hkv, ps, D), jnp.float32)
    vp = jax.random.normal(jax.random.key(2), (pool, Hkv, ps, D), jnp.float32)
    kv_len = jnp.array([2 * ps + 3, ps], jnp.int32)   # 3 and 1 live pages
    bt_clean = np.array([[3, 7, 1, 0, 0, 0],
                         [5, 0, 0, 0, 0, 0]], np.int32)
    if rows == "k-v-side-by-side":
        held = (jnp.concatenate([kp, vp], -1), None)
    else:
        held = (kp, vp)
    out_c, lse_c = jax.jit(gqa_decode_paged)(q, *held,
                                             jnp.asarray(bt_clean), kv_len)
    assert out_c.shape == q.shape
    # poison every dead entry with garbage incl. ids far outside the pool
    bt_dirty = bt_clean.copy()
    bt_dirty[0, 3:] = [10 ** 6, -5, 2 ** 31 - 1]
    bt_dirty[1, 1:] = [-(2 ** 31), 999999, -1, 888, pool]
    out_d, lse_d = jax.jit(gqa_decode_paged)(q, *held,
                                             jnp.asarray(bt_dirty), kv_len)
    np.testing.assert_array_equal(np.asarray(out_c), np.asarray(out_d))
    np.testing.assert_array_equal(np.asarray(lse_c), np.asarray(lse_d))
    golden = _paged_golden(q, kp, vp, bt_clean, np.asarray(kv_len))
    assert_allclose(np.asarray(out_d), golden, atol=1e-3, rtol=1e-3)


def test_paged_decode_kv_len_zero():
    """kv_len == 0 rows return zeros with lse = NEG_INF (the empty-shard
    convention the SP combine honors); live rows in the same batch are
    unaffected. The zero row's block table is all garbage on purpose."""
    B, Hq, Hkv, D, ps, pps, pool = 2, 4, 2, 64, 8, 4, 8
    q = jax.random.normal(jax.random.key(0), (B, Hq, D), jnp.float32)
    kp = jax.random.normal(jax.random.key(1), (pool, Hkv, ps, D), jnp.float32)
    vp = jax.random.normal(jax.random.key(2), (pool, Hkv, ps, D), jnp.float32)
    bt = jnp.asarray(np.array([[-7, 10 ** 8, -1, 4096],
                               [2, 6, 0, 0]], np.int32))
    kv_len = jnp.array([0, 2 * ps + 1], jnp.int32)
    out, lse = jax.jit(gqa_decode_paged)(q, kp, vp, bt, kv_len)
    out, lse = np.asarray(out), np.asarray(lse)
    np.testing.assert_array_equal(out[0], np.zeros_like(out[0]))
    np.testing.assert_array_equal(lse[0], np.full_like(lse[0], NEG_INF))
    golden = _paged_golden(q, kp, vp, np.asarray(bt), np.asarray(kv_len))
    assert_allclose(out[1], golden[1], atol=1e-3, rtol=1e-3)
    assert np.all(lse[1, :, 0] > -1e29)


# rows of a decode batch over pages of 8, 7 pages a sequence: ``kv_len`` a
# row, and whether consecutive rows share one block-table row (the
# speculative form)
WALK_PS, WALK_PPS = 8, 7
WALK_CASES = {
    "idle-between-live": ([19, 0, 0, 44, 0, 7], False),
    "all-idle-but-last": ([0, 0, 0, 0, 0, 33], False),
    "all-idle": ([0, 0, 0, 0, 0, 0], False),
    "page-boundaries": ([8, 16, 24, 32, 48, 56], False),   # ends ON a page
    "off-boundaries": ([1, 9, 17, 31, 41, 55], False),
    "full-table": ([56, 56, 50, 56, 49, 56], False),       # every page live
    "staggered-shared-table": ([21, 22, 23, 24, 25, 26], True),
    "shared-table-idle-tail": ([40, 41, 0, 0, 15, 16], True),
    "past-the-table": ([56, 90, 0, 57, 3, 64], False),     # kv_len > 7 pages
    "two-row-blocks": ([9] + [0] * 15 + [0, 50] + [0] * 12 + [17, 0], False),
    "idle-row-block": ([0] * 16 + [23, 0] * 8, False),
}


def _walk_inputs(case, pool_pages=48):
    kv_len, shared = WALK_CASES[case]
    B = len(kv_len)
    rng = np.random.default_rng(sorted(WALK_CASES).index(case))
    bt = rng.integers(0, pool_pages, (B, WALK_PPS))
    if shared:                           # rows 2i and 2i + 1 walk one table
        bt = np.repeat(bt[::2], 2, axis=0)
    bt = bt.astype(np.int32)
    garbage = np.array([10 ** 6, -5, 2 ** 31 - 1, -(2 ** 31), pool_pages,
                        -1, 999999], np.int32)
    for b, kl in enumerate(kv_len):      # entries past the live pages
        live = min(-(-kl // WALK_PS), WALK_PPS)
        bt[b, live:] = garbage[:WALK_PPS - live]
    return jnp.asarray(bt), jnp.asarray(kv_len, jnp.int32)


def _grid_walk_kernel(kv_len_ref, bt_ref, layer_ref, q_ref, k_ref, v_ref,
                      out_ref, lse_ref, acc, m_i, l_i, *, page_size, sm_scale,
                      n_kv_heads):
    del bt_ref, layer_ref
    b, s = pl.program_id(0), pl.program_id(1)
    kv_len = kv_len_ref[b]
    pl.when(s == 0)(lambda: _softmax_init(acc, m_i, l_i))

    @pl.when(s * page_size < kv_len)
    def _():
        _softmax_update(s * page_size, kv_len, q_ref[0], k_ref[0], v_ref[0],
                        acc, m_i, l_i, block_s=page_size, sm_scale=sm_scale,
                        n_kv_heads=n_kv_heads)

    pl.when(s == pl.num_programs(1) - 1)(
        lambda: _softmax_finish(out_ref, lse_ref, acc, m_i, l_i))


def _grid_walk(q, k_pages, v_pages, block_table, kv_len, layer=None):
    """``gqa_decode_paged`` as it was before ISSUE 29, kept as the tests'
    reference: a (B, pages_per_seq) grid, one page a grid step through
    BlockSpec index maps, a dead step revisiting the row's last live page."""
    k_pages, v_pages, layer = _as_stack(k_pages, v_pages, layer)
    B, Hq, D = q.shape
    _, P_pool, Hkv, page_size, _ = k_pages.shape
    pps = block_table.shape[1]

    def page_index(b, s, kl, bt, ly):
        last = jnp.maximum((kl[b] + page_size - 1) // page_size - 1, 0)
        page = bt[b, jnp.minimum(s, last)]
        return (ly[0], jnp.clip(page, 0, P_pool - 1), 0, 0, 0)

    row = lambda b, s, kl, bt, ly: (b, 0, 0)                # noqa: E731
    page_block = pl.BlockSpec((None, 1, Hkv, page_size, D), page_index)
    return pl.pallas_call(
        functools.partial(_grid_walk_kernel, page_size=page_size,
                          sm_scale=1.0 / math.sqrt(D), n_kv_heads=Hkv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, pps),
            in_specs=[pl.BlockSpec((1, Hq, D), row), page_block, page_block],
            out_specs=[pl.BlockSpec((1, Hq, D), row),
                       pl.BlockSpec((1, Hq, 128), row)],
            scratch_shapes=[pltpu.VMEM((Hq, D), jnp.float32),
                            pltpu.VMEM((Hq, 1), jnp.float32),
                            pltpu.VMEM((Hq, 1), jnp.float32)]),
        out_shape=(jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
                   jax.ShapeDtypeStruct((B, Hq, 128), jnp.float32)),
        interpret=True,
    )(kv_len, block_table, layer, q, k_pages, v_pages)


@pytest.mark.parametrize("stacked", [False, True], ids=["pool4d", "stack"])
@pytest.mark.parametrize("case", WALK_CASES)
def test_paged_decode_live_page_walk_is_bitwise_the_grid_walk(case, stacked):
    """The loop over live pages against the (row, page) grid it replaced
    (``_grid_walk``): the same page meets the same query in the same order
    with one softmax update a page, so out and lse are BITWISE equal,
    whatever is idle, shared, garbage or on a page boundary, in one row block
    or two, for the per-layer pool and for the stacked one."""
    Hq, Hkv, D, pool, L, layer = 4, 2, 64, 48, 3, 1
    bt, kv_len = _walk_inputs(case, pool)
    B = bt.shape[0]
    q = jax.random.normal(jax.random.key(0), (B, Hq, D), jnp.float32)
    kp = jax.random.normal(jax.random.key(1), (L, pool, Hkv, WALK_PS, D),
                           jnp.float32)
    vp = jax.random.normal(jax.random.key(2), (L, pool, Hkv, WALK_PS, D),
                           jnp.float32)
    seen = jnp.minimum(kv_len, WALK_PPS * WALK_PS)
    if stacked:
        args = (q, kp, vp, bt)
        kw = {"layer": jnp.int32(layer)}
    else:
        args = (q, kp[layer], vp[layer], bt)
        kw = {}
    out, lse = jax.jit(lambda kl: gqa_decode_paged(*args, kl, **kw))(kv_len)
    out1, lse1 = jax.jit(lambda kl: _grid_walk(*args, kl, **kw))(seen)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out1))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse1))
    idle = np.asarray(kv_len) == 0
    np.testing.assert_array_equal(np.asarray(out)[idle], 0.0)
    np.testing.assert_array_equal(np.asarray(lse)[idle], np.float32(NEG_INF))
    if not stacked:                      # and both are the right answer
        golden = _paged_golden(q, kp[layer], vp[layer], np.asarray(bt),
                               np.asarray(seen))
        assert_allclose(np.asarray(out), golden, atol=1e-3, rtol=1e-3)


# a chunk of 16 rows in blocks of 8 over pages of 8, 6 pages a sequence:
# (first position, prompt length); rows at or past the prompt are padding.
# A dict says more: ``rows`` (the chunk's), ``pps`` (the table's pages),
# ``kv_len`` (every row's, in place of consecutive positions), ``window``,
# ``sinks``, ``dv`` (values narrower than the keys' 64). With 2 query heads a
# KV head a block is [16, 64] a head and a group 4 pages; the comments count a
# block's (interior, edge) pages as ``chunk_walk_pages`` cuts them.
PREFILL_CASES = {
    "page-start": (16, 32),            # starts on a page, ends on one
    "mid-page": (5, 21),               # starts and ends inside pages
    "padded-tail": (5, 18),            # the second block ends in padding
    "padded-block": (3, 9),            # the second block is all padding
    "one-page": (0, 5),                # context of 1 page
    "three-pages": (8, 24),            # ... of 3: the blocks' pages differ
    "all-pages": (32, 48),             # ... of every page of the table
    "one-token": (40, 41),
    # every row sees every key of every page: (3, 0), one short group
    "all-interior": {"kv_len": [24] * 16},
    "interior-then-edge": (24, 40),                     # (3, 1) and (4, 1)
    "straddles-a-page": (12, 28),                       # (1, 2) and (2, 2)
    "exactly-one-group": {"at": (32, 40), "rows": 8},   # (4, 1)
    "short-last-group": {"at": (40, 56), "pps": 8},     # (5, 1): 4 + 1, then 1
    "two-groups-and-a-page": {"at": (72, 80), "rows": 8, "pps": 10},  # (9, 1)
    "past-the-table": (44, 60),        # kv_len beyond the table's 48 keys
    "all-padding": (8, 8),             # no live row: nothing is walked
    "lone-rows": {"kv_len": [0, 0, 0, 9, 0, 0, 0, 0] + [0] * 6 + [33, 0]},
    # rows 31..46 under a window of 20: the lowest bound, 11, is mid-page
    "window-bound-mid-page": {"at": (30, 46), "window": 20},
    "window-before-it-binds": {"at": (3, 19), "window": 20},
    "window-padded-tail": {"at": (30, 41), "window": 20},
    # pages 5 | 6 7 8 9 | 10 of a ring of 7: columns 6 0 1 2 are ONE group
    "ring-wraps-in-a-group": {"at": (80, 88), "rows": 8, "pps": 7,
                              "window": 40},
    "sink": {"at": (12, 28), "sinks": True},
    "window-sink": {"at": (30, 46), "window": 20, "sinks": True},
    "dk-not-dv": {"at": (24, 40), "dv": 32},
    "window-sink-dk-not-dv": {"at": (30, 46), "window": 20, "sinks": True,
                              "dv": 32},
    # ONE pool of ``[K | V]`` rows (``v_pages`` None): keys and values of 32
    "kv-side-by-side": {"at": (24, 40), "fused": True},
    "kv-side-by-side-straddles": {"at": (12, 28), "fused": True},
    "kv-side-by-side-lone-rows": {
        "kv_len": [0, 0, 0, 9, 0, 0, 0, 0] + [0] * 6 + [33, 0],
        "fused": True},
}
POOL_PAGES = 32


def _prefill_inputs(case, C=16):
    """(q, kp, vp, table, kv_len, keyword arguments) of a case: a stacked
    pool of 2 layers; pages that no live row can reach are NaN and inf, and
    the table's entries past the live pages (no window) garbage."""
    spec = PREFILL_CASES[case]
    spec = {"at": spec} if isinstance(spec, tuple) else dict(spec)
    C, pps = spec.get("rows", C), spec.get("pps", 6)
    Hq, Hkv, Dk, ps, L = 4, 2, 64, 8, 2
    Dv, window = spec.get("dv", Dk), spec.get("window")
    if "kv_len" in spec:
        kv_len = np.asarray(spec["kv_len"], np.int32)
    else:
        start, prompt_len = spec["at"]
        idx = start + np.arange(C)
        kv_len = np.where(idx < prompt_len, idx + 1, 0).astype(np.int32)
    q = jax.random.normal(jax.random.key(0), (C, Hq, Dk), jnp.float32)
    kp = jax.random.normal(jax.random.key(1), (L, POOL_PAGES, Hkv, ps, Dk),
                           jnp.float32)
    vp = jax.random.normal(jax.random.key(2), (L, POOL_PAGES, Hkv, ps, Dv),
                           jnp.float32)
    bt = np.array([3, 7, 1, 12, 5, 9, 20, 14, 27, 17], np.int32)[:pps]
    if not window:
        live = min(-(-int(kv_len.max()) // ps), pps)
        bt[live:] = [10 ** 6, -5, 2 ** 31 - 1, -(2 ** 31), POOL_PAGES,
                     -1, 99, 0, 31, 2][:pps - live]
        dead = np.setdiff1d(np.arange(POOL_PAGES), bt[:live])
        kp = kp.at[:, dead[::2]].set(np.nan).at[:, dead[1::2]].set(np.inf)
        vp = vp.at[:, dead[::2]].set(-np.inf).at[:, dead[1::2]].set(np.nan)
    kw = {"window": window} if window else {}
    if spec.get("sinks"):
        kw["sinks"] = jnp.asarray([0.5, -1.0, 2.0, 0.0], jnp.float32)
    if spec.get("fused"):
        half = Dk // 2
        q, vp = q[..., :half], None
        kp = jnp.concatenate([kp[..., :half], kp[..., half:] * 0.5], -1)
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(kv_len), kw


@functools.lru_cache(None)
def _chunk_rows(window, Rb):
    """``gqa_prefill_paged`` as ONE jitted function a window (arrays and the
    layer are arguments): cases of one shape share a compile."""
    return jax.jit(lambda q, kp, vp, bt, kl, ly, sinks: gqa_prefill_paged(
        q, kp, vp, bt, kl, layer=ly, rows_per_block=Rb, window=window,
        sinks=sinks))


@functools.lru_cache(None)
def _decode_rows(window):
    return jax.jit(lambda q, kp, vp, bt, kl, ly, sinks: gqa_decode_paged(
        q, kp, vp, bt, kl, layer=ly, window=window, sinks=sinks)[0])


# the eight cases of ISSUE 27 at both layers of the stacked pool (and as the
# per-layer form), every later one at one, alternating
PREFILL_PARAMS = [(case, layer) for n, case in enumerate(PREFILL_CASES)
                  for layer in ([0, 1] if n < 8 else [n % 2])]


@pytest.mark.parametrize("case,layer", PREFILL_PARAMS,
                         ids=[f"{c}-{ly}" for c, ly in PREFILL_PARAMS])
def test_prefill_paged_equals_decode_rows(case, layer):
    """``gqa_prefill_paged`` (rows of ONE sequence share a walk of its pages)
    against ``gqa_decode_paged`` run row by row on the same stacked pool: the
    same keys, scores and softmax, so float32's last bits in interpret mode.
    Block-table entries past the live pages are garbage, out of range too,
    and every page no live row reaches is NaN and inf."""
    Rb = 8
    q, kp, vp, bt, kv_len, kw = _prefill_inputs(case)
    C, pps = q.shape[0], bt.shape[0]
    got = _chunk_rows(kw.get("window"), Rb)(q, kp, vp, bt, kv_len,
                                            jnp.int32(layer), kw.get("sinks"))
    want = _decode_rows(kw.get("window"))(
        q, kp, vp, jnp.broadcast_to(bt, (C, pps)), kv_len, jnp.int32(layer),
        kw.get("sinks"))
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    padded = np.asarray(kv_len) == 0
    np.testing.assert_array_equal(got[padded], 0.0)
    if vp is None:
        # the one pool's halves held apart: the same keys and values
        half = q.shape[-1]
        apart = _chunk_rows(None, Rb)(q, kp[..., :half], kp[..., half:], bt,
                                      kv_len, jnp.int32(layer), None)
        np.testing.assert_allclose(got, np.asarray(apart), rtol=1e-6,
                                   atol=1e-6)
    if list(PREFILL_CASES).index(case) < 8:
        # the per-layer form reads the same pages of a [P, ...] pool
        np.testing.assert_array_equal(np.asarray(gqa_prefill_paged(
            q, kp[layer], vp[layer], bt, kv_len, rows_per_block=Rb)), got)


def _grid_prefill_kernel(*refs, page_size: int, sm_scale: float,
                         window: int | None = None, sinks: bool = False):
    """``gqa_prefill_paged``'s kernel before ISSUE 41, kept as the reference
    of the bitwise test below: grid (row blocks, pages), one masked
    online-softmax update a page, a dead page's compute skipped."""
    kl_ref, _, _, *refs = refs
    first_ref, refs = (refs[0], refs[1:]) if window else (None, refs)
    q_ref, klr_ref, *refs = refs
    sink_ref, refs = (refs[0], refs[1:]) if sinks else (None, refs)
    k_ref, v_ref, out_ref, acc, m_i, l_i = refs
    i, s = pl.program_id(0), pl.program_id(1)
    page = first_ref[i] + s if window else s

    pl.when(s == 0)(lambda: _softmax_init(
        acc, m_i, l_i, None if sink_ref is None else sink_ref[...]))

    @pl.when(page * page_size < kl_ref[i])
    def _():
        q, k, v = q_ref[...], k_ref[0], v_ref[0]
        scores = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * sm_scale
        M = scores.shape[1]
        pos = page * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (M, page_size), 1)
        seen = pos < klr_ref[...]
        if window:
            seen = jnp.logical_and(seen, pos >= klr_ref[...] - window)
        scores = jnp.where(seen[None], scores, NEG_INF)
        m_new = jnp.maximum(m_i[...], jnp.max(scores, axis=2, keepdims=True))
        alpha = jnp.exp(m_i[...] - m_new)
        p = jnp.exp(scores - m_new)
        l_i[...] = l_i[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc[...] = acc[...] * alpha + pv
        m_i[...] = m_new

    @pl.when(s == pl.num_programs(1) - 1)
    def _():
        l_safe = jnp.where(l_i[...] > 0, l_i[...], 1.0)
        out = jnp.where((klr_ref[...] > 0)[None], acc[...] / l_safe, 0.0)
        out_ref[...] = out.astype(out_ref.dtype)


def _grid_prefill(q, k_pages, v_pages, block_table, kv_len, layer, Rb,
                  window=None, sinks=None):
    """The (row block, page) grid ``gqa_prefill_paged`` was before ISSUE 41
    (stacked pool, interpret mode)."""
    C, Hq, Dk = q.shape
    _, P_pool, Hkv, page_size, _ = k_pages.shape
    Dv, G = v_pages.shape[-1], Hq // Hkv
    n_blk, M, pps = C // Rb, Rb * G, block_table.shape[0]
    kl_blk = kv_len.reshape(n_blk, Rb).max(axis=1)
    kl_rows = jnp.repeat(kv_len, G)[:, None]
    q_hm = q.reshape(C, Hkv, G, Dk).swapaxes(0, 1).reshape(Hkv, C * G, Dk)
    extra, extra_specs = (), []
    if sinks is not None:
        extra = (jnp.tile(sinks.reshape(Hkv, 1, G), (1, Rb, 1)).reshape(
            Hkv, M, 1),)
        extra_specs = [pl.BlockSpec((Hkv, M, 1), lambda i, s, *_: (0, 0, 0))]

    def page_index(i, s, kl, bt, ly, *first):
        last = jnp.maximum((kl[i] + page_size - 1) // page_size - 1, 0)
        if window:
            page = bt[jnp.minimum(first[0][i] + s, last) % pps]
        else:
            page = bt[jnp.minimum(s, last)]
        return (ly[0], jnp.clip(page, 0, P_pool - 1), 0, 0, 0)

    rows = lambda i, s, *_: (0, i, 0)                       # noqa: E731
    page_block = lambda D: pl.BlockSpec(                    # noqa: E731
        (None, 1, Hkv, page_size, D), page_index)
    scalars = (kl_blk, block_table, jnp.asarray(layer, jnp.int32).reshape(1))
    n_pages = pps
    if window:
        bound = jnp.where(kv_len > 0, jnp.maximum(kv_len - window, 0),
                          jnp.iinfo(jnp.int32).max)
        lo = bound.reshape(n_blk, Rb).min(axis=1)
        scalars += (jnp.where(kl_blk > 0, lo, 0) // page_size,)
        n_pages = min(pps, -(-(window + Rb - 1) // page_size) + 1)
    out = pl.pallas_call(
        functools.partial(_grid_prefill_kernel, page_size=page_size,
                          sm_scale=1.0 / math.sqrt(Dk), window=window,
                          sinks=sinks is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(n_blk, n_pages),
            in_specs=[pl.BlockSpec((Hkv, M, Dk), rows),
                      pl.BlockSpec((M, 1), lambda i, s, *_: (i, 0)),
                      *extra_specs, page_block(Dk), page_block(Dv)],
            out_specs=pl.BlockSpec((Hkv, M, Dv), rows),
            scratch_shapes=[pltpu.VMEM((Hkv, M, Dv), jnp.float32),
                            pltpu.VMEM((Hkv, M, 1), jnp.float32),
                            pltpu.VMEM((Hkv, M, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((Hkv, C * G, Dv), q.dtype),
        interpret=True,
    )(*scalars, q_hm, kl_rows, *extra, k_pages, v_pages)
    return out.reshape(Hkv, C, G, Dv).swapaxes(0, 1).reshape(C, Hq, Dv)


@pytest.mark.parametrize("case", [
    "padded-tail", "all-pages", "all-interior", "short-last-group",
    "window-bound-mid-page", "ring-wraps-in-a-group",
    "window-sink-dk-not-dv"])
def test_prefill_paged_one_page_a_group_is_bitwise_the_grid(case,
                                                            monkeypatch):
    """At ONE page a group the loop over live pages makes the (row block,
    page) grid's updates in the grid's order, and an interior page's update
    without the mask is the masked one (``where(True, s, NEG_INF) == s``):
    BITWISE the kernel before ISSUE 41 (``_grid_prefill``), for edge and
    interior pages, padding, a window over a wrapping ring, a sink and
    values narrower than keys. (The grid masked the dead pages it revisited;
    the pool's unreachable pages are NaN, as for the loop.)"""
    from triton_dist_tpu.ops import flash_decode
    monkeypatch.setattr(flash_decode, "PREFILL_PAGES_PER_GROUP", 1)
    q, kp, vp, bt, kv_len, kw = _prefill_inputs(case)
    got = gqa_prefill_paged(q, kp, vp, bt, kv_len, layer=1,
                            rows_per_block=8, **kw)
    want = _grid_prefill(q, kp, vp, bt, kv_len, 1, 8, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _brute_force_pages(kv_len, page_size, window, pages_per_seq):
    """(walked, interior) logical pages of ONE block by the mask itself over
    every (row, key): a page is walked when some live row sees a key of it,
    interior when every live row sees every key of it."""
    live = kv_len[kv_len > 0]
    if not live.size:
        return set(), set()
    cap = None if window else pages_per_seq * page_size
    keys = np.arange(-(-int(live.max()) // page_size) * page_size)
    if cap is not None:
        keys = keys[keys < cap]
    seen = keys[None, :] < live[:, None]
    if window:
        seen &= keys[None, :] >= live[:, None] - window
    by_page = seen.reshape(live.size, -1, page_size)
    walked = set(np.flatnonzero(by_page.any(axis=(0, 2))).tolist())
    return walked, set(np.flatnonzero(by_page.all(axis=(0, 2))).tolist())


@pytest.mark.parametrize("window", [None, 20, 64])
def test_chunk_walk_pages_equal_the_brute_force_mask(window):
    """``chunk_walk_pages`` (the plan the kernel walks by and the engine
    counts by) against the mask over every (row, key): the interior pages are
    EXACTLY those every live row sees whole; the walk ``[first, end)`` holds
    every page some live row sees a key of and, for a chunk's consecutive
    rows, nothing else; ``chunk_walk_counts`` is the sum over blocks."""
    from triton_dist_tpu.ops.flash_decode import (chunk_walk_bounds,
                                                  chunk_walk_counts,
                                                  chunk_walk_pages)
    ps, Rb, pps = 8, 8, 12
    rng = np.random.default_rng(0)
    chunks = []                       # (kv_len [C], its rows are consecutive)
    for start in list(range(0, 70, 3)) + [88]:
        for real in (16, 11, 8, 3, 0):
            idx = start + np.arange(16)
            chunks.append((np.where(idx < start + real, idx + 1, 0), True))
    for _ in range(40):               # any values: holes, repeats, padding
        kl = rng.integers(0, pps * ps + 1, 16)
        chunks.append((np.where(rng.random(16) < 0.3, 0, kl), False))
    for kv_len, consecutive in chunks:
        kv_len = kv_len.astype(np.int32)
        bounds = [np.asarray(x)
                  for x in chunk_walk_bounds(jnp.asarray(kv_len), Rb)]
        for xp in (np, jnp):          # on the host, and in a program
            first, lo, hi, end = (np.asarray(x) for x in chunk_walk_pages(
                *(xp.asarray(x) for x in bounds), ps, window, pps, xp))
            pages = edge = 0
            for b in range(2):
                block = kv_len[b * Rb:(b + 1) * Rb]
                walked, interior = _brute_force_pages(block, ps, window, pps)
                assert first[b] <= lo[b] <= hi[b] <= end[b], (kv_len, b)
                plan = set(range(int(first[b]), int(end[b])))
                assert walked <= plan, (kv_len, b, walked, plan)
                assert not consecutive or walked == plan, (kv_len, b)
                assert set(range(int(lo[b]), int(hi[b]))) == interior, (
                    kv_len, b, interior, (lo[b], hi[b]))
                pages += len(plan)
                edge += len(plan - interior)
        if consecutive:
            start, real = int(kv_len[0]) - 1, int((kv_len > 0).sum())
            if real:
                assert chunk_walk_counts(start, real, 16, Rb, ps, window,
                                         pps) == (pages, edge), kv_len


@pytest.mark.parametrize("ag_method", ["push", "fused"])
def test_sp_flash_decode(ctx, ag_method):
    """Full SP pipeline on the mesh vs dense golden, ragged lengths —
    over the generic push AG and the fused AG+merge latency path."""
    n = ctx.num_ranks
    B, Hq, Hkv, D = 2, 4, 2, 128
    s_local = 128
    S = n * s_local
    q = jax.random.normal(jax.random.key(0), (B, Hq, D), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (B, Hkv, S, D), jnp.float32)
    kv_lens = jnp.array([S, S // 2 + 17], jnp.int32)
    ks = ctx.shard(k, P(None, None, "x"))
    vs = ctx.shard(v, P(None, None, "x"))
    f = jax.jit(lambda *a: sp_gqa_flash_decode(ctx, *a, ag_method=ag_method))
    out = f(q, ks, vs, kv_lens)
    golden = _dense_golden(q, k, v, np.asarray(kv_lens))
    assert_allclose(np.asarray(out), golden, atol=1e-3, rtol=1e-3)
    # repeated-call safety (ws buffer addresses are reused across calls)
    out2 = f(q, ks, vs, kv_lens)
    assert_allclose(np.asarray(out2), golden, atol=1e-3, rtol=1e-3)
