"""The KV pool stays in one layout and in place (ISSUE 25): the stacked forms
``gqa_decode_paged(layer=)`` / ``paged_kv_write(layer=)`` and the one layer
body of the paged programs, held BITWISE to the per-layer forms they replace
(the Python-unrolled hooked path, a second program to XLA, to the last bits).

The references below are the parent commit's functions (cad256e), kept here
trimmed to what the comparison needs: the pool sliced per layer, written by
the window scatter ``at[page, :, slot].set``, read by the 4-D kernel call and
re-stacked. What they cost on the chip is why they went (PERF.md section 6,
PR 25); what they compute is the contract. The compile-level guard that the
programs hold no pool-shaped copy is in ``tests/test_aot_topology.py``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conftest  # noqa: F401  (forces the CPU platform)
from triton_dist_tpu.models.llama import (LlamaConfig,
                                          decode_multistep_paged,
                                          decode_speculate_paged,
                                          decode_step_paged, init_page_pool,
                                          init_params, prefill_chunk_paged,
                                          rmsnorm, rope)
from triton_dist_tpu.ops.flash_decode import gqa_decode_paged, paged_kv_write

# float32: XLA:CPU may keep excess precision across fused bf16 operations,
# so two program structures (the scan, the parent's unrolled slices) agree
# bitwise only where there is no narrower type to skip
CFG = dataclasses.replace(LlamaConfig.tiny(2), dtype=jnp.float32)
PAGE, PPS, B = 16, 4, 4
N_PAGES = 1 + B * PPS                       # page 0 is the scratch page


# -- the parent's functions -------------------------------------------------

def ref_kv_write(k_pages, v_pages, k_new, v_new, block_table, pos,
                 active=None):
    page = block_table[jnp.arange(pos.shape[0]), pos // k_pages.shape[2]]
    if active is not None:
        page = jnp.where(active, page, 0)
    slot = pos % k_pages.shape[2]
    return (k_pages.at[page, :, slot].set(k_new),
            v_pages.at[page, :, slot].set(v_new))


def ref_layers(params, x, pos, kv_len, active, pages, bt):
    R = x.shape[0]
    Hq, Hkv, Dh = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
    positions = pos[:, None].astype(jnp.int32)

    def body(x, layer):
        p, kp, vp = layer
        h = rmsnorm(x, p["attn_norm"], CFG.norm_eps)
        q = rope((h @ p["wq"]).reshape(R, 1, Hq, Dh), positions,
                 CFG.rope_theta)[:, 0]
        k = rope((h @ p["wk"]).reshape(R, 1, Hkv, Dh), positions,
                 CFG.rope_theta)[:, 0]
        v = (h @ p["wv"]).reshape(R, 1, Hkv, Dh)[:, 0]
        kp, vp = ref_kv_write(kp, vp, k, v, bt, pos, active)
        attn, _ = gqa_decode_paged(q, kp, vp, bt, kv_len)
        x = x + attn.reshape(R, Hq * Dh) @ p["wo"]
        h = rmsnorm(x, p["mlp_norm"], CFG.norm_eps)
        ff = (jax.nn.silu((h @ p["w_gate"]).astype(jnp.float32)
                          ).astype(h.dtype) * (h @ p["w_up"])) @ p["w_down"]
        return x + ff.astype(x.dtype), (kp, vp)

    x, (ks, vs) = jax.lax.scan(body, x, (params["blocks"], pages["k"],
                                         pages["v"]))
    return x, {"k": ks, "v": vs}


def ref_decode_step(params, token, pos, pages, bt, active=None):
    x = params["embed"][token].astype(CFG.dtype)
    x, pages = ref_layers(params, x, pos, (pos + 1).astype(jnp.int32),
                          active, pages, bt)
    x = rmsnorm(x, params["final_norm"], CFG.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return jnp.argmax(logits, -1).astype(jnp.int32), pages


def ref_multistep(params, token, pos, pages, bt, limit, horizon, eos_id):
    stopped = jnp.zeros(token.shape, jnp.bool_)
    toks = []
    for i in range(horizon):
        act = jnp.logical_and(i < limit, ~stopped)
        nxt, pages = ref_decode_step(params, token, pos, pages, bt, act)
        token = jnp.where(act, nxt, token)
        pos = jnp.where(act, pos + 1, pos)
        if eos_id is not None:
            stopped = jnp.logical_or(stopped,
                                     jnp.logical_and(act, nxt == eos_id))
        toks.append(nxt)
    return jnp.stack(toks), token, pos, pages


def ref_speculate(params, token, pos, pages, bt, limit, K, hist, hist_len):
    from triton_dist_tpu.serving.speculate import ngram_draft, spec_accept
    nb = token.shape[0]
    drafts = ngram_draft(hist, hist_len, K - 1)
    inp = jnp.concatenate([token[:, None].astype(jnp.int32), drafts], axis=1)
    offs = jnp.arange(K, dtype=jnp.int32)[None, :]
    ract = offs < limit[:, None]
    rpos = jnp.where(ract, pos[:, None] + offs, 0).astype(jnp.int32)
    fl = lambda a: a.reshape((nb * K,) + a.shape[2:])          # noqa: E731
    nxt_fl, pages = ref_decode_step(params, fl(inp), fl(rpos), pages,
                                    jnp.repeat(bt, K, axis=0), fl(ract))
    nxt = nxt_fl.reshape(nb, K)
    return nxt.T, spec_accept(inp, nxt, ract, None), pages


def ref_chunk(params, tokens, start, prompt_len, pages, bt_row):
    C = tokens.shape[0]
    idx = start.astype(jnp.int32) + jnp.arange(C, dtype=jnp.int32)
    valid = idx < prompt_len
    pos = jnp.where(valid, idx, 0).astype(jnp.int32)
    kv_len = jnp.where(valid, idx + 1, 0).astype(jnp.int32)
    bt = jnp.broadcast_to(bt_row[None, :], (C, bt_row.shape[0]))
    x = params["embed"][tokens].astype(CFG.dtype)
    x, pages = ref_layers(params, x, pos, kv_len, valid, pages, bt)
    last = jnp.clip(prompt_len - 1 - start, 0, C - 1).astype(jnp.int32)
    h_last = rmsnorm(jax.lax.dynamic_slice_in_dim(x, last, 1),
                     params["final_norm"], CFG.norm_eps)
    logits = (h_last @ params["lm_head"]).astype(jnp.float32)
    return jnp.argmax(logits[0], -1).astype(jnp.int32), pages


# -- shared state ------------------------------------------------------------

@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def pool():
    """A pool of noise: a write that lands anywhere it should not shows."""
    shape = init_page_pool(CFG, N_PAGES, PAGE)["k"].shape
    k = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32)
    return {"k": k.astype(CFG.dtype), "v": (-k).astype(CFG.dtype)}


BT = jnp.asarray(1 + np.arange(B * PPS).reshape(B, PPS), jnp.int32)


def live_pages(tree):
    """Every page but the scratch page (page 0) of a pool's leaves."""
    return jax.tree_util.tree_map(lambda a: a[..., 1:, :, :, :], tree)


def same(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b), strict=True))


# -- (a) the kernel on the stack ----------------------------------------------

@pytest.mark.parametrize("kv_len", [[0, 0, 0, 0], [5, 16, 23, 64],
                                    [64, 64, 1, 17]],
                         ids=["empty", "mid-page", "full"])
@pytest.mark.parametrize("layer", [0, 1])
def test_stacked_decode_equals_per_layer(pool, kv_len, layer):
    q = jax.random.normal(jax.random.PRNGKey(2),
                          (B, CFG.n_heads, CFG.head_dim), CFG.dtype)
    kv_len = jnp.asarray(kv_len, jnp.int32)
    # block-table tails past the walked pages hold garbage, out of range too
    used = (np.asarray(kv_len)[:, None] + PAGE - 1) // PAGE
    bt = jnp.where(np.arange(PPS)[None, :] < used, BT,
                   jnp.asarray([[-7, 10_000, 3, -1]], jnp.int32))
    want = gqa_decode_paged(q, pool["k"][layer], pool["v"][layer], bt, kv_len)
    got = jax.jit(lambda l: gqa_decode_paged(
        q, pool["k"], pool["v"], bt, kv_len, layer=l))(jnp.int32(layer))
    assert same(got, want)
    assert same(gqa_decode_paged(q, pool["k"], pool["v"], bt, kv_len,
                                 layer=layer), want)


def test_stacked_forms_refuse_a_missing_or_stray_layer(pool):
    q = jnp.zeros((B, CFG.n_heads, CFG.head_dim), CFG.dtype)
    z = jnp.zeros((B,), jnp.int32)
    new = jnp.zeros((B, CFG.n_kv_heads, CFG.head_dim), CFG.dtype)
    with pytest.raises(AssertionError):
        gqa_decode_paged(q, pool["k"], pool["v"], BT, z)
    with pytest.raises(AssertionError):
        gqa_decode_paged(q, pool["k"][0], pool["v"][0], BT, z, layer=0)
    with pytest.raises(AssertionError):
        paged_kv_write(pool["k"], pool["v"], new, new, BT, z)
    with pytest.raises(AssertionError):
        paged_kv_write(pool["k"][0], pool["v"][0], new, new, BT, z, layer=0)


# -- (b) the write in place ---------------------------------------------------

def _rows(n, seed):
    shape = (n, CFG.n_kv_heads, CFG.head_dim)
    k = jax.random.normal(jax.random.PRNGKey(seed), shape, CFG.dtype)
    return k, k * 2


WRITE_CASES = {
    # every slot live, each on its own page and row
    "all-live": dict(pos=[0, 17, 33, 63], active=None),
    # two rows parked on page 0 at once (rows 4 and 5 of it), two live
    "parked": dict(pos=[4, 17, 5, 63], active=[False, True, False, True]),
    # every row parked: nothing but the scratch page may change
    "all-parked": dict(pos=[1, 2, 3, 4], active=[False] * 4),
    # a block-table id outside the pool drops the write; a negative one
    # counts from the end, as in the per-layer form
    "stray-ids": dict(pos=[0, 17, 33, 63], active=None,
                      bt=[[10_000] * PPS, [-1] * PPS, [-10_000] * PPS,
                          list(range(1, 1 + PPS))]),
}


@pytest.mark.parametrize("case", WRITE_CASES)
@pytest.mark.parametrize("layer", [0, 1])
def test_write_in_place_equals_window_scatter(pool, case, layer):
    c = WRITE_CASES[case]
    pos = jnp.asarray(c["pos"], jnp.int32)
    act = None if c["active"] is None else jnp.asarray(c["active"])
    bt = jnp.asarray(c.get("bt", BT), jnp.int32)
    kn, vn = _rows(B, 3)
    wk, wv = ref_kv_write(pool["k"][layer], pool["v"][layer], kn, vn, bt,
                          pos, act)
    want = (pool["k"].at[layer].set(wk), pool["v"].at[layer].set(wv))
    got = jax.jit(lambda l: paged_kv_write(
        pool["k"], pool["v"], kn, vn, bt, pos, active=act,
        layer=l))(jnp.int32(layer))
    assert same(got, want)
    if case == "all-parked":
        assert same(got[0][:, 1:], pool["k"][:, 1:])
    # the per-layer form: the same rows of a [P, ...] pool
    assert same(paged_kv_write(pool["k"][layer], pool["v"][layer], kn, vn,
                               bt, pos, active=act), (wk, wv))
    # ONE pool of ``[K | V]`` rows (``v_pages`` None): the same rows, side by
    # side, in one scatter
    both, none = paged_kv_write(jnp.concatenate([pool["k"], pool["v"]], -1),
                                None, kn, vn, bt, pos, active=act,
                                layer=layer)
    assert none is None and same(both, jnp.concatenate(want, -1))


def test_write_in_place_speculative_rows(pool):
    """B*K rows, K consecutive positions a slot (crossing a page), rows past
    a slot's limit parked: the verify dispatch's pattern."""
    K = 4
    pos0 = jnp.asarray([14, 0, 30, 61], jnp.int32)
    limit = jnp.asarray([4, 2, 0, 3], jnp.int32)
    offs = jnp.arange(K, dtype=jnp.int32)[None, :]
    ract = (offs < limit[:, None]).reshape(B * K)
    rpos = jnp.where(ract.reshape(B, K), pos0[:, None] + offs,
                     0).reshape(B * K).astype(jnp.int32)
    bt = jnp.repeat(BT, K, axis=0)
    kn, vn = _rows(B * K, 4)
    # the parked rows of one slot share one row of the scratch page: give
    # them one value, so which of them lands last cannot show
    parked = jnp.logical_not(ract)[:, None, None]
    kn, vn = jnp.where(parked, kn[:1], kn), jnp.where(parked, vn[:1], vn)
    wk, wv = ref_kv_write(pool["k"][1], pool["v"][1], kn, vn, bt, rpos, ract)
    got = paged_kv_write(pool["k"], pool["v"], kn, vn, bt, rpos, active=ract,
                         layer=1)
    assert same(got, (pool["k"].at[1].set(wk), pool["v"].at[1].set(wv)))


# -- (b') a chunk's run of rows, a page at a time (ISSUE 47) --------------------

RUN_C = 32                   # rows of the run: two pages, three when unaligned
RUN_CASES = {
    # start, prompt_len: rows [start, start + RUN_C) of which those before
    # prompt_len are live, as ``prefill_chunk_paged`` hands them over
    "aligned": dict(start=16, plen=48),
    "unaligned": dict(start=5, plen=37),
    "spans-three-pages": dict(start=15, plen=47),
    "ends-mid-chunk": dict(start=5, plen=20),
    "ends-at-a-page-edge": dict(start=5, plen=32),
    "one-token": dict(start=32, plen=33),
    "no-row-live": dict(start=40, plen=40),
    "no-mask": dict(start=21, plen=53, active=False),
    # a window layer's ring of three pages: positions 40..71 land on rows
    # 40..47 and 0..23 of the ring, so the run wraps inside the chunk
    "ring-wraps": dict(start=40, plen=72, ring=3),
    "ring-wraps-padded": dict(start=88, plen=100, ring=3),
    "one-pool-of-kv-rows": dict(start=5, plen=30, one_pool=True),
    "keys-wider-than-values": dict(start=13, plen=45, wide_keys=True),
    "per-layer-pool": dict(start=5, plen=30, stacked=False),
    "scanned-layers": dict(start=21, plen=50, scanned=True),
    # ids outside the pool write nothing, a negative one counts from the end
    "stray-ids": dict(start=5, plen=37, table=[10_000, -1, -10_000, 3]),
    "garbage-before": dict(start=5, plen=30, garbage=True),
}


def bits(tree):
    return [np.asarray(a).view(np.uint32)
            for a in jax.tree_util.tree_leaves(tree)]


@pytest.mark.parametrize("case", RUN_CASES)
def test_run_write_equals_row_scatter(pool, case):
    """``paged_kv_write(shared_table=True)`` against the row scatter on the
    same rows: every page but the scratch page, bit for bit. Page 0 is left
    out because the two differ there BY DESIGN: the scatter parks masked
    rows on it, the run write leaves it alone; its bytes are unspecified
    and no live row ever reads them."""
    c = RUN_CASES[case]
    idx = c["start"] + jnp.arange(RUN_C, dtype=jnp.int32)
    valid = idx < c["plen"]
    pos = jnp.where(valid, idx, 0).astype(jnp.int32)
    row = jnp.asarray(c.get("table", BT[2]), jnp.int32)
    if "ring" in c:
        row = row[0] + jnp.arange(c["ring"], dtype=jnp.int32)
        pos = pos % (c["ring"] * PAGE)
    table = jnp.broadcast_to(row[None, :], (RUN_C, row.shape[0]))
    active = None if c.get("active") is False else valid
    kn, vn = _rows(RUN_C, 5)
    kp, vp = pool["k"], pool["v"]
    if c.get("garbage"):
        kp, vp = jnp.full_like(kp, jnp.nan), jnp.full_like(vp, jnp.nan)
    if c.get("one_pool"):
        kp, vp = jnp.concatenate([kp, vp], -1), None
    if c.get("wide_keys"):
        kp, kn = jnp.concatenate([kp, vp], -1), jnp.concatenate([kn, vn], -1)

    def write(shared):
        def at(kp, vp, layer, kn, vn):
            return paged_kv_write(kp, vp, kn, vn, table, pos, active=active,
                                  layer=layer, shared_table=shared)
        if c.get("scanned"):
            # a traced layer inside a scan that carries the pool, each layer
            # its own rows: the form the programs' layer loop has
            def body(pools, layer):
                return at(*pools, layer, kn * (layer + 1), vn - layer), None
            return jax.jit(lambda pools: jax.lax.scan(
                body, pools, jnp.arange(CFG.n_layers, dtype=jnp.int32))[0])(
                    (kp, vp))
        if c.get("stacked") is False:
            return jax.jit(lambda k, v: at(k, v, None, kn, vn))(
                kp[1], None if vp is None else vp[1])
        return jax.jit(lambda k, v: at(k, v, jnp.int32(1), kn, vn))(kp, vp)

    got, want = write(True), write(False)
    assert (got[1] is None) == (want[1] is None) == (vp is None)
    for g, w in zip(bits(live_pages(got)), bits(live_pages(want)),
                    strict=True):
        assert np.array_equal(g, w)
    before = (kp, vp) if c.get("stacked") is not False else (
        kp[1], None if vp is None else vp[1])
    changed = any((g != b).any() for g, b in zip(bits(live_pages(got)),
                                                 bits(live_pages(before))))
    assert changed == (case != "no-row-live")
    # the run write leaves the scratch page alone
    for g, b in zip(bits(got), bits(before), strict=True):
        assert np.array_equal(g[..., 0, :, :, :], b[..., 0, :, :, :])


# -- (c) the programs ----------------------------------------------------------

@pytest.mark.parametrize("horizon", [1, 4])
def test_multistep_equals_parent(params, pool, horizon):
    tok = jnp.asarray([3, 70, 200, 9], jnp.int32)
    pos = jnp.asarray([14, 0, 30, 59], jnp.int32)     # row 0 crosses a page
    limit = jnp.asarray([horizon, 1, 0, horizon], jnp.int32)
    want = ref_multistep(params, tok, pos, pool, BT, limit, horizon, 7)
    got = jax.jit(lambda pg: decode_multistep_paged(
        params, tok, pos, CFG, pg, BT, limit, horizon, eos_id=7))(pool)
    assert same(got, want)


def test_speculate_equals_parent(params, pool):
    K, H = 4, 8
    tok = jnp.asarray([3, 70, 200, 9], jnp.int32)
    pos = jnp.asarray([14, 0, 30, 59], jnp.int32)
    limit = jnp.asarray([4, 2, 0, 3], jnp.int32)
    hist = jnp.asarray(np.arange(B * H).reshape(B, H) % 5, jnp.int32)
    hlen = jnp.full((B,), H, jnp.int32)
    toks, acc, pages = ref_speculate(params, tok, pos, pool, BT, limit, K,
                                     hist, hlen)
    got = jax.jit(lambda pg: decode_speculate_paged(
        params, tok, pos, CFG, pg, BT, limit, K, hist, hlen))(pool)
    assert same((got[0], got[1]), (toks, acc))
    # rows past a slot's limit all park on one row of the scratch page and
    # race there by design: every live page must agree
    assert same(live_pages(got[6]), live_pages(pages))


@pytest.mark.parametrize("start,prompt_len", [(0, 16), (16, 40), (5, 21),
                                              (21, 30), (32, 33)],
                         ids=["aligned-full", "aligned-last", "unaligned",
                              "unaligned-padded", "one-token"])
def test_chunk_equals_parent(params, pool, start, prompt_len):
    C = 16
    tokens = jnp.asarray(np.arange(C) * 31 % CFG.vocab_size, jnp.int32)
    want = ref_chunk(params, tokens, jnp.int32(start), jnp.int32(prompt_len),
                     pool, BT[2])
    got = jax.jit(lambda pg: prefill_chunk_paged(
        params, tokens, jnp.int32(start), jnp.int32(prompt_len), CFG, pg,
        BT[2]))(pool)
    assert int(got[0]) == int(want[0])
    # the padded tail parks on (page 0, row 0), every row of it: see above.
    # The chunk's rows share one walk of the pages (``gqa_prefill_paged``)
    # where the parent ran C rows of decode: the rows written are the
    # parent's exactly, their values up to the order of summation
    same_rows_close_values(live_pages(got[1]), live_pages(want[1]),
                           live_pages(pool))


# -- (d) the hooked path is the same body --------------------------------------

def _identity_attn_io(q, k, v, kp, vp, bt, pos, kv_len, active):
    assert kp.ndim == 4, "attn_io keeps its per-layer contract"
    kp, vp = paged_kv_write(kp, vp, k, v, bt, pos, active=active)
    return gqa_decode_paged(q, kp, vp, bt, kv_len)[0], kp, vp


def same_rows_close_values(got, want, before):
    """The unrolled loop and the scan are two programs to XLA:CPU, which
    fuses them differently (rope's sin and cos, the dots' blocking): the
    rows written are held exactly, their values to float32's last bits."""
    for n in "kv":
        g, w, b = (np.asarray(t[n]) for t in (got, want, before))
        assert np.array_equal((g != b).any(-1), (w != b).any(-1)), n
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("hook", ["attn_io", "ffn", "linear"])
def test_hooked_path_equals_scanned(params, pool, hook):
    def dense_ffn(h, p):
        return (jax.nn.silu((h @ p["w_gate"]).astype(jnp.float32)
                            ).astype(h.dtype) * (h @ p["w_up"])) @ p["w_down"]

    hooks = {"attn_io": dict(attn_io=_identity_attn_io),
             "ffn": dict(ffn=dense_ffn),
             "linear": dict(linear=lambda h, w, name: h @ w)}[hook]
    tok = jnp.asarray([3, 70, 200, 9], jnp.int32)
    pos = jnp.asarray([14, 0, 30, 59], jnp.int32)
    act = jnp.asarray([True, True, False, True])
    want = decode_step_paged(params, tok, pos, CFG, pool, BT, active=act,
                             sample=True)
    got = decode_step_paged(params, tok, pos, CFG, pool, BT, active=act,
                            sample=True, **hooks)
    assert same(got[0], want[0])
    same_rows_close_values(got[1], want[1], pool)
    tokens = jnp.asarray(np.arange(16) * 31 % CFG.vocab_size, jnp.int32)
    args = (params, tokens, jnp.int32(5), jnp.int32(18), CFG, pool, BT[1])
    cw, cg = prefill_chunk_paged(*args), prefill_chunk_paged(*args, **hooks)
    assert int(cg[0]) == int(cw[0])
    # every page but page 0: the ``attn_io`` hook takes the chunk as rows of
    # decode, whose scatter parks the padded tail on the scratch page, where
    # the chunk's own run write leaves that page alone (ISSUE 47)
    same_rows_close_values(live_pages(cg[1]), live_pages(cw[1]),
                           live_pages(pool))


# -- (e) the engine, its parameters as they come or held as asked (ISSUE 38) ---

PROMPTS = [[int(t) for t in np.arange(n) * 31 % CFG.vocab_size + 1]
           for n in (21, 16, 5, 33)]
NEW_TOKENS = 6


def parent_tokens(params, prompt):
    """What the parent's functions serve one request alone: the prompt
    through 16-token chunks, then one token a decode step."""
    live = jnp.asarray([1, 0, 0, 0], jnp.int32)
    chunk_fn = jax.jit(ref_chunk)
    step_fn = jax.jit(lambda p, t, pos, pg, bt: ref_multistep(
        p, t, pos, pg, bt, live, 1, None))
    pool = init_page_pool(CFG, N_PAGES, PAGE)
    row, n = BT[0], len(prompt)
    for start in range(0, n, 16):
        chunk = np.zeros(16, np.int32)
        part = prompt[start:start + 16]
        chunk[:len(part)] = part
        tok, pool = chunk_fn(params, jnp.asarray(chunk), jnp.int32(start),
                             jnp.int32(n), pool, row)
    out = [int(tok)]
    bt = jnp.zeros((B, PPS), jnp.int32).at[0].set(row)
    for i in range(NEW_TOKENS - 1):
        toks, _, _, pool = step_fn(
            params, jnp.zeros(B, jnp.int32).at[0].set(out[-1]),
            jnp.zeros(B, jnp.int32).at[0].set(n + i), pool, bt)
        out.append(int(toks[0, 0]))
    return out


def build_engine(params, monkeypatch, held):
    """``held``: the engine takes the branch it takes on an accelerator (the
    decode program compiled with each parameter leaf's layout left to the
    compiler, the weights committed to what it chose), here on the CPU."""
    from triton_dist_tpu.serving import engine as engine_mod
    if held:
        monkeypatch.setattr(engine_mod.jax, "default_backend", lambda: "tpu")
    eng = engine_mod.ServingEngine(
        params, CFG, num_slots=B, page_size=PAGE, num_pages=N_PAGES - 1,
        pages_per_seq=PPS, decode_horizon=4, prefill_chunk=16)
    monkeypatch.undo()
    return eng


@pytest.fixture(scope="module")
def parents_tokens(params):
    return {i: parent_tokens(params, p) for i, p in enumerate(PROMPTS)}


@pytest.mark.parametrize("held", [False, True],
                         ids=["as-they-come", "held-as-asked"])
def test_engine_serves_the_parents_tokens(params, parents_tokens,
                                          monkeypatch, held):
    """On the CPU the engine leaves its parameters as they come and reports
    that it re-laid out nothing; made to take the accelerator's branch, it
    compiles the decode program ahead, holds the weights in the formats that
    program asked for (on this backend: the ones they have), and serves the
    same tokens from ONE decode and ONE chunk program."""
    from jax.stages import Compiled
    eng = build_engine(params, monkeypatch, held)
    assert isinstance(eng._step, Compiled) == held
    assert (eng._formats is not None) == held
    out = eng.run(max_steps=400, arrivals=[
        (i, p, NEW_TOKENS) for i, p in enumerate(PROMPTS)])
    assert out == parents_tokens
    assert eng.compile_stats == {
        "decode_compiles": 1, "prefill_chunk_compiles": 1,
        "params_relaid_bytes": 0, "params_relaid_leaves": []}
    assert eng.metrics.snapshot()["params_relaid_bytes"] == 0


def test_setting_params_recommits_them(params, monkeypatch):
    """``eng.params = w`` hands the programs ``w`` in the formats they were
    compiled for: the engine then serves what an engine built on ``w``
    serves, and compiles nothing."""
    other = jax.tree_util.tree_map(lambda a: a * 1.5, params)
    want = build_engine(other, monkeypatch, held=True).run(
        max_steps=400, arrivals=[(0, PROMPTS[0], NEW_TOKENS)])
    eng = build_engine(params, monkeypatch, held=True)
    first = eng.run(max_steps=400, arrivals=[(0, PROMPTS[0], NEW_TOKENS)])
    eng.params = other
    for got, fmt in zip(jax.tree_util.tree_leaves(eng.params),
                        jax.tree_util.tree_leaves(eng._formats)):
        assert got.format.sharding == fmt.sharding
    again = eng.run(max_steps=400, arrivals=[(1, PROMPTS[0], NEW_TOKENS)])
    assert again[1] == want[0] != first[0]
    assert eng.compile_stats["prefill_chunk_compiles"] == 1


def test_commit_copies_only_what_is_not_held_as_asked():
    """``layouts.commit`` on formats that DO ask for something (the CPU takes
    a concrete layout too): the leaf asked for column-major is copied into
    it, value for value, and counted by ``relaid``; a leaf whose format asks
    for nothing is the caller's buffer; a tree already committed comes back
    as it is."""
    from jax.experimental.layout import Format, Layout
    from triton_dist_tpu.serving import layouts
    w = jnp.arange(24, dtype=jnp.float32).reshape(4, 6)
    b = jnp.ones((3,))
    asked = {"w": Format(Layout((1, 0), ()), w.sharding),
             "b": Format(None, b.sharding)}
    got = layouts.commit({"w": w, "b": b}, asked)
    assert got["w"].format == asked["w"] and got["b"] is b
    assert np.array_equal(got["w"], w)
    assert layouts.relaid({"w": w, "b": b}, asked) == [{
        "leaf": "['w']", "shape": [4, 6], "from": "{1,0}", "to": "{0,1}",
        "bytes": 96}]
    assert layouts.commit(got, asked)["w"] is got["w"]
