"""The latent-attention, shared-plus-routed-expert family (ISSUE 26:
``models/mla.py``, ``ops/mla_decode.py``) held to the benchmark's plain
reference (``benchmark/references/latent_moe_lm.py``, float32, imports nothing
of the program) at a small size on the CPU, seeded weights, float32:

- the family's forward and its chunk-then-decode through the latent cache
  against the reference's full forward, on logits;
- the absorbed and the plain attention forms agree on one cache;
- the latent kernel (interpret mode) against ``jax.numpy``: decode rows of
  ragged lengths and idle rows, a prefill chunk's row blocks that share a
  table, groups of pages an update, short last groups;
- the router with a non-zero bias and YaRN's frequencies against literal
  transcriptions of the published code;
- THE SHARES ADD UP: 16 experts over 4 shares, the routed parts of all shares
  plus the shared expert once = the uncut reference layer;
- through ``ServingEngine``: a tight pool's preempt-and-resume replays the
  ample pool's tokens, page copy / export / import on a latent pool,
  ``host_syncs`` flat while the two counters move; what the family lacks is
  refused by name.
"""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conftest  # noqa: F401  (forces the CPU platform)
from benchmark.adapters.latent_engine import Adapter
from benchmark.references import latent_moe_lm as ref
from triton_dist_tpu.models import mla
from triton_dist_tpu.models.llama import (decode_step_paged,
                                          prefill_chunk_paged)
from triton_dist_tpu.ops import mla_decode as mla_kernel
from triton_dist_tpu.ops.mla_decode import mla_decode_paged
from triton_dist_tpu.serving import ServingEngine

PAGE, PPS, CHUNK = 16, 4, 16


TINY = os.path.join(conftest.REPO_ROOT, "benchmark", "tests",
                    "rehearsal_latent", "configs", "tiny-latent.json")


def file_cfg(held=4, first=4, layers=3):
    """A configuration FILE's keys at test size (what the adapter and the
    reference read): the benchmark's own tiny rehearsal file, in float32,
    with the share and the depth the test asks for."""
    with open(TINY) as f:
        cfg = json.load(f)
    cfg.update(n_routed_experts=held, share={"first_expert": first},
               num_hidden_layers=layers, torch_dtype="float32")
    return cfg


@pytest.fixture(scope="module")
def model():
    """(file config, program config, weights): a share of 4 of 16 experts."""
    fc = file_cfg()
    pc = Adapter(fc)._program_config()
    w = jax.jit(lambda k: ref.init_weights(k, fc))(jax.random.PRNGKey(3))
    return fc, pc, w


def tokens_of(n, seed=5):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 1,
                                         256), np.int32)


# -- against the reference, on logits -------------------------------------------

def test_forward_matches_the_reference(model):
    fc, pc, w = model
    toks = tokens_of(40)
    want = np.asarray(ref.logits(w, toks, fc))
    got = np.asarray(mla.forward(w, jnp.asarray(toks)[None], pc)[0])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_chunks_then_decode_through_the_latent_cache_match_the_reference(
        model):
    """24 prompt tokens in two chunks (the second half padded), then 16
    teacher-forced decode steps beside a parked row, all through one pool."""
    fc, pc, w = model
    toks = tokens_of(40)
    want = np.asarray(ref.logits(w, toks, fc))
    pool = pc.paged.init_pool(pc, 9, PAGE)
    bt = jnp.asarray([3, 5, 2, 7], jnp.int32)
    n_pre = 24
    chunk = jax.jit(lambda t, s, pg: prefill_chunk_paged(
        w, t, s, jnp.int32(n_pre), pc, pg, bt))
    for start in range(0, n_pre, CHUNK):
        part = np.zeros(CHUNK, np.int32)
        real = toks[start:min(start + CHUNK, n_pre)]
        part[:len(real)] = real
        tok, pool = chunk(jnp.asarray(part), jnp.int32(start), pool)
    assert int(tok) == int(want[n_pre - 1].argmax())
    step = jax.jit(lambda t, pos, pg: decode_step_paged(
        w, t, pos, pc, pg, jnp.stack([bt, jnp.zeros(4, jnp.int32)]),
        active=jnp.asarray([True, False]), counters=True))
    rows = 0
    for i in range(n_pre, 40):
        logits, pool, counts = step(jnp.asarray([toks[i], 0]),
                                    jnp.asarray([i, 0]), pool)
        np.testing.assert_allclose(np.asarray(logits[0]), want[i],
                                   atol=2e-5, rtol=1e-4)
        rows += int(counts[0])
        assert 0 <= int(counts[1]) <= 2 * fc["n_routed_experts"]
    # only the live row counts: at most k assignments a sparse layer a step
    assert 0 < rows <= 16 * 2 * fc["num_experts_per_tok"]


def test_absorbed_and_plain_attention_agree_on_one_cache(model):
    fc, pc, w = model
    p = {n: a[0] for n, a in w["blocks"].items() if not n.startswith("we_")}
    T = 37
    h = jax.random.normal(jax.random.PRNGKey(9), (T, pc.d_model))
    pos = jnp.arange(T)
    q_nope, q_rope, c, k_rope = mla.latent_qkv(pc, p, h, pos)
    plain = mla.latent_attention_plain(
        pc, p, q_nope, q_rope, c, k_rope, jnp.tril(jnp.ones((T, T), bool)))
    # the same rows through the pool and the kernel: layer 1 of a 3-layer pool
    pool = pc.paged.init_pool(pc, 9, PAGE)
    bt = jnp.broadcast_to(jnp.asarray([6, 1, 8, 4], jnp.int32), (T, PPS))
    out, _ = pc.paged.attention(pc, p, h, 1, pool, bt, pos, pos + 1, None,
                                False, lambda x, w_, name: x @ w_, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(plain @ p["wo"]),
                               atol=2e-6, rtol=1e-4)


# -- the kernel -------------------------------------------------------------------

def attend_ref(q, pool, layer, bt, kv_len, latent, scale):
    """``jax.numpy`` twin of the kernel, row by row."""
    out = np.zeros(q.shape[:2] + (latent,), np.float32)
    for r in range(q.shape[0]):
        n = int(kv_len[r])
        if n == 0:
            continue
        rows = np.concatenate([np.asarray(pool[layer, int(p)])
                               for p in bt[r]])[:n]
        s = np.einsum("hw,tw->ht", np.asarray(q[r]), rows) * scale
        s = np.exp(s - s.max(-1, keepdims=True))
        out[r] = (s / s.sum(-1, keepdims=True)) @ rows[:, :latent]
    return out


G = mla_kernel.DECODE_PAGES_PER_GROUP
GC = mla_kernel.CHUNK_PAGES_PER_GROUP
PPS_D = 2 * max(G, GC) + 3  # the table: two groups and a short one
IDLE = [0] * 8


def _decode(kv_len, **kw):
    return dict(kv_len=kv_len, pps=PPS_D, **kw)


def _chunk(start, rows=16, valid=None, rows_per_block=4, **kw):
    """A prefill chunk's ``rows`` rows, ``start`` tokens cached before them:
    one table, row t attends ``start + t + 1`` keys, rows from ``valid`` on
    are padding (``kv_len`` 0)."""
    valid = rows if valid is None else valid
    kv_len = np.where(np.arange(rows) < valid, start + np.arange(rows) + 1, 0)
    return dict(kv_len=kv_len, pps=PPS_D, rows_per_block=rows_per_block, **kw)


KERNEL_CASES = {
    # a chunk's rows: row blocks that share the table, GC pages an update
    "chunk-2-rows-a-block": _chunk(2 * PAGE - 3, rows_per_block=2),
    "chunk-4-rows-a-block": _chunk(2 * PAGE - 3),
    "chunk-8-rows-a-block": _chunk(2 * PAGE - 3, rows_per_block=8),
    "chunk-one-block": _chunk(2 * PAGE - 3, rows_per_block=16),
    "chunk-first": _chunk(0),
    "chunk-first-of-two-pages": _chunk(0, rows=32),
    "chunk-starts-mid-page": _chunk(3 * PAGE + 5),
    "chunk-block-straddles-a-page": _chunk(PAGE - 2),
    "chunk-padding-tail-and-padding-block": _chunk(PAGE + 3, valid=10),
    "chunk-all-padding": _chunk(5 * PAGE, valid=0),
    "chunk-short-last-group": _chunk((GC + 2) * PAGE),
    "chunk-exactly-a-group": _chunk(GC * PAGE - 16),
    "chunk-two-groups-and-a-page": _chunk(2 * GC * PAGE - 9),
    "chunk-every-page": _chunk(PPS_D * PAGE - 16),
    "chunk-layer-0": _chunk((GC + 1) * PAGE + 3, valid=13, layer=0),
    "chunk-layer-1": _chunk((GC + 1) * PAGE + 3, valid=13, layer=1),
    "chunk-garbage-table": _chunk(GC * PAGE + 7, valid=14, garbage=True),
    "chunk-unread-inf-nan": _chunk(GC * PAGE + 7, valid=14, garbage=True,
                                   poison=True),
    "chunk-published-widths": _chunk((GC + 1) * PAGE + 5, rows=32, valid=27,
                                     rows_per_block=16, dims=(64, 640, 512)),
    # the decode rows: a table a row, G pages an update
    "loop-ragged": dict(),
    "loop-short-last-group": _decode(
        [(G + 3) * PAGE, (2 * G + 1) * PAGE - 5, 2 * PAGE, 0,
         (G + 1) * PAGE - 1, 0, 3 * PAGE + 2, (G - 1) * PAGE]),
    "loop-one-token": _decode([1, 0, 1, 1, 0, 0, 1, 0]),
    "loop-exactly-a-group": _decode([G * PAGE] * 3 + [0, 2 * G * PAGE, 0,
                                                      G * PAGE, G * PAGE - 1]),
    "loop-every-page": _decode([PPS_D * PAGE] * 2 + [0] * 5 + [PPS_D * PAGE]),
    "loop-page-boundary": _decode(
        [PAGE, PAGE + 1, G * PAGE, G * PAGE + 1, 2 * G * PAGE,
         2 * G * PAGE + 1, (G + 2) * PAGE, (G + 2) * PAGE + 1]),
    "loop-idle-first-last-between": _decode(
        [0, 0, 5 * PAGE - 2, 0, (G + 2) * PAGE, 9, 0, 0]),
    "loop-all-idle": _decode(IDLE),
    "loop-layer-0": _decode([40, 0, (G + 1) * PAGE + 3, 7, 0, 200, 16, 1],
                            layer=0),
    "loop-layer-1": _decode([40, 0, (G + 1) * PAGE + 3, 7, 0, 200, 16, 1],
                            layer=1),
    "loop-garbage-table": _decode(
        [3 * PAGE, 1, 0, G * PAGE, (G + 1) * PAGE, 0, 2 * G * PAGE + 1, 30],
        garbage=True),
    "loop-unread-inf-nan": _decode(
        [3 * PAGE - 4, 1, 0, G * PAGE, (G + 1) * PAGE + 1, 0,
         2 * G * PAGE + 1, 30], garbage=True, poison=True),
    "loop-published-widths": _decode([(G + 1) * PAGE + 5, 0], dims=(64, 640, 512)),
    # two blocks of DECODE_ROWS_PER_BLOCK rows: the second starts on an idle
    # row, and its short groups find the ring as the first block left it
    "loop-two-row-blocks": _decode(
        [(G + 2) * PAGE - 3, 0, 5, 2 * G * PAGE] * 4
        + [0, 0, 3 * PAGE + 1, 0, PPS_D * PAGE, 1, 0, (G + 1) * PAGE] * 2),
}


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_kernel_matches_numpy_ragged_and_inactive(case):
    c = KERNEL_CASES[case]
    Rb = c.get("rows_per_block", 1)
    pps, layer = c.get("pps", PPS), c.get("layer", 1)
    H, W, latent = c.get("dims", (2, 256, 128))
    kv_len = np.asarray(c.get("kv_len", [1, 16, 17, 0, 64, 33, 0, 48]),
                        np.int32)
    R, L = len(kv_len), 2
    P = 1 + R * pps
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (R, H, W))
    pool = np.array(jax.random.normal(ks[1], (L, P, PAGE, W)))
    bt = 1 + np.array(jax.random.permutation(ks[2], R * pps)).reshape(R, pps)
    if "kv_len" not in c:              # (the seed's garbage, kept)
        bt[5, 3] = 10_000              # past a row's live pages: never read
        bt[1, 1:] = -7
    if Rb > 1:                         # a chunk: every row the first's table
        bt[:] = bt[0]
    # what a row's walk may fetch: its own pages, or its row block's
    reach = kv_len.reshape(-1, Rb).max(axis=1).repeat(Rb)
    n_live = -(-reach // PAGE)
    if c.get("poison"):                # whatever no walk reads: inf and NaN
        unread = np.ones((L, P), bool)
        for r in range(R):
            unread[layer, bt[r, :n_live[r]]] = False
        pool[unread] = np.where(np.arange(W) % 2, np.inf, np.nan)
    if c.get("garbage"):               # past the live pages: never read
        junk = np.asarray([10_000, -7, P, 2**31 - 1, -2**31, P + 3])
        for r in range(R):
            bt[r, n_live[r]:] = np.resize(junk, pps - n_live[r])
    got = mla_decode_paged(q, jnp.asarray(pool), jnp.asarray(bt, jnp.int32),
                           jnp.asarray(kv_len), layer=layer,
                           latent_dim=latent, sm_scale=0.1,
                           rows_per_block=Rb)
    want = attend_ref(q, pool, layer, np.clip(bt, 0, P - 1), kv_len, latent,
                      0.1)
    live = kv_len > 0
    np.testing.assert_allclose(np.asarray(got)[live], want[live], atol=2e-5,
                               rtol=1e-4)
    # a dead row: finite and unread; zero when its whole walk is dead
    assert np.isfinite(np.asarray(got)).all()
    assert not np.asarray(got)[reach == 0].any()


def test_the_chunk_walk_at_one_page_a_group_is_the_decode_loop_to_the_bit(
        monkeypatch):
    """Sharing the walk changes no arithmetic: rows that share a table, four
    a block and ONE page a group, get what the decode loop gives them at one
    page a group as rows with (equal) tables of their own, which PR 31 held
    to the (row, page) grid's updates in the grid's order. What a larger
    group changes, in either walk, is the order of the float32 sums inside
    it, held by the tolerance above."""
    R, H, W, latent, L, P, pps = 8, 2, 256, 128, 2, 41, 5
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (R, H, W))
    pool = jax.random.normal(ks[1], (L, P, PAGE, W))
    bt = jnp.broadcast_to(1 + jax.random.permutation(ks[2], P - 1)[:pps],
                          (R, pps))
    kv_len = jnp.asarray([38, 39, 40, 41, 42, 43, 0, 0], jnp.int32)
    walk = lambda **kw: np.asarray(mla_decode_paged(          # noqa: E731
        q, pool, bt.astype(jnp.int32), kv_len, layer=1, latent_dim=latent,
        sm_scale=0.1, **kw))
    grouped = {rows: walk(rows_per_block=rows) for rows in (1, 4)}
    monkeypatch.setattr(mla_kernel, "DECODE_PAGES_PER_GROUP", 1)
    monkeypatch.setattr(mla_kernel, "CHUNK_PAGES_PER_GROUP", 1)
    paged = {rows: walk(rows_per_block=rows) for rows in (1, 4)}
    live = np.asarray(kv_len) > 0
    assert np.array_equal(paged[4][live], paged[1][live])
    for rows in (1, 4):
        assert not np.array_equal(grouped[rows], paged[rows]), (
            "a group's sums are reordered")


# -- literal transcriptions -------------------------------------------------------

def test_router_with_a_bias_matches_a_literal_transcription():
    """``noaux_tc`` with n_group = topk_group = 1: sigmoid scores, choice by
    score + bias, weights = the chosen scores (without the bias) over their
    sum, times the scaling factor."""
    pc = mla.LatentMoEConfig.tiny()
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    h = jax.random.normal(ks[0], (9, pc.d_model))
    w_r = jax.random.normal(ks[1], (pc.d_model, pc.n_routed_experts)) * 0.3
    bias = jax.random.normal(ks[2], (pc.n_routed_experts,)) * 0.5
    ids, wts = mla.route(pc, h, w_r, bias)
    ids2, wts2 = ref.route(h, w_r, bias, {"k": pc.topk,
                                          "route_scale": 2.827})
    hn, wn, bn = (np.asarray(a, np.float64) for a in (h, w_r, bias))
    moved = 0
    for t in range(9):
        scores = 1.0 / (1.0 + np.exp(-(hn[t] @ wn)))
        chosen = np.argsort(-(scores + bn), kind="stable")[:pc.topk]
        moved += set(chosen) != set(np.argsort(-scores)[:pc.topk])
        weight = scores[chosen] / scores[chosen].sum() * 2.827
        for got_i, got_w in ((ids, wts), (ids2, wts2)):
            assert list(np.asarray(got_i[t])) == list(chosen)
            np.testing.assert_allclose(np.asarray(got_w[t]), weight,
                                       rtol=1e-5)
    assert moved, "a bias that moves no choice tests nothing"


@pytest.mark.parametrize("rope_dim,theta,factor,orig,fast,slow", [
    (64, 50000.0, 32.0, 4096, 1.0, 1.0),       # the published settings
    (64, 10000.0, 40.0, 4096, 32.0, 1.0),      # a ramp over several indices
    (16, 50000.0, 4.0, 32, 1.0, 1.0)])         # the test size
def test_yarn_frequencies_match_a_literal_transcription(rope_dim, theta,
                                                        factor, orig, fast,
                                                        slow):
    def correction_dim(turns):
        return (rope_dim * math.log(orig / (turns * 2 * math.pi))) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(fast)), 0)
    high = min(math.ceil(correction_dim(slow)), rope_dim - 1)
    freq_extra = [1.0 / theta ** (i / rope_dim)
                  for i in range(0, rope_dim, 2)]
    freq_inter = [1.0 / (factor * theta ** (i / rope_dim))
                  for i in range(0, rope_dim, 2)]
    top = high + 0.001 if low == high else high
    want = []
    for i in range(rope_dim // 2):
        ramp = min(max((i - low) / (top - low), 0.0), 1.0)
        mask = 1.0 - ramp
        want.append(freq_inter[i] * (1 - mask) + freq_extra[i] * mask)
    pc = dataclasses.replace(
        mla.LatentMoEConfig.tiny(), qk_rope_head_dim=rope_dim,
        rope_theta=theta, rope_factor=factor, rope_original_max_pos=orig,
        rope_beta_fast=fast, rope_beta_slow=slow)
    z = {"rp": rope_dim, "theta": theta, "factor": factor, "orig": orig,
         "beta_fast": fast, "beta_slow": slow}
    np.testing.assert_allclose(mla.yarn_inv_freq(pc), want, rtol=1e-6)
    np.testing.assert_allclose(ref.yarn_inv_freq(z), want, rtol=1e-6)
    assert want[0] == pytest.approx(1.0) \
        and want[-1] == pytest.approx(freq_inter[-1])
    # the softmax scale: (nope + rope)^-0.5 x (0.1 ln(factor) + 1)^2
    published = mla.LatentMoEConfig()
    assert published.sm_scale == pytest.approx(0.13086, rel=1e-4)
    assert published.cache_width == 640


# -- the shares add up ------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 shares of 4: the program's FFN of every share (its
    held experts' part + the shared expert), summed, with the shared expert
    counted once, is the reference's UNCUT layer on the same rows."""
    whole = file_cfg(held=16, first=0, layers=2)
    w = jax.jit(lambda k: ref.init_weights(k, whole))(jax.random.PRNGKey(4))
    z = ref.sizes(whole)
    p = {n: a[0] for n, a in w["blocks"].items() if not n.startswith("we_")}
    tables = tuple(w["blocks"][n] for n in ("we_gate", "we_up", "we_down"))
    h = jax.random.normal(jax.random.PRNGKey(8), (24, 128))
    shared = ref.shared_part(h, p, None)
    uncut = ref.routed_part(h, p, tables, jnp.int32(0), z, None) + shared
    total, rows, used = 0.0, 0, set()
    for first in (0, 4, 8, 12):
        fc = file_cfg(held=4, first=first, layers=2)
        pc = Adapter(fc)._program_config()
        part = tuple(t[:, first:first + 4] for t in tables)
        out, counts = mla.sparse_ffn(pc, p, h, 1, None, tables=part)
        total = total + out - shared
        rows += int(counts["moe_local_rows"])
        assert 0 < int(counts["moe_experts_touched"]) <= 4
        # and the reference handed the same share gives the same part
        zs = ref.sizes(fc)
        np.testing.assert_allclose(
            np.asarray(out - shared),
            np.asarray(ref.routed_part(h, p, part, jnp.int32(0), zs, None)),
            atol=2e-6, rtol=1e-4)
    assert rows == 24 * whole["num_experts_per_tok"]   # every assignment once
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(uncut),
                               atol=5e-6, rtol=1e-4)


# -- through the engine ---------------------------------------------------------

REQUESTS = [(tokens_of(21, 11), 6), (tokens_of(30, 12), 7),
            (tokens_of(9, 13), 9), (tokens_of(17, 14), 5)]


def serve(model, num_pages):
    fc, pc, w = model
    eng = ServingEngine(w, pc, **{**fc["engine"], "num_pages": num_pages})
    for prompt, n in REQUESTS:
        eng.submit(prompt, n)
    return eng, eng.run(max_steps=200)


@pytest.fixture(scope="module")
def ample(model):
    return serve(model, 12)


def test_the_engine_serves_the_family_and_counts(model, ample):
    fc, pc, w = model
    eng, out = ample
    assert {r: len(t) for r, t in out.items()} == {
        i: n for i, (_, n) in enumerate(REQUESTS)}
    # greedy tokens are the reference's argmax, teacher-forced
    prompt, n = REQUESTS[1]
    seq = np.concatenate([prompt, out[1][:-1]]).astype(np.int32)
    logits = np.asarray(ref.logits(w, seq, fc))[len(prompt) - 1:]
    gap = logits.max(-1) - logits[np.arange(n), out[1]]
    assert gap.max() < 1e-4
    c = eng.metrics.counters
    assert c["preemptions"] == 0 and c["prefill_chunks"] >= 6
    assert 0 < c["moe_experts_touched"] <= c["moe_local_rows"]
    per_step = fc["n_routed_experts"] * 2          # 2 sparse layers
    assert c["moe_experts_touched"] <= per_step * c["decode_steps"]
    assert eng.compile_stats["decode_compiles"] == 1
    assert eng.compile_stats["prefill_chunk_compiles"] == 1
    # a latent chunk walks no K/V pages: the walk's counters do not exist
    assert not {"chunk_walk_pages", "chunk_walk_edge_pages"} & set(
        eng.metrics.snapshot())


def test_a_tight_pool_preempts_and_resumes_to_the_same_tokens(model, ample):
    eng, out = serve(model, 5)
    assert eng.metrics.counters["preemptions"] > 0
    conftest.assert_replay_identical(out, ample[1], len(REQUESTS))


def test_page_copy_export_and_import_on_a_latent_pool(ample):
    eng, _ = ample
    before = jax.tree.map(np.asarray, eng.pool)
    leaf = before["ckv"]
    assert leaf.shape[:2] == (3, 13) and leaf[:, 1:4].any()
    eng._copy_page(2, 9)
    assert (np.asarray(eng.pool["ckv"])[:, 9] == leaf[:, 2]).all()
    payload = eng._export_pages([1, 3])
    assert jax.tree.map(lambda a: a.shape, payload) == {
        "ckv": (3, 2, PAGE, 256)}
    eng._import_pages([10, 11], payload)
    after = np.asarray(eng.pool["ckv"])
    assert (after[:, 10] == leaf[:, 1]).all()
    assert (after[:, 11] == leaf[:, 3]).all()
    untouched = [p for p in range(13) if p not in (9, 10, 11)]
    assert (after[:, untouched] == leaf[:, untouched]).all()


def test_host_syncs_stay_flat_while_the_counters_move(model):
    """One request decoding alone: between control-plane changes (its one
    page growth) a dispatch uploads nothing, and the two counters still
    arrive with every token slab."""
    fc, pc, w = model
    eng = ServingEngine(w, pc, **fc["engine"])
    eng.submit(tokens_of(18, 21), 25)
    seen = []
    while eng.step():
        c = eng.metrics.counters
        seen.append((c["dispatches"], c["host_syncs"], c["moe_local_rows"]))
    quiet = [b for a, b in zip(seen, seen[1:])
             if b[0] == a[0] + 1 and b[1] == a[1]]
    assert len(quiet) >= 8                     # of 13 dispatches
    assert seen[-1][1] <= 3                    # admission, growth(s)
    rows = [s[2] for s in seen if s[0]]
    assert all(b >= a for a, b in zip(rows, rows[1:])) and rows[-1] > rows[0]


@pytest.mark.parametrize("option", [
    {"prefix_cache": True}, {"speculate": 2}, {"ffn": lambda h, p: h}])
def test_the_engine_refuses_what_the_family_lacks(model, option):
    fc, pc, w = model
    with pytest.raises(NotImplementedError, match="latent_moe"):
        ServingEngine(w, pc, **{**fc["engine"], **option})


def test_prefill_chunk_is_a_shape_for_this_family_too(model):
    """No family has another admission path for ``None`` to select: the
    engine's one check refuses it, by the option's name."""
    fc, pc, w = model
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingEngine(w, pc, **{**fc["engine"], "prefill_chunk": None})


@pytest.mark.parametrize("engine", ["ShardedServingEngine",
                                    "DisaggServingEngine",
                                    "DisaggShardedEngine"])
def test_the_other_engines_refuse_the_family(model, engine):
    import triton_dist_tpu.serving as serving
    fc, pc, w = model
    with pytest.raises(NotImplementedError, match="LatentMoEConfig"):
        getattr(serving, engine)(w, pc, None)
