"""The window-and-full-attention, parallel-block, shared-plus-routed-expert
family (ISSUE 30: ``models/window_moe.py``, the ``window=`` of the paged GQA
kernels, the engine's per-slot rings) at a small size on the CPU, seeded
weights, float32, interpret-mode kernels:

- (a) chunked prefill then decode through the TWO pools against the
  benchmark's plain reference (``benchmark/references/window_moe_lm.py``,
  imports nothing of the program), on logits, at contexts that cross the
  window five times and wrap the ring three times;
- (b) THE SHARES ADD UP: 16 experts over 8 shares, the routed parts of all
  shares plus the shared experts once = the uncut reference layer;
- (c) the windowed kernels against a dense masked softmax: ``kv_len`` below,
  at and just past the window, a bound inside a page, idle rows;
- (d) with ``window=None`` both kernels and ``jit_step`` / ``jit_chunk`` of the
  dense and the latent tiny presets give the PARENT's result (pins taken on
  commit 6bf7760 by ``tests/fixtures/parent_pins.py``; the latent decode
  rows' loop, ISSUE 31, at one page an update: a larger group reorders the
  float32 sums and nothing else);
- (e) a window layer never holds more than its ring, a ring that another
  sequence filled is never attended, and through ``ServingEngine`` a sequence
  preempted mid-prefill or mid-decode replays its tokens; what the family
  lacks is refused by name.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conftest  # noqa: F401  (forces the CPU platform)
from benchmark.adapters.window_engine import Adapter
from benchmark.references import window_moe_lm as ref
from triton_dist_tpu.models import window_moe as wm
from triton_dist_tpu.models.llama import (decode_step_paged,
                                          prefill_chunk_paged)
from triton_dist_tpu.ops import flash_decode, mla_decode
from triton_dist_tpu.ops.flash_decode import (gqa_decode_paged,
                                              gqa_prefill_paged)
from triton_dist_tpu.serving import ServingEngine

sys.path.insert(0, os.path.join(conftest.REPO_ROOT, "tests", "fixtures"))
import parent_pins  # noqa: E402

PAGE, CHUNK, PPS = 8, 16, 20
TINY = os.path.join(conftest.REPO_ROOT, "benchmark", "tests",
                    "rehearsal_window", "configs", "tiny-window.json")


def file_cfg(held=4, first=4):
    """A configuration FILE's keys at test size (what the adapter and the
    reference read): the benchmark's own tiny rehearsal file (window 32, page
    8, chunk 16: a ring of 7 pages), in float32, with the share asked for."""
    with open(TINY) as f:
        cfg = json.load(f)
    cfg.update(num_experts=held, share={"first_expert": first},
               torch_dtype="float32")
    return cfg


def weights_of(fc, seed=3):
    """The reference's seeded weights with the layers' matrices 6x larger:
    at a hidden size of 64 a std of 0.02 leaves every layer's output far
    under the embedding, and a tied head then only repeats its input."""
    w = jax.jit(lambda k: ref.init_weights(k, fc))(jax.random.PRNGKey(seed))
    blocks = {n: a * 6.0 if a.ndim > 2 else a
              for n, a in w["blocks"].items()}
    return {**w, "blocks": blocks}


@pytest.fixture(scope="module")
def model():
    """(file config, program config, weights): a share of 4 of 16 experts."""
    fc = file_cfg()
    return fc, Adapter(fc)._program_config(), weights_of(fc)


def tokens_of(n, seed=5):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 1,
                                         256), np.int32)


def table(pc, slot, first_page=3):
    """A slot's block-table row: PPS ledger pages, then its ring's first."""
    return jnp.asarray(list(range(first_page, first_page + PPS))
                       + [1 + slot * pc.ring_pages(PAGE)], jnp.int32)


_CHUNK_PROGRAMS = {}


def prefill(w, pc, pool, bt, toks, n_pre):
    # ONE traced chunk program a configuration: the weights, the table and
    # the prompt's length are its arguments (a program a call re-traced and
    # re-compiled it three times in one test)
    chunk = _CHUNK_PROGRAMS.setdefault(pc, jax.jit(
        lambda w, t, s, n, pg, bt: prefill_chunk_paged(w, t, s, n, pc, pg,
                                                       bt)))
    for start in range(0, n_pre, CHUNK):
        part = np.zeros(CHUNK, np.int32)
        real = toks[start:min(start + CHUNK, n_pre)]
        part[:len(real)] = real
        tok, pool = chunk(w, jnp.asarray(part), jnp.int32(start),
                          jnp.int32(n_pre), pool, bt)
    return tok, pool


# -- (a) against the reference, on logits ------------------------------------------

@pytest.fixture(scope="module")
def served(model):
    """141 prompt tokens in nine chunks (the last padded) into slot 1's ring
    (7 pages of 8: it wraps three times), then 19 teacher-forced decode steps
    between two parked rows; logits of the decode steps, and the reference's."""
    fc, pc, w = model
    toks = tokens_of(160)
    want = np.asarray(ref.logits(w, toks, fc))
    pool = pc.paged.init_pool(pc, 3 + PPS, PAGE)
    bt, n_pre = table(pc, 1), 141
    tok, pool = prefill(w, pc, pool, bt, toks, n_pre)
    parked = jnp.zeros(PPS + 1, jnp.int32)
    step = jax.jit(lambda t, pos, pg: decode_step_paged(
        w, t, pos, pc, pg, jnp.stack([parked, bt, parked]),
        active=jnp.asarray([False, True, False]), counters=True))
    got, counts = [], []
    for i in range(n_pre, 160):
        logits, pool, c = step(jnp.asarray([0, toks[i], 0]),
                               jnp.asarray([0, i, 0]), pool)
        got.append(np.asarray(logits[1]))
        counts.append([int(x) for x in c])
    return {"tok": int(tok), "got": np.stack(got), "want": want,
            "counts": np.asarray(counts), "pool": pool, "n_pre": n_pre}


def test_chunks_then_decode_through_both_pools_match_the_reference(served):
    """float32 program against the float32 reference: what is left is the
    order of summation (an online softmax a page at a time, grouped GEMMs,
    the shared experts as one FFN), 1e-7 here. atol 5e-6 on logits of order
    0.4 is 50x that and 200x under what bfloat16 activations give (1e-3:
    next test)."""
    n_pre = served["n_pre"]
    assert served["tok"] == int(served["want"][n_pre - 1].argmax())
    np.testing.assert_allclose(served["got"], served["want"][n_pre:160],
                               atol=5e-6, rtol=1e-4)
    # the tied head does more than repeat its input at these weights
    assert len(set(served["want"][n_pre:160].argmax(-1))) > 5


def test_the_tolerance_fails_bfloat16_activations(model, served):
    """The same weights and tokens with bfloat16 activations miss the
    tolerance of the test above by orders of magnitude."""
    fc, pc, w = model
    low = dataclasses.replace(pc, dtype=jnp.bfloat16)
    wl = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                      if a.ndim > 1 and a.shape[-1] != 16 else a, w)
    toks, n_pre = tokens_of(160), 40
    pool = low.paged.init_pool(low, 3 + PPS, PAGE)
    bt = table(low, 0)
    _, pool = prefill(wl, low, pool, bt, toks, n_pre)
    logits, _ = decode_step_paged(wl, jnp.asarray([toks[n_pre]]),
                                  jnp.asarray([n_pre]), low, pool, bt[None])
    err = np.abs(np.asarray(logits[0]) - served["want"][n_pre]).max()
    assert err > 5e-4, err


def test_the_walk_counters_count_live_rows_only(model, served):
    """One live row between two parked ones: a window layer attends
    min(context, window) keys, the full layer the context; 3 and 1 layers."""
    fc, pc, _ = model
    n_pre = served["n_pre"]
    names = pc.paged.counters
    assert names == ("moe_local_rows", "moe_experts_touched",
                     "attn_window_keys", "attn_full_keys")
    c = served["counts"]
    kv = np.arange(n_pre, 160) + 1
    assert (c[:, 2] == 3 * np.minimum(kv, pc.window)).all()
    assert (c[:, 3] == kv).all()
    assert (c[:, 0] <= 4 * fc["num_experts_per_tok"]).all() and c[:, 0].sum()
    assert (c[:, 1] <= c[:, 0]).all()


# -- (b) the shares add up ------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """16 experts, 8 shares of 2: the routed part of every share + the shared
    experts' mean ONCE = the reference's layer FFN with all 16 held."""
    whole = file_cfg(held=16, first=0)
    w = weights_of(whole)
    z = ref.sizes(whole)
    u = jax.random.normal(jax.random.PRNGKey(9), (24, z["D"]), jnp.float32)
    layer = 2
    p = {n: a[layer] for n, a in w["blocks"].items()
         if not n.startswith("we_")}
    tables = tuple(w["blocks"][n] for n in ("we_gate", "we_up", "we_down"))
    shared = np.asarray(ref.shared_part(u, p, z, None))
    want = np.asarray(ref.routed_part(u, p["w_router"], tables, layer, z,
                                      None)) + shared
    total, rows = 0.0, 0
    for first in range(0, 16, 2):
        fc = file_cfg(held=2, first=first)
        pc = Adapter(fc)._program_config()
        mine = tuple(t[:, first:first + 2] for t in tables)
        out, counts = wm.sparse_ffn(pc, p, u, layer, None, tables=mine)
        total = total + (np.asarray(out) - shared)
        rows += int(counts["moe_local_rows"])
    assert rows == 24 * whole["num_experts_per_tok"]   # every pick, once
    np.testing.assert_allclose(total + shared, want, atol=5e-6, rtol=1e-4)


def test_the_shared_experts_as_one_ffn_are_their_mean(model):
    fc, pc, w = model
    z = ref.sizes(fc)
    u = jax.random.normal(jax.random.PRNGKey(4), (8, z["D"]), jnp.float32)
    p = {n: a[1] for n, a in w["blocks"].items() if not n.startswith("we_")}
    F = z["F"]
    each = [np.asarray(ref._swiglu(
        u, p["ws_gate"][:, j * F:(j + 1) * F], p["ws_up"][:, j * F:(j + 1) * F],
        p["ws_down"][j * F:(j + 1) * F], None)) for j in range(z["S"])]
    none_held = dataclasses.replace(pc, first_held_expert=10 ** 6)
    tables = tuple(w["blocks"][n] for n in ("we_gate", "we_up", "we_down"))
    out, counts = wm.sparse_ffn(none_held, p, u, 1, None, tables=tables)
    assert int(counts["moe_local_rows"]) == 0
    np.testing.assert_allclose(np.asarray(out), sum(each) / len(each),
                               atol=1e-6, rtol=1e-5)


# -- (c) the windowed kernels ----------------------------------------------------

HQ, HKV, D, W = 4, 2, 32, 20


def dense_window(q, keys, vals, kv_len, window):
    """Row r attends keys max(0, n - window) .. n - 1 of ``keys`` [S, Hkv, D]
    (n = ``kv_len[r]``): a plain softmax over the slice."""
    out = np.zeros(q.shape, np.float32)
    G = q.shape[1] // keys.shape[1]
    for r, n in enumerate(int(x) for x in kv_len):
        lo = max(0, n - window) if window else 0
        for h in range(q.shape[1]):
            if n == 0:
                continue
            k, v = keys[lo:n, h // G], vals[lo:n, h // G]
            s = (k @ q[r, h]) / np.sqrt(q.shape[2])
            p = np.exp(s - s.max())
            out[r, h] = (p / p.sum()) @ v
    return out


@pytest.fixture(scope="module")
def kv():
    rng = np.random.default_rng(0)
    return (rng.standard_normal((130, HKV, D)).astype(np.float32),
            rng.standard_normal((130, HKV, D)).astype(np.float32))


def ring_pool(kv, lens, ring):
    """Every row's keys written into a ring of its own, position p in ring
    page (p // PAGE) % ring: later keys overwrite the oldest."""
    keys, vals = kv
    P = 1 + len(lens) * ring
    kp = np.zeros((P, HKV, PAGE, D), np.float32)
    vp = np.zeros_like(kp)
    bt = np.zeros((len(lens), ring), np.int32)
    for b, n in enumerate(lens):
        bt[b] = 1 + b * ring + np.arange(ring)
        for pos in range(n):
            page = bt[b, (pos // PAGE) % ring]
            kp[page, :, pos % PAGE] = keys[pos]
            vp[page, :, pos % PAGE] = vals[pos]
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt)


# kv_len 0 (idle), inside the first page, just below / at / just past the
# window (20: the bound falls inside a page of 8), several windows deep
DECODE_LENS = [0, 5, 19, 20, 21, 27, 64, 113]


@pytest.mark.parametrize("ring", [4, 5, 9])
def test_windowed_decode_rows_match_a_dense_masked_softmax(kv, ring):
    kp, vp, bt = ring_pool(kv, DECODE_LENS, ring)
    q = np.random.default_rng(1).standard_normal(
        (len(DECODE_LENS), HQ, D)).astype(np.float32)
    out, lse = gqa_decode_paged(jnp.asarray(q), kp, vp, bt,
                                jnp.asarray(DECODE_LENS), window=W)
    np.testing.assert_allclose(np.asarray(out),
                               dense_window(q, *kv, DECODE_LENS, W),
                               atol=2e-6, rtol=1e-5)
    assert np.asarray(lse)[0].max() < -1e29          # the idle row: empty


@pytest.mark.parametrize("start,valid", [(0, 16), (7, 16), (50, 16),
                                         (96, 13), (19, 2)])
@pytest.mark.parametrize("rows", [4, 16])
def test_a_windowed_chunk_matches_a_dense_masked_softmax(kv, start, valid,
                                                         rows):
    """16 rows at positions ``start ..`` (the last ``16 - valid`` padded)
    over a ring of 6 pages = ceil((20 + 16 - 1) / 8) + 1: the chunk's keys are
    written before the walk, the oldest window starts 19 keys before the
    chunk's first row."""
    C, ring = 16, 6
    kp, vp, bt = ring_pool(kv, [start + valid], ring)
    kv_len = np.where(np.arange(C) < valid, start + np.arange(C) + 1, 0)
    q = np.random.default_rng(2).standard_normal((C, HQ, D)).astype(
        np.float32)
    out = gqa_prefill_paged(jnp.asarray(q), kp, vp, bt[0],
                            jnp.asarray(kv_len), window=W,
                            rows_per_block=rows)
    np.testing.assert_allclose(np.asarray(out),
                               dense_window(q, *kv, kv_len, W),
                               atol=2e-6, rtol=1e-5)


def test_a_window_no_context_reaches_is_the_unwindowed_walk(kv):
    """window >= kv_len and a table the context fits: the windowed kernel
    walks what the unwindowed one walks, in the same order: bitwise."""
    lens = [0, 5, 27, 64]
    kp, vp, bt = ring_pool(kv, lens, 10)
    q = jnp.asarray(np.random.default_rng(3).standard_normal(
        (4, HQ, D)).astype(np.float32))
    a, _ = gqa_decode_paged(q, kp, vp, bt, jnp.asarray(lens), window=72)
    b, _ = gqa_decode_paged(q, kp, vp, bt, jnp.asarray(lens))
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_a_ring_too_short_for_its_window_is_refused(kv):
    kp, vp, bt = ring_pool(kv, [30], 3)
    q = jnp.zeros((1, HQ, D), jnp.float32)
    with pytest.raises(AssertionError, match="cannot hold a window"):
        gqa_decode_paged(q, kp, vp, bt, jnp.asarray([30]), window=W)
    with pytest.raises(AssertionError, match="cannot hold a window"):
        gqa_prefill_paged(jnp.zeros((16, HQ, D), jnp.float32), kp, vp,
                          jnp.arange(5, dtype=jnp.int32),
                          jnp.arange(16) + 1, window=W)


# -- (d) window=None: the parent's results -----------------------------------------

PINS = np.load(parent_pins.FILE)


@pytest.fixture(scope="module")
def recomputed():
    cache = {}

    def get(case):
        if case not in cache:
            # the pinned tree made one online-softmax update a page: so do
            # the latent loop, a chunk's or the decode rows', and the K/V
            # chunk walk's loop (ISSUE 41) with a group of one page
            with pytest.MonkeyPatch.context() as patch:
                patch.setattr(mla_decode, "DECODE_PAGES_PER_GROUP", 1)
                patch.setattr(mla_decode, "CHUNK_PAGES_PER_GROUP", 1)
                patch.setattr(flash_decode, "PREFILL_PAGES_PER_GROUP", 1)
                cache[case] = parent_pins.CASES[case]()
        return cache[case]
    return get


def pinned(name):
    if name.startswith(("decode_", "prefill_")):
        return "kernels"
    return name.split("_")[0]


@pytest.mark.parametrize("name", [n for n in PINS.files if n != "canary"])
def test_without_a_window_the_parent_s_result_to_the_bit(recomputed, name):
    """Pins taken on the parent commit. Where this machine computes a chain
    of float operations the repo does not own as the pinning machine did,
    equality is to the bit; elsewhere the same arrays are held to 1e-5 and
    the tokens to equality."""
    got = recomputed(pinned(name))[name]
    if np.array_equal(recomputed("canary")["canary"], PINS["canary"]):
        assert np.array_equal(got, PINS[name]), name
    elif got.dtype.kind == "i":
        assert np.array_equal(got, PINS[name]), name
    else:
        np.testing.assert_allclose(got, PINS[name], atol=1e-5, rtol=1e-5)


# -- (e) rings ----------------------------------------------------------------------

def test_a_window_layer_holds_its_ring_and_no_more(model, served):
    """After 160 tokens through slot 1: the window layers' pool is the
    scratch page + a ring a slot (sized by the adapter for 3 slots), slot 1's
    ring is written all over, slots 0's and 2's hold nothing; the full layer
    holds the context, 20 pages."""
    fc, pc, _ = model
    ring = pc.ring_pages(PAGE)
    assert ring == -(-(32 + 16 - 1) // PAGE) + 1 == 7
    wk = np.asarray(served["pool"]["wk"])
    assert wk.shape[:2] == (3, 1 + 3 * ring)
    touched = np.abs(wk).sum(axis=(0, 2, 3, 4)) > 0
    assert touched[1 + ring:1 + 2 * ring].all()
    assert not touched[1:1 + ring].any() and not touched[1 + 2 * ring:].any()
    full = np.abs(np.asarray(served["pool"]["k"])).sum(axis=(0, 2, 3, 4)) > 0
    assert full[3:3 + PPS].all() and full.sum() == PPS + 1   # + scratch


def test_a_ring_another_sequence_filled_is_never_attended(model):
    """Sequence B through a ring that sequence A (longer, other tokens)
    wrapped twice gives, bit for bit, B's logits through a fresh ring:
    every stale key lies outside B's window or beyond its ``kv_len``."""
    fc, pc, w = model
    a, b = tokens_of(110, seed=11), tokens_of(44, seed=12)

    step = jax.jit(lambda t, pos, pg: decode_step_paged(
        w, t, pos, pc, pg, table(pc, 2)[None]))

    def logits_of(pool):
        _, pool = prefill(w, pc, pool, table(pc, 2), b, 40)
        out = []
        for i in range(40, 44):
            lg, pool = step(jnp.asarray([b[i]]), jnp.asarray([i]), pool)
            out.append(np.asarray(lg[0]))
        return np.stack(out)

    fresh = pc.paged.init_pool(pc, 3 + PPS, PAGE)
    _, used = prefill(w, pc, fresh, table(pc, 2), a, 110)
    assert np.array_equal(logits_of(used), logits_of(fresh))


@pytest.fixture(scope="module")
def replay_engine(model):
    """Three requests (contexts to 75 tokens: past the 56 a ring holds) and
    ONE engine of two slots to put them through, undisturbed
    (``replay_golden``) and then with the oldest request preempted in the
    middle of its prefill and a decoding one preempted later (``replay``): a
    fixture each, because a run is most of a minute of interpreter and the
    suite's watchdog counts a fixture's wall."""
    fc, pc, w = model
    rng = np.random.default_rng(7)
    reqs = [(rng.integers(1, 256, n), m) for n, m in
            ((70, 6), (20, 8), (37, 6))]
    eng = ServingEngine(w, dataclasses.replace(pc, ring_slots=0, ring_chunk=0),
                        num_slots=2, page_size=PAGE, num_pages=30,
                        pages_per_seq=PPS, prefill_chunk=CHUNK,
                        decode_horizon=2)
    seen = {}

    def serve(disturb):
        rids = [eng.submit(prompt, n) for prompt, n in reqs]
        steps = 0
        while eng.step():
            steps += 1
            slots = list(enumerate(eng.sched.slots))
            if disturb and steps == 2:
                slot, req = next((s, r) for s, r in slots if r is not None
                                 and r.state.value == "prefilling"
                                 and r.prefill_cursor > 0)
                eng._preempt(slot)
                seen["mid_prefill"] = (req.prefill_cursor,
                                       len(eng.alloc.pages_of(req.rid)))
            if disturb and steps == 9:
                slot = next(s for s, r in slots if r is not None
                            and r.state.value == "active")
                eng._preempt(slot)
                seen["mid_decode"] = True
        done = {r.rid: list(r.generated) for r in eng._finished}
        return {i: done[rid] for i, rid in enumerate(rids)}

    return eng, serve, seen


@pytest.fixture(scope="module")
def replay_golden(replay_engine):
    return replay_engine[1](False)


@pytest.fixture(scope="module")
def replay(replay_engine, replay_golden):
    eng, serve, seen = replay_engine
    return eng, replay_golden, serve(True), seen


def test_a_preempted_sequence_replays_its_tokens(replay):
    """A victim leaves its ring behind with its slot: preempted in the middle
    of its prefill it keeps NO page and restarts at cursor 0 (a family without
    rings keeps its filled pages and resumes); the tokens are the undisturbed
    run's either way."""
    eng, golden, again, seen = replay
    assert seen == {"mid_prefill": (0, 0), "mid_decode": True}
    assert eng.metrics.counters["preemptions"] == 2
    conftest.assert_replay_identical(again, golden, 3)
    assert len({tuple(t) for t in golden.values()}) == 3


def test_the_engine_sizes_the_rings_and_counts_pages_by_kind(replay):
    eng = replay[0]
    ring = eng.cfg.ring_pages(PAGE)
    assert (eng.cfg.ring_slots, eng.cfg.ring_chunk) == (2, CHUNK)
    assert eng._bt.shape == (2, PPS + 1)
    assert eng.pool["wk"].shape[1] == 1 + 2 * ring
    full = eng.metrics.hist["kv_pages_full"].total
    held = eng.metrics.hist["kv_pages_window"].total
    assert 0 < held < full            # a context past the ring was served
    c = eng.metrics.counters
    assert c["attn_window_keys"] > 0 and c["attn_full_keys"] > 0
    assert c["moe_local_rows"] > 0
    # both kinds' chunk walks are counted, by the kind's layers and window
    walks = dict((w, n) for n, _, w in eng.cfg.paged.chunk_walks(eng.cfg))
    assert walks == {eng.cfg.window: eng.cfg.layers_of("window"),
                     None: eng.cfg.layers_of("full")}
    assert 0 < c["chunk_walk_edge_pages"] <= c["chunk_walk_pages"]


@pytest.fixture(scope="module")
def ring_victim(model):
    """``serve(horizon, spare, fence)``: a 15-token prompt decoding 5 tokens
    beside a 24-token prompt through a two-slot engine whose pool has
    ``spare`` pages free (None: all 29), every chunk fenced or not, as
    ``conftest.serve_noting_victims`` returns it. One trace of the programs
    a horizon serves every engine (they are of one shape); the roomy pool's
    tokens at K=1 are the golden."""
    fc, pc, w = model
    rng = np.random.default_rng(11)
    reqs = [(rng.integers(1, 256, 15), 5), (rng.integers(1, 256, 24), 2)]

    def serve(horizon, spare, fence=False):
        eng = ServingEngine(
            w, dataclasses.replace(pc, ring_slots=0, ring_chunk=0),
            num_slots=2, page_size=PAGE, num_pages=30, pages_per_seq=PPS,
            prefill_chunk=CHUNK, decode_horizon=horizon)
        if spare is not None:
            assert eng.alloc.alloc("ballast", eng.alloc.free_pages - spare)
        return conftest.serve_noting_victims(eng, reqs, fence)

    golden, _, _, roomy, _ = serve(1, spare=None)
    assert roomy["preemptions"] == 0
    return serve, golden


@pytest.mark.parametrize("horizon", [1, 4], ids=["k1", "k4"])
def test_a_ring_victim_whose_chunk_was_not_awaited(ring_victim, horizon):
    """With 5 pages to spare ``_grow`` preempts the prefilling slot while its
    chunk may still be running (ISSUE 36). The victim restarts (its ring
    stays with the slot); the tokens are the roomy pool's, and the digests
    those of a run which fences every chunk."""
    serve, golden = ring_victim
    tokens, digests, hit, counters, _ = serve(horizon, spare=5)
    assert hit and counters["preemptions"] == 1
    assert counters["prefill_chunks"] == 1 + 1 + 2      # the victim's again
    assert tokens == golden
    assert serve(horizon, spare=5, fence=True)[:2] == (tokens, digests)


@pytest.mark.parametrize("option", [{"prefix_cache": True},
                                    {"speculate": 2},
                                    {"ffn": lambda h, p: h}])
def test_what_the_window_family_lacks_is_refused_by_name(model, option):
    fc, pc, w = model
    with pytest.raises(NotImplementedError, match="window_moe"):
        ServingEngine(w, pc, num_slots=2, page_size=PAGE, num_pages=20,
                      pages_per_seq=PPS, prefill_chunk=CHUNK, **option)


def test_the_tiny_preset_serves():
    cfg = wm.bind(wm.WindowMoEConfig.tiny(), 2, CHUNK)
    params = wm.init_params(jax.random.PRNGKey(0), cfg)
    assert "lm_head" not in params
    pool = cfg.paged.init_pool(cfg, 6, PAGE)
    assert set(pool) == {"k", "v", "wk", "wv"}
    assert pool["k"].shape[0] == 1 and pool["wk"].shape[0] == 3
    bt = jnp.asarray([1, 2, 3, 4, 1], jnp.int32)
    toks = jnp.asarray(np.arange(CHUNK) + 1, jnp.int32)
    tok, pool = prefill_chunk_paged(params, toks, jnp.int32(0),
                                    jnp.int32(CHUNK), cfg, pool, bt)
    assert 0 <= int(tok) < cfg.vocab_size
