"""The MiMo-V2-Flash class of ``models/window_moe.py`` (ISSUE 37: window layers
with a learned attention sink beside full layers of ANOTHER SHAPE, keys and
values of different widths, partial rotary at two thetas, a leading dense
layer outside the period, bias-selected experts) at a small size on the CPU,
seeded weights, float32, interpret-mode kernels. Every mechanism is kept: 8 / 4
KV heads, keys of 24 in a pool of 32 lanes, values of 16, a window of 32 over
pages of 16, sinks, 8 rotary dims, 16 experts with a selection bias.

- (a) chunked prefill then decode through the TWO pools against the
  benchmark's plain reference (``benchmark/references/sink_window_moe_lm.py``,
  imports nothing of the program), on logits, and every control of
  ``benchmark/tools/sink_control.py`` (the PROGRAM wrong in one thing) moves
  them far past the tolerance;
- (b) THE SHARES ADD UP: 16 experts over 8 shares, the routed parts of all
  shares = the uncut reference layer (the model has no common part);
- (c) the kernels against a dense softmax in ``numpy``: keys wider than
  values, a sink, both walks, with and without a window; at one width and
  without sinks the windowed kernels give the PARENT's result to the bit
  (``tests/fixtures/parent_pins_window.py``; the unwindowed ones are held by
  ``test_window_moe.py``);
- (d) a period of unequal shapes and a leading segment are ONE scanned body a
  segment;
- (e) through ``ServingEngine``: a sequence preempted mid-prefill or mid-decode
  replays its tokens across a ring wrap, the tokens are the reference's greedy
  ones, and what the family lacks is refused by name.
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
from benchmark.adapters.sink_window_engine import Adapter
from benchmark.references import sink_window_moe_lm as ref
from benchmark.tools.sink_control import CONTROLS
from triton_dist_tpu.models import window_moe as wm
from triton_dist_tpu.models.llama import (decode_step_paged,
                                          prefill_chunk_paged)
from triton_dist_tpu.ops.flash_decode import (gqa_decode_paged,
                                              gqa_prefill_paged)
from triton_dist_tpu.serving import ServingEngine

sys.path.insert(0, os.path.join(conftest.REPO_ROOT, "tests", "fixtures"))
import parent_pins  # noqa: E402
import parent_pins_window  # noqa: E402

PAGE, CHUNK, PPS = 16, 16, 12
TINY = os.path.join(conftest.REPO_ROOT, "benchmark", "tests",
                    "rehearsal_sink_window", "configs",
                    "tiny-sink-window.json")


def file_cfg(held=4, first=4):
    """A configuration FILE's keys at test size (what the adapter and the
    reference read): the benchmark's own tiny rehearsal file (window 32, page
    16, chunk 16: a ring of 4 pages), with the share asked for."""
    with open(TINY) as f:
        cfg = json.load(f)
    cfg.update(n_routed_experts=held, share={"first_expert": first})
    return cfg


@pytest.fixture(scope="module")
def model():
    """(file config, program config, weights): a share of 4 of 16 experts."""
    fc = file_cfg()
    w = jax.jit(lambda k: ref.init_weights(k, fc))(jax.random.PRNGKey(3))
    return fc, Adapter(fc)._program_config(), w


def tokens_of(n, seed=5):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 1,
                                         256), np.int32)


def table(pc, slot, first_page=3):
    """A slot's block-table row: PPS ledger pages, then its ring's first."""
    return jnp.asarray(list(range(first_page, first_page + PPS))
                       + [1 + slot * pc.ring_pages(PAGE)], jnp.int32)


_PROGRAMS = {}


def serve(w, pc, toks, n_pre):
    """``n_pre`` prompt tokens in chunks (the last padded) into slot 1's ring,
    then teacher-forced decode steps between two parked rows: (first token,
    decode logits, counters of each step, the pool)."""
    pool = pc.paged.init_pool(pc, 3 + PPS, PAGE)
    bt = table(pc, 1)
    # ONE traced pair of programs a configuration (the weights, the table and
    # the prompt's length are arguments): the controls that only re-weigh
    # share the sound run's
    chunk, step = _PROGRAMS.setdefault(pc, (
        jax.jit(lambda w, t, s, n, pg, bt: prefill_chunk_paged(
            w, t, s, n, pc, pg, bt)),
        jax.jit(lambda w, t, pos, pg, tables: decode_step_paged(
            w, t, pos, pc, pg, tables,
            active=jnp.asarray([False, True, False]), counters=True))))
    for start in range(0, n_pre, CHUNK):
        part = np.zeros(CHUNK, np.int32)
        real = toks[start:min(start + CHUNK, n_pre)]
        part[:len(real)] = real
        tok, pool = chunk(w, jnp.asarray(part), jnp.int32(start),
                          jnp.int32(n_pre), pool, bt)
    parked = jnp.zeros(PPS + 1, jnp.int32)
    tables = jnp.stack([parked, bt, parked])
    got, counts = [], []
    for i in range(n_pre, len(toks)):
        logits, pool, c = step(w, jnp.asarray([0, toks[i], 0]),
                               jnp.asarray([0, i, 0]), pool, tables)
        got.append(np.asarray(logits[1]))
        counts.append([int(x) for x in c])
    return int(tok), np.stack(got), np.asarray(counts), pool


# -- (a) against the reference, on logits ------------------------------------------

N_PRE, N_ALL = 141, 160


@pytest.fixture(scope="module")
def wanted(model):
    """160 tokens and the reference's logits of them."""
    fc, _, w = model
    toks = tokens_of(N_ALL)
    return toks, np.asarray(ref.logits(w, toks, fc))


@pytest.fixture(scope="module")
def programs(model, wanted):
    """The sound configuration's pair of programs, compiled by two chunks and
    a decode step: a fixture of their own, because the suite's watchdog
    counts a fixture's wall and ``served`` is a minute of interpreter
    without them."""
    _, pc, w = model
    serve(w, pc, wanted[0][:CHUNK + 2], CHUNK + 1)
    return _PROGRAMS[pc]


@pytest.fixture(scope="module")
def served(model, wanted, programs):
    """141 prompt tokens in nine chunks (the last padded) into a ring of 4
    pages of 16 (it wraps twice), then 19 decode steps, beside the
    reference's logits of the same 160 tokens."""
    _, pc, w = model
    toks, want = wanted
    tok, got, counts, pool = serve(w, pc, toks, N_PRE)
    return {"tok": tok, "got": got, "counts": counts, "pool": pool,
            "toks": toks, "want": want}


def test_chunks_then_decode_through_both_pools_match_the_reference(served):
    want = served["want"]
    assert served["tok"] == want[N_PRE - 1].argmax()
    assert np.abs(served["got"] - want[N_PRE:]).max() < 2e-5
    assert np.abs(want).max() > 0.3          # against logits of this size


@pytest.fixture(scope="module")
def small():
    """The class at THREE layers (a leading dense full layer, then one period
    of a window layer and a full one): every mechanism a control alters is
    there, and a pair of programs compiles in a third of the seven layers'
    time. (program config, weights, 66 tokens, the reference's logits of
    them); the sound programs' five decode steps after a 61-token prompt
    match the reference like the seven layers' do."""
    fc = file_cfg()
    fc.update(num_hidden_layers=3, hybrid_layer_pattern=[0, 1, 0],
              moe_layer_freq=[0, 1, 1])
    w = jax.jit(lambda k: ref.init_weights(k, fc))(jax.random.PRNGKey(3))
    pc = Adapter(fc)._program_config()
    assert (pc.n_dense_layers, pc.layer_kinds) == (1, ("window", "full"))
    toks = tokens_of(66)
    want = np.asarray(ref.logits(w, toks, fc))
    _, got, _, _ = serve(w, pc, toks, 61)
    assert np.abs(got - want[61:]).max() < 2e-5
    assert np.abs(want).max() > 0.3
    return pc, w, toks, want


@pytest.mark.parametrize("control", [c for c in CONTROLS if c != "none"])
def test_the_program_wrong_in_one_thing_moves_the_logits(small, control):
    """Each control of ``benchmark/tools/sink_control.py``: the sink dropped,
    the window a page wider, the value scale dropped, RoPE over the whole
    head, one theta for both kinds, the selection bias ignored, the full
    layers grouped as under 8 KV heads. A comparison that holds the mechanism
    reads a difference hundreds of times its tolerance (61 prompt tokens in
    four chunks: past the window and the page beyond it; then 5 decode
    steps), at three layers already (the least: the ignored bias, 8e-3)."""
    pc, w, toks, want = small
    alter, reweigh = CONTROLS[control]
    if alter:
        pc = alter(pc, PAGE)
    if reweigh:
        w = reweigh(pc, w)
    _, got, _, _ = serve(w, pc, toks, 61)
    assert np.abs(got - want[61:]).max() > 2e-3, control


def test_the_walk_counters_count_live_rows_only(model, served):
    """One live row between two parked ones: each decode step attends
    min(context, 32) keys in each of 5 window layers and its whole context in
    each of 2 full layers, and routes 4 picks in each of 6 sparse layers."""
    fc, pc, _ = model
    names = list(pc.paged.counters)
    c = {n: served["counts"][:, names.index(n)] for n in names}
    ctx = np.arange(N_PRE, N_ALL) + 1
    assert (c["attn_window_keys"] == 5 * 32).all()
    assert (c["attn_full_keys"] == 2 * ctx).all()
    assert (c["moe_local_rows"] <= 6 * 4).all() and c["moe_local_rows"].sum()
    assert (c["moe_experts_touched"] <= c["moe_local_rows"]).all()


def test_each_kind_holds_its_own_pool(model, served):
    """Two full layers (the dense one and the period's) of 4 KV heads hold the
    context in the ledger's pages; five window layers of 8 hold slot 1's ring
    and no more; keys lie in 32 lanes of which the last 8 are zeros."""
    fc, pc, _ = model
    pool, ring = served["pool"], pc.ring_pages(PAGE)
    assert ring == -(-(32 + 16 - 1) // PAGE) + 1 == 4
    assert pool["k"].shape == (2, 3 + PPS, 4, PAGE, 32)
    assert pool["v"].shape == (2, 3 + PPS, 4, PAGE, 16)
    assert pool["wk"].shape == (5, 1 + 3 * ring, 8, PAGE, 32)
    assert pool["wv"].shape == (5, 1 + 3 * ring, 8, PAGE, 16)
    for name in ("k", "wk"):
        leaf = np.asarray(pool[name])
        assert np.abs(leaf[..., :24]).sum() > 0 and not leaf[..., 24:].any()
    touched = np.abs(np.asarray(pool["wk"])).sum(axis=(0, 2, 3, 4)) > 0
    assert touched[1 + ring:1 + 2 * ring].all()
    assert not touched[1:1 + ring].any() and not touched[1 + 2 * ring:].any()
    full = np.abs(np.asarray(pool["k"])).sum(axis=(2, 3, 4)) > 0
    assert full[:, 3:3 + 10].all() and (full.sum(axis=1) == 10 + 1).all()


# -- (b) the shares add up ------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """16 experts, 8 shares of 2: the routed parts of every share, each chosen
    under the selection bias and weighed without it, add up to the
    reference's sparse FFN with all 16 held (no shared expert: nothing is
    common to the chips). Some picks differ from the unbiased choice (k = 4 of
    16: fewer than at 8 of 256): the bias is not idle."""
    whole = file_cfg(held=16, first=0)
    w = jax.jit(lambda k: ref.init_weights(k, whole))(jax.random.PRNGKey(3))
    z = ref.sizes(whole)
    h = jax.random.normal(jax.random.PRNGKey(9), (48, z["D"]), jnp.float32)
    layer = 4                   # the period's full layer: blocks["full"][0]
    p = {n: a[0] for n, a in w["blocks"]["full"].items()}
    tables = tuple(w["blocks"][n] for n in ("we_gate", "we_up", "we_down"))
    want = np.asarray(ref.routed_part(h, p["w_router"], p["router_bias"],
                                      tables, layer, z, None))
    ids, _, scores = ref.route(h, p["w_router"], p["router_bias"], z)
    plain = np.asarray(jax.lax.top_k(scores, z["k"])[1])
    moved = np.mean([len(set(a) - set(b)) for a, b in
                     zip(np.asarray(ids), plain)]) / z["k"]
    assert 0.02 < moved < 0.5, moved
    total, rows = 0.0, 0
    for first in range(0, 16, 2):
        pc = Adapter(file_cfg(held=2, first=first))._program_config()
        mine = tuple(t[:, first:first + 2] for t in tables)
        out, counts = wm.sparse_ffn(pc, p, h, layer + 1, None, tables=mine)
        total = total + np.asarray(out)
        rows += int(counts["moe_local_rows"])
    assert rows == 48 * whole["num_experts_per_tok"]   # every pick, once
    np.testing.assert_allclose(total, want, atol=5e-6, rtol=1e-4)


# -- (c) the kernels ----------------------------------------------------------------

HQ, HKV, DK, DV, W = 8, 2, 32, 16, 20


def dense_softmax(q, keys, vals, kv_len, window, sinks):
    """Row r attends keys max(0, n - window) .. n - 1 (n = ``kv_len[r]``);
    with ``sinks`` one more logit a head joins the softmax and is dropped."""
    out = np.zeros(q.shape[:2] + (vals.shape[-1],), np.float32)
    G = q.shape[1] // keys.shape[1]
    for r, n in enumerate(int(x) for x in kv_len):
        lo = max(0, n - window) if window else 0
        for h in range(q.shape[1]):
            if n == 0:
                continue
            k, v = keys[lo:n, h // G], vals[lo:n, h // G]
            s = (k @ q[r, h]) / np.sqrt(q.shape[2])
            if sinks is not None:
                s = np.append(s, sinks[h])
            p = np.exp(s - s.max())
            p = p / p.sum()
            out[r, h] = (p[:len(k)] if sinks is not None else p) @ v
    return out


@pytest.fixture(scope="module")
def kv():
    rng = np.random.default_rng(0)
    return (rng.standard_normal((130, HKV, DK)).astype(np.float32),
            rng.standard_normal((130, HKV, DV)).astype(np.float32),
            (2.0 * rng.standard_normal(HQ)).astype(np.float32))


def paged(kv, lens, ring):
    """Every row's keys in pages of its own; with ``ring`` position p lands in
    page (p // PAGE) % ring and later keys overwrite the oldest."""
    keys, vals, _ = kv
    cols = ring or -(-max(lens) // PAGE)
    P = 1 + len(lens) * cols
    kp = np.zeros((P, HKV, PAGE, DK), np.float32)
    vp = np.zeros((P, HKV, PAGE, DV), np.float32)
    bt = np.zeros((len(lens), cols), np.int32)
    for b, n in enumerate(lens):
        bt[b] = 1 + b * cols + np.arange(cols)
        for pos in range(n):
            page = bt[b, (pos // PAGE) % cols]
            kp[page, :, pos % PAGE] = keys[pos]
            vp[page, :, pos % PAGE] = vals[pos]
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt)


# kv_len 0 (idle), inside the first page, at / just past the window, deep
DECODE_LENS = [0, 5, 20, 21, 64, 113]


@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("window", [None, W], ids=["full", "window"])
def test_decode_rows_at_two_widths_match_a_dense_softmax(kv, window, sink):
    kp, vp, bt = paged(kv, DECODE_LENS, 4 if window else None)
    q = np.random.default_rng(1).standard_normal(
        (len(DECODE_LENS), HQ, DK)).astype(np.float32)
    sinks = kv[2] if sink else None
    out, lse = gqa_decode_paged(
        jnp.asarray(q), kp, vp, bt, jnp.asarray(DECODE_LENS), window=window,
        sinks=None if sinks is None else jnp.asarray(sinks))
    assert out.shape == (len(DECODE_LENS), HQ, DV)
    np.testing.assert_allclose(
        np.asarray(out), dense_softmax(q, kv[0], kv[1], DECODE_LENS, window,
                                       sinks), atol=2e-6, rtol=1e-5)
    assert np.asarray(lse)[0].max() < -1e29          # the idle row: empty


@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("window", [None, W], ids=["full", "window"])
@pytest.mark.parametrize("start,valid", [(0, 16), (50, 16), (96, 13)])
def test_a_chunk_at_two_widths_matches_a_dense_softmax(kv, start, valid,
                                                       window, sink):
    """16 rows at positions ``start ..`` (the last ``16 - valid`` padded),
    their keys written before the walk; under a window over a ring of 4 pages
    = ceil((20 + 16 - 1) / 16) + 1; two row blocks of 8."""
    C = 16
    kp, vp, bt = paged(kv, [start + valid], 4 if window else None)
    kv_len = np.where(np.arange(C) < valid, start + np.arange(C) + 1, 0)
    q = np.random.default_rng(2).standard_normal((C, HQ, DK)).astype(
        np.float32)
    sinks = kv[2] if sink else None
    out = gqa_prefill_paged(
        jnp.asarray(q), kp, vp, bt[0], jnp.asarray(kv_len), window=window,
        rows_per_block=8, sinks=None if sinks is None else jnp.asarray(sinks))
    assert out.shape == (C, HQ, DV)
    np.testing.assert_allclose(
        np.asarray(out), dense_softmax(q, kv[0], kv[1], kv_len, window,
                                       sinks), atol=2e-6, rtol=1e-5)


def test_a_sink_far_below_every_score_is_no_sink(kv):
    """exp(sink - m) underflows to zero: the result is the sinkless kernel's
    up to the order of one addition (the running maximum started elsewhere)."""
    kp, vp, bt = paged(kv, DECODE_LENS, 4)
    q = jnp.asarray(np.random.default_rng(3).standard_normal(
        (len(DECODE_LENS), HQ, DK)).astype(np.float32))
    lens = jnp.asarray(DECODE_LENS)
    a, _ = gqa_decode_paged(q, kp, vp, bt, lens, window=W,
                            sinks=jnp.full((HQ,), -200.0))
    b, _ = gqa_decode_paged(q, kp, vp, bt, lens, window=W)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


PINS = np.load(parent_pins_window.FILE)


@pytest.mark.parametrize("name", PINS.files)
def test_at_one_width_without_sinks_the_parent_s_result_to_the_bit(
        name, monkeypatch):
    """The windowed kernels with ``Dk == Dv`` and ``sinks=None``: pins taken
    on the parent commit. Bitwise where this machine computes as the pinning
    one did (``parent_pins.canary``), to 1e-5 elsewhere. The pinned tree made
    one online-softmax update a page: so does the chunk walk's loop (ISSUE
    41) with a group of one page. Its INTERIOR pages take that update
    without the mask: the same float operations (``tests/test_flash_decode.py``
    holds the loop to the grid bitwise, and on the chip the probe's hashes
    are the parent's), but at this case's shapes XLA's CPU backend contracts
    ``s * scale - m`` into one fused multiply-add once no ``select`` stands
    between the two, so the chunk's pin is held by the 1e-5 branch."""
    from triton_dist_tpu.ops import flash_decode
    monkeypatch.setattr(flash_decode, "PREFILL_PAGES_PER_GROUP", 1)
    got = parent_pins_window.windowed()[name]
    same = np.array_equal(parent_pins.canary()["canary"],
                          np.load(parent_pins.FILE)["canary"])
    if same and name != "window_prefill_out":
        assert np.array_equal(got, PINS[name]), name
    else:
        np.testing.assert_allclose(got, PINS[name], atol=1e-5, rtol=1e-5)


def test_a_ring_too_short_names_both_widths(kv):
    kp, vp, bt = paged(kv, [30], 2)
    with pytest.raises(AssertionError, match="32 and 16 wide"):
        gqa_decode_paged(jnp.zeros((1, HQ, DK), jnp.float32), kp, vp, bt,
                         jnp.asarray([30]), window=W)


# -- (d) one scanned body a segment --------------------------------------------------

def scans(jaxpr, out):
    """Lengths of the ``scan``s of a jaxpr, Pallas kernels' own left out."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name == "scan":
            out.append(eqn.params["length"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            scans(sub, out)
    return out


def test_unequal_shapes_and_a_leading_segment_scan_one_body_each():
    """13 layers = the dense layer + two periods of six whose kinds differ in
    shape: the chunk program scans a body of one layer once and a body of six
    layers twice, and nothing else; the period's slices of the two stacks are
    [2, 5, ...] and [2, 1, ...] views."""
    cfg = wm.bind(wm.WindowMoEConfig.tiny_sink(n_layers=13), 2, CHUNK)
    params = jax.eval_shape(lambda k: wm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert params["blocks"]["window"]["wk"].shape == (10, 64, 8 * 24)
    assert params["blocks"]["full"]["wk"].shape == (2, 64, 4 * 24)
    assert params["dense"]["wk"].shape == (1, 64, 4 * 24)
    pool = jax.eval_shape(lambda: cfg.paged.init_pool(cfg, 6, PAGE))
    assert pool["k"].shape[0] == 3 and pool["wk"].shape[0] == 10
    i32 = jax.ShapeDtypeStruct((), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, t, s, n, pg, bt: prefill_chunk_paged(
        p, t, s, n, cfg, pg, bt))(
        params, jax.ShapeDtypeStruct((CHUNK,), jnp.int32), i32, i32, pool,
        jax.ShapeDtypeStruct((5,), jnp.int32))
    assert scans(jaxpr.jaxpr, []) == [1, 2]


# -- (e) through the engine -----------------------------------------------------------

@pytest.fixture(scope="module")
def replay_engine(model):
    """Three requests (contexts to 71 tokens: past the 64 a ring holds) and
    ONE engine of two slots to put them through, undisturbed (``replay_golden``)
    and then with the oldest request preempted in the middle of its prefill
    and a decoding one preempted later (``replay``): a fixture each, because
    a run is a minute of interpreter and the suite's watchdog counts a
    fixture's wall."""
    fc, pc, w = model
    rng = np.random.default_rng(7)
    reqs = [(rng.integers(1, 256, n), m) for n, m in
            ((66, 5), (20, 6), (37, 4))]
    eng, twin = (ServingEngine(
        w, dataclasses.replace(pc, ring_slots=0, ring_chunk=0), num_slots=2,
        page_size=PAGE, num_pages=30, pages_per_seq=PPS, prefill_chunk=CHUNK,
        decode_horizon=2) for _ in range(2))
    # both programs compile HERE, through a twin of the same shape (two chunks
    # and a decode dispatch), so that the golden run's fixture is the
    # interpreter's minute alone under the watchdog
    assert twin._step is eng._step and twin._chunk_step is eng._chunk_step
    twin.submit(reqs[1][0][:CHUNK + 1], 2)
    while twin.step():
        pass
    seen = {}

    def run(disturb):
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

    return eng, reqs, run, seen


@pytest.fixture(scope="module")
def replay_golden(replay_engine):
    return replay_engine[2](False)


@pytest.fixture(scope="module")
def replay(replay_engine, replay_golden):
    eng, reqs, run, seen = replay_engine
    return eng, reqs, replay_golden, run(True), seen


def test_a_preempted_sequence_replays_its_tokens(replay):
    eng, _, golden, again, seen = replay
    assert seen == {"mid_prefill": (0, 0), "mid_decode": True}
    assert eng.metrics.counters["preemptions"] == 2
    conftest.assert_replay_identical(again, golden, 3)
    assert len({tuple(t) for t in golden.values()}) == 3


def test_the_engine_s_tokens_are_the_reference_s_greedy_ones(model, replay):
    """Teacher-forced on what the engine served, the reference puts the
    served token first at every position (its gap is zero)."""
    fc, _, w = model
    _, reqs, golden, _, _ = replay
    for i, (prompt, n) in enumerate(reqs):
        seq = np.concatenate([prompt, golden[i][:-1]]).astype(np.int32)
        rows = np.asarray(ref.logits(w, seq, fc))[len(prompt) - 1:]
        assert len(golden[i]) == n
        assert rows.argmax(axis=-1).tolist() == golden[i], i


def test_the_engine_sizes_the_rings_and_counts_pages_by_kind(replay):
    eng = replay[0]
    ring = eng.cfg.ring_pages(PAGE)
    assert (eng.cfg.ring_slots, eng.cfg.ring_chunk) == (2, CHUNK)
    assert eng._bt.shape == (2, PPS + 1)
    assert eng.pool["wk"].shape[:3] == (5, 1 + 2 * ring, 8)
    assert eng.pool["k"].shape[:3] == (2, 31, 4)
    full = eng.metrics.hist["kv_pages_full"].total
    held = eng.metrics.hist["kv_pages_window"].total
    assert 0 < held < full            # a context past the ring was served
    c = eng.metrics.counters
    assert c["attn_window_keys"] > 0 and c["attn_full_keys"] > 0
    assert c["moe_local_rows"] > 0


@pytest.mark.parametrize("option", [{"prefix_cache": True},
                                    {"speculate": 2},
                                    {"ffn": lambda h, p: h}])
def test_what_the_sink_window_family_lacks_is_refused_by_name(model, option):
    fc, pc, w = model
    with pytest.raises(NotImplementedError, match="sink_window_moe"):
        ServingEngine(w, pc, num_slots=2, page_size=PAGE, num_pages=20,
                      pages_per_seq=PPS, prefill_chunk=CHUNK, **option)


def test_the_tiny_sink_preset_serves():
    cfg = wm.bind(wm.WindowMoEConfig.tiny_sink(), 2, CHUNK)
    assert cfg.paged is wm.SINK_WINDOW_MOE
    assert wm.WindowMoEConfig.tiny().paged is wm.WINDOW_MOE
    params = wm.init_params(jax.random.PRNGKey(0), cfg)
    assert params["lm_head"].shape == (64, 256)
    assert params["blocks"]["window"]["sinks"].shape == (5, 8)
    assert "sinks" not in params["blocks"]["full"]
    pool = cfg.paged.init_pool(cfg, 6, PAGE)
    bt = jnp.asarray([1, 2, 3, 4, 1], jnp.int32)
    toks = jnp.asarray(np.arange(CHUNK) + 1, jnp.int32)
    tok, pool = prefill_chunk_paged(params, toks, jnp.int32(0),
                                    jnp.int32(CHUNK), cfg, pool, bt)
    assert 0 <= int(tok) < cfg.vocab_size
