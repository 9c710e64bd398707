"""The linear-attention family (ISSUE 39: ``models/linear_attn_moe.py``,
``ops/gdn.py``, layers that hold a state and NO pages among layers that hold
pages and no state) at a small size on the CPU, seeded weights, interpret-mode
kernels: two periods of four layers, 16 experts top-3, a key head serving two
value heads, rotary on a quarter of the head.

- (a) ``gdn_decode_update`` against one step of the recurrence, and rows that
  are not live leave every state equal TO THE BIT;
- (b) the chunk's scan (chunked / WY form) against the token-by-token
  recurrence: from a non-zero state, a prompt that ends inside a block, a
  chunk boundary inside a prompt;
- (c) prefill in chunks, then decode, gives the LOGITS of the benchmark's
  plain reference's full forward pass
  (``benchmark/references/linear_attn_moe_lm.py``: imports nothing of the
  program, scans token by token): the programs at two periods, and through
  ``ServingEngine``'s cache (one period: an interpreter step costs 0.25 s a
  layer) while another slot decodes between the chunks;
- (d) THE SHARES ADD UP: 16 experts as 4 shares of 4, the routed parts of all
  shares plus the shared expert counted once = the uncut reference layer;
- (e) a new tenant of a slot starts from a zero state, a preempted request
  restarts and regenerates its tokens, and what a state forbids is refused by
  name;
- (f) the other families' tokens on fixed seeds are the parent's
  (``tests/fixtures/parent_pins_families.py``).
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
from benchmark.adapters.linear_attn_engine import Adapter
from benchmark.references import linear_attn_moe_lm as ref
from triton_dist_tpu.models import linear_attn_moe as lm
from triton_dist_tpu.models.llama import (decode_step_paged,
                                          prefill_chunk_paged)
from triton_dist_tpu.ops.gdn import (gdn_chunk_scan, gdn_decode_update,
                                     gdn_step_reference)
from triton_dist_tpu.serving import ServingEngine

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "fixtures"))
import parent_pins_families  # noqa: E402

PAGE, CHUNK, PPS = 8, 16, 12
TINY = os.path.join(conftest.REPO_ROOT, "benchmark", "tests",
                    "rehearsal_linear_attn", "configs",
                    "tiny-linear-attn.json")


def file_cfg(dtype="float32", held=16, first=0, layers=8):
    """A configuration FILE's keys at test size (what the adapter and the
    reference read): the benchmark's own tiny rehearsal file, with the share
    and the depth asked for."""
    with open(TINY) as f:
        cfg = json.load(f)
    cfg.update(torch_dtype=dtype, num_experts=held, num_hidden_layers=layers,
               share={"first_expert": first})
    # the conv rows are held in the activations' dtype
    cfg["cache"] = dict(cfg["cache"], state_bytes_per_slot_per_linear_layer=(
        4 * 16 * 16 * 4 + 3 * 128 * jnp.dtype(dtype).itemsize))
    return cfg


def weights_of(fc, seed=3):
    return jax.jit(lambda k: ref.init_weights(k, fc))(
        jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def model():
    """(file config, program config bound to 3 slots, weights), float32, all
    16 experts held."""
    fc = file_cfg()
    return fc, Adapter(fc)._program_config(), weights_of(fc)


@pytest.fixture(scope="module")
def decode3(model):
    """The decode step of three rows at two periods (8 layers), jitted once:
    (tokens, pos, pool, table rows, active) -> (logits, pool, counters)."""
    _, pc, w = model
    return jax.jit(lambda t, pos, pg, rows, active: decode_step_paged(
        w, t, pos, pc, pg, rows, active=active, counters=True))


def tokens_of(n, seed=5):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 1,
                                         256), np.int32)


# -- (a) the decode rows' update ---------------------------------------------------

def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


@pytest.mark.parametrize("live", [(True, False, True, True),
                                  (False, False, False, False),
                                  (True, True, True, True)])
def test_the_decode_update_is_one_step_and_idle_rows_move_nothing(live):
    """A key head serves two value heads; four heads a block of the kernel's
    loop, so a row is one item. float32: what is left is the order of the
    sums over the 16 keys (1e-6 of values of order 3)."""
    L, S, H, Hk, K, V, R = 2, 6, 4, 2, 16, 128, 4
    k = jax.random.split(jax.random.PRNGKey(1), 6)
    state = jax.random.normal(k[0], (L, S, H, K, V))
    slots = jnp.asarray([3, 5, 1, 4], jnp.int32)
    live = jnp.asarray(live)
    q = _l2(jax.random.normal(k[1], (R, Hk, K))) * K ** -0.5
    kk = _l2(jax.random.normal(k[2], (R, Hk, K)))
    v = jax.random.normal(k[3], (R, H, V))
    alpha, beta = (jax.nn.sigmoid(jax.random.normal(k[i], (R, H)))
                   for i in (4, 5))
    o, new = jax.jit(lambda s: gdn_decode_update(
        s, 1, slots, live, q, kk, v, alpha, beta))(state)
    o_want, s_want = gdn_step_reference(state[1][slots], q, kk, v, alpha,
                                        beta)
    want = np.asarray(state).copy()
    for r in range(R):
        if live[r]:
            want[1, int(slots[r])] = np.asarray(s_want[r])
    np.testing.assert_allclose(new, want, atol=1e-5)
    np.testing.assert_allclose(
        o, jnp.where(live[:, None, None], o_want, 0.0), atol=1e-5)
    # every state but the live rows' own, layer 0 and the scratch row among
    # them, is the same to the bit
    touched = np.zeros((L, S), bool)
    touched[1, np.asarray(slots)[np.asarray(live)]] = True
    assert np.array_equal(np.asarray(new)[~touched],
                          np.asarray(state)[~touched])


def test_rows_that_are_not_live_keep_state_and_conv_rows_to_the_bit(model,
                                                                    decode3):
    """Through the decode program: slot 2 decodes; slot 1's row is frozen
    (``active`` False) though its table names its state, slot 3's is parked
    on the scratch row. The states and conv rows of slots 1 and 3, set to
    arbitrary values, come back the same to the bit in every LINEAR layer (6
    of 8: the leaves have no row for a full layer), and the counters count
    the one live row in 6 linear layers and its one key in 2 full ones."""
    fc, pc, w = model
    pool = pc.paged.init_pool(pc, 3 + PPS, PAGE)
    assert pool["k"].shape[0] == 2 and pool["gdn"].shape[:2] == (6, 4)
    k = jax.random.split(jax.random.PRNGKey(2), 2)
    pool = {**pool, "gdn": jax.random.normal(k[0], pool["gdn"].shape),
            "conv": jax.random.normal(k[1], pool["conv"].shape)}
    pages = jnp.arange(3, 3 + PPS, dtype=jnp.int32)
    rows = jnp.stack([jnp.append(pages, 2), jnp.append(pages * 0, 1),
                      jnp.zeros(PPS + 1, jnp.int32)])
    _, new, counts = decode3(jnp.asarray([7, 9, 0]), jnp.asarray([0, 4, 0]),
                             pool, rows, jnp.asarray([True, False, False]))
    counts = dict(zip(pc.paged.counters, (int(c) for c in counts)))
    assert counts["gdn_state_rows"] == 6 and counts["attn_full_keys"] == 2
    assert counts["moe_local_rows"] == 8 * 3      # every pick is held here
    for leaf in ("gdn", "conv"):
        a, b = (np.asarray(p[leaf]).reshape(6, 4, -1) for p in (pool, new))
        assert np.array_equal(a[:, [0, 1, 3]], b[:, [0, 1, 3]]), leaf
        assert not np.array_equal(a[:, 2], b[:, 2]), leaf


# -- (b) the chunk's scan -----------------------------------------------------------

def scan_inputs(T, H=4, Hk=2, K=16, V=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return dict(
        q=_l2(jax.random.normal(k[0], (T, Hk, K))) * K ** -0.5,
        k=_l2(jax.random.normal(k[1], (T, Hk, K))),
        v=jax.random.normal(k[2], (T, H, V)),
        g=-0.3 * jax.nn.softplus(jax.random.normal(k[3], (T, H))),
        beta=jax.nn.sigmoid(jax.random.normal(k[4], (T, H))),
        s0=jax.random.normal(k[5], (H, K, V)))


def token_by_token(q, k, v, g, beta, s0):
    def step(s, t):
        qt, kt, vt, gt, bt = t
        o, s = gdn_step_reference(s[None], qt[None], kt[None], vt[None],
                                  jnp.exp(gt)[None], bt[None])
        return s[0], o[0]
    sT, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return o, sT


@pytest.mark.parametrize("T,live,block", [(24, 24, 8), (40, 33, 16),
                                          (16, 5, 8), (48, 1, 16),
                                          (21, 21, 8), (128, 100, 64)])
def test_the_chunked_scan_is_the_token_by_token_recurrence(T, live, block):
    """From a non-zero state; ``live`` rows of T (the rest padding: beta = 0,
    g = 0), ending in the middle of a block; T = 21 is no multiple of the
    block (the block becomes 1). float32 at HIGHEST: what is left is the
    order of summation and the triangular inverse, 1e-6 of values of order
    one."""
    a = scan_inputs(T)
    a["g"] = a["g"].at[live:].set(0.0)
    a["beta"] = a["beta"].at[live:].set(0.0)
    o, sT = jax.jit(lambda a: gdn_chunk_scan(**a, block=block))(a)
    o_want, s_want = token_by_token(**a)
    np.testing.assert_allclose(o[:live], o_want[:live], atol=2e-6)
    np.testing.assert_allclose(sT, s_want, atol=2e-6)
    # the state after the chunk is the state after its last LIVE row
    _, s_live = token_by_token(**{n: x if n == "s0" else x[:live]
                                  for n, x in a.items()})
    np.testing.assert_allclose(sT, s_live, atol=2e-6)


def test_a_chunk_boundary_inside_a_prompt_carries_the_state():
    """Two chunks of 24 from the state the first left = one scan of 48."""
    a = scan_inputs(48, seed=3)
    scan = jax.jit(lambda a: gdn_chunk_scan(**a, block=8))
    first = {n: x if n == "s0" else x[:24] for n, x in a.items()}
    o1, s1 = scan(first)
    o2, s2 = scan({**{n: x[24:] for n, x in a.items() if n != "s0"},
                   "s0": s1})
    o, sT = token_by_token(**a)
    np.testing.assert_allclose(jnp.concatenate([o1, o2]), o, atol=2e-6)
    np.testing.assert_allclose(s2, sT, atol=2e-6)


def test_without_the_delta_term_both_forms_are_gated_linear_attention():
    """``delta=False`` (``u = beta v``: the state is never read before the
    write; what ``benchmark/tools/gdn_control.py`` runs as a control): the
    chunked form and the kernel against the same plain step, and far from
    the delta rule's result."""
    a = scan_inputs(24)
    o, sT = jax.jit(lambda a: gdn_chunk_scan(**a, block=8, delta=False))(a)

    def step(s, t):
        qt, kt, vt, gt, bt = t
        o, s = gdn_step_reference(s[None], qt[None], kt[None], vt[None],
                                  jnp.exp(gt)[None], bt[None], delta=False)
        return s[0], o[0]
    s_want, o_want = jax.lax.scan(step, a["s0"], (a["q"], a["k"], a["v"],
                                                  a["g"], a["beta"]))
    np.testing.assert_allclose(o, o_want, atol=2e-6)
    np.testing.assert_allclose(sT, s_want, atol=2e-6)
    assert float(jnp.abs(sT - token_by_token(**a)[1]).max()) > 0.05
    state = a["s0"][None, None]                       # [1, 1, H, K, V]
    args = (a["q"][:1], a["k"][:1], a["v"][:1], jnp.exp(a["g"][:1]),
            a["beta"][:1])
    o1, new = gdn_decode_update(state, 0, jnp.zeros(1, jnp.int32),
                                jnp.ones(1, bool), *args, delta=False)
    o1_want, s1_want = gdn_step_reference(state[0], *args, delta=False)
    np.testing.assert_allclose(new[0], s1_want, atol=1e-5)
    np.testing.assert_allclose(o1, o1_want, atol=1e-5)


# -- (c), (e) through the engine, against the reference ------------------------------

def reference_rows(w, fc, seq):
    """The reference's logits [len(seq), V]; every sequence padded to 64 (both
    mixers are causal), so that the reference compiles once."""
    padded = np.zeros(64, np.int32)
    padded[:len(seq)] = seq
    return np.asarray(ref.logits(w, padded, fc))[:len(seq)]


def test_two_periods_of_chunks_then_decode_match_the_reference(model, decode3):
    """The programs at TWO periods (layer j of period i reads row 3 i + j of
    the state leaves, row i of the K/V leaves): 40 tokens in chunks of 16 +
    16 + 8 (the last ends inside a block of the scan) into slot 1's state,
    then a decode step between a parked and a frozen row, float32 against the
    reference's full forward pass. What is left is the order of summation
    (the chunked form against the token scan, an online softmax a page at a
    time, the grouped GEMMs): 1e-6 on logits of order one; atol 1e-4 is a
    hundred times that and a hundred times under what one wrong term gives."""
    fc, pc, w = model
    toks = tokens_of(41, seed=13)
    pool = pc.paged.init_pool(pc, 3 + PPS, PAGE)
    pool = {**pool, "gdn": pool["gdn"] + 1.0}    # a former tenant's state
    bt = jnp.append(jnp.arange(3, 3 + PPS, dtype=jnp.int32), 2)
    chunk = jax.jit(lambda t, s, pg: prefill_chunk_paged(
        w, t, s, jnp.int32(40), pc, pg, bt))
    for start in (0, 16, 32):
        part = np.zeros(CHUNK, np.int32)
        part[:min(CHUNK, 40 - start)] = toks[start:min(start + CHUNK, 40)]
        tok, pool = chunk(jnp.asarray(part), jnp.int32(start), pool)
    want = reference_rows(w, fc, toks)
    assert int(tok) == int(want[39].argmax())
    parked = jnp.zeros(PPS + 1, jnp.int32)
    logits, _, _ = decode3(jnp.asarray([0, toks[40], 5]),
                           jnp.asarray([0, 40, 3]), pool,
                           jnp.stack([parked, bt, bt]),
                           jnp.asarray([False, True, False]))
    assert float(np.abs(want).max()) > 1
    np.testing.assert_allclose(logits[1], want[40], atol=1e-4, rtol=1e-5)


@pytest.fixture(scope="module")
def story():
    """ONE engine of two slots and two requests. Every step of it is a whole
    program through the interpreter (0.25 s a layer and token-step), so it
    has ONE period of four layers (two periods: the test above) and one pass
    tells the whole story: A (28 tokens: chunks of 16 + 12) is preempted
    after its first chunk and restarts in a slot whose state its own first
    chunk left behind; B (9 tokens) decodes between A's chunks, is preempted
    with two tokens out, is prefilled again and regenerates them. When A has
    decoded one token, the LOGITS of its next position are read through the
    engine's own pool, table row and state."""
    fc = file_cfg(layers=4)
    pc, w = Adapter(fc)._program_config(), weights_of(fc)
    eng = ServingEngine(w, dataclasses.replace(pc, state_slots=0),
                        num_slots=2, page_size=PAGE, num_pages=30,
                        pages_per_seq=PPS, prefill_chunk=CHUNK,
                        decode_horizon=1)
    reqs = [(tokens_of(28, seed=13), 3), (tokens_of(9, seed=11), 3)]
    rids = [eng.submit(prompt, n) for prompt, n in reqs]
    seen = {}
    while eng.step():
        for slot, req in enumerate(eng.sched.slots):
            if req is None:
                continue
            if "mid_prefill" not in seen and req.rid == rids[0] \
                    and req.state.value == "prefilling" \
                    and req.prefill_cursor > 0:
                eng._preempt(slot)
                seen["mid_prefill"] = (req.prefill_cursor,
                                       len(eng.alloc.pages_of(req.rid)))
            elif "mid_decode" not in seen and req.rid == rids[1] \
                    and req.state.value == "active" \
                    and len(req.generated) >= 2:
                seen["mid_decode"] = list(req.generated)
                eng._preempt(slot)
            elif "logits" not in seen and req.rid == rids[0] \
                    and req.state.value == "active" \
                    and len(req.generated) >= 2:
                served = list(req.generated)
                pos = len(reqs[0][0]) + len(served) - 1
                row = jnp.asarray(eng._device_bt_row(req.rid, slot))
                logits, _ = decode_step_paged(
                    w, jnp.asarray([served[-1]]), jnp.asarray([pos]),
                    eng.cfg, eng.pool, row[None])
                seen["logits"] = (served, np.asarray(logits[0]))
    done = {r.rid: list(r.generated) for r in eng._finished}
    return eng, fc, w, reqs, [done[rid] for rid in rids], seen


def test_chunks_then_decode_through_the_engine_match_the_reference_s_logits(
        story):
    """Prefill in two chunks (after a restart), then a decode step through
    the engine's cache, float32 program against the float32 reference's full
    forward pass: the tolerance of the test above, for its reasons."""
    _, fc, w, reqs, _, seen = story
    served, logits = seen["logits"]
    seq = np.concatenate([reqs[0][0], served])
    want = reference_rows(w, fc, seq)
    assert float(np.abs(want).max()) > 1
    np.testing.assert_allclose(logits, want[len(seq) - 1], atol=1e-4,
                               rtol=1e-5)


def test_new_tenants_start_from_zero_and_victims_regenerate_their_tokens(
        story):
    """Every request serves the tokens the reference's greedy decoding gives
    from a ZERO state: A restarted from cursor 0 with no page kept (a state
    cannot be rewound to a cursor; a family of pages alone resumes) in a slot
    that holds what its first chunk left, the decoding victim prefilled
    again, the tokens it had served before served again."""
    eng, fc, w, reqs, tokens, seen = story
    assert seen["mid_prefill"] == (0, 0)
    assert tokens[1][:len(seen["mid_decode"])] == seen["mid_decode"]
    assert eng.metrics.counters["preemptions"] == 2
    for (prompt, n), got in zip(reqs, tokens):
        seq = np.concatenate([prompt, got]).astype(np.int32)
        want = reference_rows(w, fc, seq)[len(prompt) - 1:-1]
        assert want.argmax(-1).tolist() == got and len(got) == n


def test_the_engine_sizes_each_kind_s_leaves_and_counts_the_state(story):
    eng = story[0]
    assert eng.cfg.state_slots == 2 and eng._bt.shape == (2, PPS + 1)
    # 3 linear layers hold states and no page, the full layer pages and no
    # state
    assert eng.pool["gdn"].shape == (3, 3, 4, 16, 16)
    assert eng.pool["gdn"].dtype == jnp.float32
    assert eng.pool["conv"].shape == (3 * 3, 3 * 128)
    assert eng.pool["k"].shape[0] == eng.pool["v"].shape[0] == 1
    per_slot = lm.slot_state_bytes(eng.cfg)
    assert per_slot == 3 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    h = eng.metrics.hist["state_bytes"]
    assert h.count > 0 and 0 < h.total <= h.count * 2 * per_slot
    c = eng.metrics.counters
    # live rows only: never more than slots x linear layers x token-steps
    assert 0 < c["gdn_state_rows"] <= 2 * 3 * c["decode_steps"]
    assert c["attn_full_keys"] > 0 and c["moe_local_rows"] > 0


# -- (d) the shares add up -----------------------------------------------------------

@pytest.mark.parametrize("kind,layer", [("linear", 1), ("full", 7)])
def test_the_four_shares_add_up_to_the_uncut_layer(model, kind, layer):
    """16 experts, 4 shares of 4: the routed parts of every share (softmax
    over all 16, the 3 largest renormalised) plus the shared expert under its
    gate, counted ONCE, are the reference's FFN with all 16 held. Every share
    computes the shared expert (it is common to the chips): three of the four
    copies are taken off."""
    fc, _, w = model
    z = ref.sizes(fc)
    h = jax.random.normal(jax.random.PRNGKey(9), (48, z["D"]), jnp.float32)
    at = layer // 4 * 3 + layer % 4 if kind == "linear" else layer // 4
    p = {n: a[at] for n, a in w["blocks"][kind].items()}
    tables = tuple(w["blocks"][n] for n in ("we_gate", "we_up", "we_down"))
    want = np.asarray(ref.ffn(h, p, tables, layer, z, None))
    shared = np.asarray(want - ref.routed_part(h, p["w_router"], tables,
                                               layer, z, None))
    assert float(np.abs(shared).max()) > 0.01
    total, rows = 0.0, 0
    for first in range(0, 16, 4):
        pc = Adapter(file_cfg(held=4, first=first))._program_config()
        mine = tuple(t[:, first:first + 4] for t in tables)
        out, counts = lm.sparse_ffn(pc, p, h, layer, None, tables=mine)
        total = total + np.asarray(out)
        rows += int(counts["moe_local_rows"])
    assert rows == 48 * fc["num_experts_per_tok"]      # every pick, once
    np.testing.assert_allclose(total - 3 * shared, want, atol=5e-6,
                               rtol=1e-4)


def test_the_compacted_share_is_the_whole_bookkeeping_s_result():
    """``held_picks`` (the held picks alone, 1.6 x their expected number at
    a time) against ``held_experts`` (every pick aligned, gathered and
    unscrambled): a batch routed as the share expects (37 held picks of 144,
    one trip) and one that crowds it (every pick held: 144 against a cap of
    128, two trips). The terms ride two bfloat16 halves through the one-hot
    sum: 16 bits of each, 3e-6 of values of order one."""
    from triton_dist_tpu.models import expert_share as es
    R, k, D, F, E, Eh = 48, 3, 64, 32, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    tables = tuple(jax.random.normal(ks[i], shape) * 0.1 for i, shape in
                   enumerate([(2, Eh, D, F), (2, Eh, D, F), (2, Eh, F, D)]))
    h = jax.random.normal(ks[3], (R, D))
    w = jax.nn.softmax(jax.random.normal(ks[5], (R, k)), -1)
    for ids, held in ((jax.random.randint(ks[4], (R, k), 0, E), 37),
                      (jnp.tile(jnp.asarray([[4, 5, 6]]), (R, 1)), 144)):
        lid, counts = es.held_ids(ids, Eh, 4)
        assert int(counts["moe_local_rows"]) == held
        want = es.held_experts(h, lid, w, tables, Eh, Eh, 8)
        got = jax.jit(lambda lid: es.held_picks(h, lid, w, tables, Eh, Eh,
                                                Eh / E, 8))(lid)
        assert float(jnp.abs(want).max()) > 0.5
        np.testing.assert_allclose(got, want, atol=1e-5)


# -- (e) what a state forbids ---------------------------------------------------------

@pytest.fixture(scope="module")
def idle_engine(model):
    """An engine that never runs (nothing is compiled)."""
    fc, pc, w = model
    return ServingEngine(w, pc, num_slots=3, page_size=PAGE, num_pages=20,
                         pages_per_seq=PPS, prefill_chunk=CHUNK)


@pytest.mark.parametrize("option", [{"prefix_cache": True},
                                    {"speculate": 2},
                                    {"ffn": lambda h, p: h}])
def test_what_the_linear_attention_family_lacks_is_refused_by_name(model,
                                                                   option):
    fc, pc, w = model
    with pytest.raises(NotImplementedError, match="linear_attn_moe"):
        ServingEngine(w, pc, num_slots=2, page_size=PAGE, num_pages=20,
                      pages_per_seq=PPS, prefill_chunk=CHUNK, **option)


@pytest.mark.parametrize("move", ["copy", "export", "import"])
def test_pages_do_not_move_without_their_state(idle_engine, move):
    """A sequence is its pages AND its slot's state: page copy, export and
    import (what prefix sharing, disaggregation and migration are made of)
    are refused by name rather than served from pages alone."""
    eng = idle_engine
    with pytest.raises(NotImplementedError, match="linear_attn_moe.*state"):
        if move == "copy":
            eng._copy_page(1, 2)
        elif move == "export":
            eng._export_pages([1])
        else:
            eng._import_pages([1], None)


def test_the_tiny_preset_serves():
    cfg = lm.bind(lm.LinearAttnMoEConfig.tiny(held=4, first=8, n_layers=4),
                  2, CHUNK)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    pool = cfg.paged.init_pool(cfg, 6, PAGE)
    assert set(pool) == {"k", "v", "gdn", "conv"}
    bt = jnp.asarray([1, 2, 3, 4, 1], jnp.int32)
    toks = jnp.asarray(np.arange(CHUNK) + 1, jnp.int32)
    tok, pool = prefill_chunk_paged(params, toks, jnp.int32(0),
                                    jnp.int32(CHUNK), cfg, pool, bt)
    assert 0 <= int(tok) < cfg.vocab_size
    assert float(jnp.abs(pool["gdn"][:, 1]).max()) > 0
    assert float(jnp.abs(pool["gdn"][:, [0, 2]]).max()) == 0


# -- (f) the other families serve what the parent served --------------------------------

PINS = np.load(parent_pins_families.FILE)


@pytest.fixture(scope="module", params=parent_pins_families.FAMILIES)
def family(request):
    """A family whose two programs are compiled: an engine of the pinned
    run's shape has served two chunks and a dispatch, so that the compile
    (most of a minute for the sink-window preset) and the pinned run each
    have the suite's watchdog to themselves."""
    eng = parent_pins_families.engine(request.param)
    eng.submit(np.arange(1, 18), 2)
    while eng.step():
        pass
    return request.param


def test_the_other_families_serve_the_parent_s_tokens(family):
    """The chunk and the decode program of each other family that shares
    code with this one (the layer loop, ``expert_share``, the GQA kernels,
    the in-place state loop), tiny presets, fixed seeds, through an engine of
    two slots: every request's tokens are the parent's (pins taken on commit
    140cd65)."""
    for name, value in parent_pins_families.programs(family).items():
        assert np.array_equal(value, PINS[name]), name
