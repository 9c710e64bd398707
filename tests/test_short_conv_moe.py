"""The short-convolution family (ISSUE 43: ``models/short_conv_moe.py``, conv
layers that hold two rows a slot and NO pages among GQA layers whose pages
hold ``[K | V]`` rows, a leading dense run, a whole layer of bias-selected
experts) at a small size on the CPU, seeded weights, interpret-mode kernels:
two dense conv layers, then periods of (full, conv, conv, conv), 16 experts
top-3, a query group of 2.

- (a) the one-step conv (decode rows) against the chunk conv, from carried
  rows, and rows that are not live keep their rows TO THE BIT;
- (b) prefill in chunks, then decode, gives the LOGITS of the benchmark's
  plain reference's full forward pass
  (``benchmark/references/short_conv_moe_lm.py``: imports nothing of the
  program, scans token by token): the programs at two periods, and through
  ``ServingEngine``'s cache (one period) across chunk boundaries, after a
  restart, and in a slot a former tenant left its rows in;
- (c) THE WHOLE LAYER IS THE SHARE OF ONE: with all 16 held no pick is
  dropped; 16 experts as 4 shares of 4 add up to the same uncut layer;
- (d) every control of ``benchmark/tools/short_conv_control.py`` (the PROGRAM
  wrong in one thing) moves what its mixer or router gives;
- (e) a preempted request restarts and regenerates its tokens, and what a
  state forbids is refused by name.
"""

import dataclasses
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conftest  # noqa: F401  (forces the CPU platform)
from benchmark.adapters.short_conv_engine import Adapter, period_of
from benchmark.references import short_conv_moe_lm as ref
from benchmark.tools.short_conv_control import patches
from triton_dist_tpu.models import short_conv_moe as sc
from triton_dist_tpu.models.llama import (decode_step_paged,
                                          prefill_chunk_paged)
from triton_dist_tpu.serving import ServingEngine

PAGE, CHUNK, PPS = 8, 16, 12
TINY = os.path.join(conftest.REPO_ROOT, "benchmark", "tests",
                    "rehearsal_short_conv", "configs", "tiny-short-conv.json")


def file_cfg(dtype="float32", layers=10):
    """A configuration FILE's keys at test size (what the adapter and the
    reference read): the benchmark's own tiny rehearsal file at the depth
    asked for; the cache's bytes follow the dtype."""
    with open(TINY) as f:
        cfg = json.load(f)
    size = jnp.dtype(dtype).itemsize
    cfg.update(torch_dtype=dtype, num_hidden_layers=layers)
    cfg["cache"] = {"kv_bytes_per_token_per_full_layer": 2 * 2 * 16 * size,
                    "state_bytes_per_slot_per_conv_layer": 2 * 64 * size}
    return cfg


def weights_of(fc, seed=3):
    return jax.jit(lambda k: ref.init_weights(k, fc))(
        jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def model():
    """(file config, program config bound to 3 slots, weights), float32, two
    dense layers and two periods."""
    fc = file_cfg()
    return fc, Adapter(fc)._program_config(), weights_of(fc)


@pytest.fixture(scope="module")
def decode3(model):
    """The decode step of three rows at ten layers, jitted once: (tokens,
    pos, pool, table rows, active) -> (logits, pool, counters)."""
    _, pc, w = model
    return jax.jit(lambda t, pos, pg, rows, active: decode_step_paged(
        w, t, pos, pc, pg, rows, active=active, counters=True))


def tokens_of(n, seed=5):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 1,
                                         256), np.int32)


# -- (a) the two forms of the conv --------------------------------------------------

def conv_layer(model, layer=3):
    """(program config, params of conv layer ``layer`` of the periodic run, a
    mixer call on rows h): layer 3 is the first conv layer of the first
    period, row 2 of the ``conv`` leaf."""
    _, pc, w = model
    p = {n: a[0] for n, a in w["blocks"]["conv"].items()}
    lin = lambda h, wt, name: jnp.matmul(                   # noqa: E731
        h, wt, precision=jax.lax.Precision.HIGHEST)

    def mixer(h, pool, table, pos, kv_len, active, shared):
        return sc._conv_mixer(0, pc, p, h, layer, pool, table, pos, kv_len,
                              active, shared, lin, None)
    return pc, mixer


def test_the_one_step_conv_is_the_chunk_conv_and_carries_its_rows(model):
    """21 tokens of slot 2 as chunks of 16 (fresh) + 5 live of 16 (from the
    carried rows), and the same tokens one decode step at a time: the same
    outputs and the same two rows left behind, which are the last two rows of
    ``B * u``. float32 at HIGHEST: the order of three products' sum."""
    pc, mixer = conv_layer(model)
    h = jax.random.normal(jax.random.PRNGKey(1), (32, pc.d_model))
    pool = pc.paged.init_pool(pc, 4, PAGE)
    pool = {**pool, "conv": pool["conv"] + 3.0}     # a former tenant's rows
    bt = jnp.asarray([[1, 2, 3, 2]], jnp.int32)     # last column: the slot
    at = jnp.arange(16)
    o1, chunked, _ = mixer(h[:16], pool, bt, at, at + 1, None, True)
    live = jnp.where(at < 5, 16 + at + 1, 0)
    o2, chunked, counts = mixer(h[16:], chunked, bt, 16 + at, live, None,
                                True)
    assert int(counts["conv_state_rows"]) == 0      # a chunk counts none
    stepped, outs = pool, []
    for t in range(21):
        # a slot's first token finds zeros, as a fresh chunk does
        if t == 0:
            stepped = {**stepped, "conv": stepped["conv"].at[2 * 4 + 2].set(0)}
        o, stepped, counts = mixer(h[t:t + 1], stepped, bt,
                                   jnp.asarray([t]), jnp.asarray([t + 1]),
                                   None, False)
        assert int(counts["conv_state_rows"]) == 1
        outs.append(o[0])
    want = jnp.stack(outs)
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(jnp.concatenate([o1, o2[:5]]), want,
                               atol=1e-5)
    np.testing.assert_allclose(chunked["conv"], stepped["conv"], atol=1e-6)
    # the carried rows are the last two of B * u
    bcu = jnp.matmul(h[19:21], model[2]["blocks"]["conv"]["w_in"][0],
                     precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(
        chunked["conv"][2 * 4 + 2].reshape(2, -1),
        bcu[:, :64] * bcu[:, 128:], atol=1e-5)
    # every other row of the leaf is the former tenant's, to the bit
    others = np.ones(chunked["conv"].shape[0], bool)
    others[2 * 4 + 2] = False
    assert np.array_equal(np.asarray(chunked["conv"])[others],
                          np.asarray(pool["conv"])[others])


def test_rows_that_are_not_live_keep_their_conv_rows_to_the_bit(model,
                                                                 decode3):
    """Through the decode program: slot 2 decodes; slot 1's row is frozen
    (``active`` False) though its table names its rows, slot 3's is parked on
    the scratch row. The rows of slots 1 and 3, set to arbitrary values, come
    back the same to the bit in every CONV layer (8 of 10: the leaf has no
    row for a full layer), and the counters count the one live row in 8 conv
    layers, its one key in 2 full ones and its 3 picks in 8 sparse layers."""
    fc, pc, w = model
    pool = pc.paged.init_pool(pc, 3 + PPS, PAGE)
    assert pool["kv"].shape == (2, 3 + PPS, 2, PAGE, 32)
    assert pool["conv"].shape == (8 * 4, 2 * 64)
    pool = {**pool, "conv": jax.random.normal(jax.random.PRNGKey(2),
                                              pool["conv"].shape)}
    pages = jnp.arange(3, 3 + PPS, dtype=jnp.int32)
    rows = jnp.stack([jnp.append(pages, 2), jnp.append(pages * 0, 1),
                      jnp.zeros(PPS + 1, jnp.int32)])
    _, new, counts = decode3(jnp.asarray([7, 9, 0]), jnp.asarray([0, 4, 0]),
                             pool, rows, jnp.asarray([True, False, False]))
    counts = dict(zip(pc.paged.counters, (int(c) for c in counts)))
    assert counts["conv_state_rows"] == 8 and counts["attn_full_keys"] == 2
    assert counts["moe_local_rows"] == 8 * 3      # every pick is held here
    a, b = (np.asarray(p["conv"]).reshape(8, 4, -1) for p in (pool, new))
    assert np.array_equal(a[:, [0, 1, 3]], b[:, [0, 1, 3]])
    assert not np.array_equal(a[:, 2], b[:, 2])


# -- (b), (e) through the programs and the engine, against the reference ----------------

def reference_rows(w, fc, seq):
    """The reference's logits [len(seq), V]; every sequence padded to 64 (both
    mixers are causal), so that the reference compiles once a depth."""
    padded = np.zeros(64, np.int32)
    padded[:len(seq)] = seq
    return np.asarray(ref.logits(w, padded, fc))[:len(seq)]


def test_two_periods_of_chunks_then_decode_match_the_reference(model, decode3):
    """The programs at the leading run and TWO periods (conv layer j of
    period i reads row 2 + 3 i + j of the ``conv`` leaf, the full layer row i
    of ``kv``): 40 tokens in chunks of 16 + 16 + 8 into slot 1's rows, which
    a former tenant left non-zero, then a decode step between a parked and a
    frozen row, float32 against the reference's full forward pass. What is
    left is the order of summation (an online softmax a page at a time, the
    grouped GEMMs): 1e-6 on logits of order one; atol 1e-4 is a hundred times
    that and far under what one wrong term gives (test (d))."""
    fc, pc, w = model
    toks = tokens_of(41, seed=13)
    pool = pc.paged.init_pool(pc, 3 + PPS, PAGE)
    pool = {**pool, "conv": pool["conv"] + 1.0}
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
    assert float(np.abs(want).max()) > 0.3
    np.testing.assert_allclose(logits[1], want[40], atol=1e-4, rtol=1e-5)


@pytest.fixture(scope="module")
def story():
    """ONE engine of two slots and three requests. Every step of it is a
    whole program through the interpreter, so it has the leading run and ONE
    period (two periods: the test above) and one pass tells the whole story:
    A (28 tokens: chunks of 16 + 12, the carried rows cross a boundary; two
    boundaries: the test above) is preempted after its first chunk and restarts in a slot
    whose rows its own first chunk left behind; B (9 tokens) decodes between
    A's chunks, is preempted with two tokens out, is prefilled again and
    regenerates them; C (12 tokens) arrives when both slots are taken and is
    seated in the slot of whoever finishes first, over that tenant's rows.
    When A has decoded one token, the LOGITS of its next position are read
    through the engine's own pool, table row and state."""
    fc = file_cfg(layers=6)
    pc, w = Adapter(fc)._program_config(), weights_of(fc)
    eng = ServingEngine(w, dataclasses.replace(pc, state_slots=0),
                        num_slots=2, page_size=PAGE, num_pages=30,
                        pages_per_seq=PPS, prefill_chunk=CHUNK,
                        decode_horizon=1)
    reqs = [(tokens_of(28, seed=13), 3), (tokens_of(9, seed=11), 3),
            (tokens_of(12, seed=7), 2)]
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
    assert float(np.abs(want).max()) > 0.3
    np.testing.assert_allclose(logits, want[len(seq) - 1], atol=1e-4,
                               rtol=1e-5)


def test_new_tenants_start_from_zero_and_victims_regenerate_their_tokens(
        story):
    """Every request serves the tokens the reference's greedy decoding gives
    from ZERO rows: A restarted from cursor 0 with no page kept (a state
    cannot be rewound to a cursor) in a slot that holds what its first chunk
    left, the decoding victim prefilled again, the tokens it had served
    before served again, and C in a slot a finished request left its rows
    in."""
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
    # 5 conv layers (2 dense, 3 of the period) hold rows and no page, the
    # full layer pages of [K | V] and no rows
    assert set(eng.pool) == {"kv", "conv"}
    assert eng.pool["conv"].shape == (5 * 3, 2 * 64)
    assert eng.pool["kv"].shape[0] == 1 and eng.pool["kv"].shape[-1] == 32
    per_slot = sc.slot_state_bytes(eng.cfg)
    assert per_slot == 5 * 2 * 64 * 4
    assert sc.kv_bytes_per_token(eng.cfg) == 2 * 32 * 4
    h = eng.metrics.hist["state_bytes"]
    assert h.count > 0 and 0 < h.total <= h.count * 2 * per_slot
    c = eng.metrics.counters
    # live rows only: never more than slots x conv layers x token-steps
    assert 0 < c["conv_state_rows"] <= 2 * 5 * c["decode_steps"]
    assert c["attn_full_keys"] > 0
    # every pick is held: decode rows x 3 picks x 4 sparse layers, and as
    # many rows of the conv layers x 3 / 5 x 4
    assert c["moe_local_rows"] * 5 == c["conv_state_rows"] * 3 * 4


# -- (c) the whole layer is the share of one ------------------------------------------

@pytest.mark.parametrize("shares", [1, 4])
def test_the_shares_add_up_to_the_uncut_layer_and_one_share_drops_nothing(
        model, shares):
    """16 experts as ``shares`` shares of 16 / shares: the routed parts of
    every share (sigmoid scores over all 16, the 3 largest of score + bias,
    renormalised) add up to the reference's routed sum with all 16 held, and
    the shares count every pick once. ONE share is the whole layer
    (``n_experts_held == n_routed_experts``): no pick is dropped, no local id
    is the sentinel, ``moe_local_rows`` is rows x 3 and every expert with a
    row is counted."""
    fc, pc, w = model
    z = ref.sizes(fc)
    h = jax.random.normal(jax.random.PRNGKey(9), (16, z["D"]), jnp.float32)
    layer = 7                     # a conv layer of the second period: row 3
    p = {n: a[3] for n, a in w["blocks"]["conv"].items()}
    tables = tuple(w["blocks"][n] for n in ("we_gate", "we_up", "we_down"))
    want = np.asarray(ref.routed_sum(h, p, tables, layer - 2, z, None))
    assert float(np.abs(want).max()) > 0.02
    held = 16 // shares
    total, rows, touched = 0.0, 0, 0
    for first in range(0, 16, held):
        mine = dataclasses.replace(pc, n_experts_held=held,
                                   first_held_expert=first)
        out, counts = sc.sparse_ffn(
            mine, p, h, layer, None,
            tables=tuple(t[:, first:first + held] for t in tables))
        total = total + np.asarray(out)
        rows += int(counts["moe_local_rows"])
        touched += int(counts["moe_experts_touched"])
    assert rows == 16 * fc["num_experts_per_tok"]      # every pick, once
    ids, _ = sc.route(pc, p, h)
    assert touched == len(np.unique(np.asarray(ids)))
    np.testing.assert_allclose(total, want, atol=5e-6, rtol=1e-4)
    if shares == 1:
        from triton_dist_tpu.models.expert_share import held_ids
        lid, _ = held_ids(ids, 16, 0, None)
        assert np.array_equal(np.asarray(lid), np.asarray(ids))
        # a frozen row's picks are the only ones dropped
        active = jnp.arange(16) % 2 == 0
        lid, counts = held_ids(ids, 16, 0, active)
        assert int(counts["moe_local_rows"]) == 8 * 3
        assert np.all(np.asarray(lid)[1::2] == -1)


def test_the_row_block_follows_the_rows_an_expert_sees():
    cfg = sc.ShortConvMoEConfig()     # the published widths
    # 96 decode rows x 4 of 64: 6 rows an expert; a 2,048-row chunk: 128
    assert sc.expert_block_m(96, cfg) == 16
    assert sc.expert_block_m(2048, cfg) == 128
    assert sc.expert_block_m(8192, cfg) == 128


# -- (d) the controls move what they put wrong ------------------------------------------

CONTROLS = [n for n in patches(sc) if n != "none"]


@pytest.mark.parametrize("control", CONTROLS)
def test_every_control_moves_what_its_mixer_or_router_gives(model, control):
    """The PROGRAM wrong in one thing (``benchmark/tools/short_conv_control``'s
    patches, what the chip-side controls run): a conv layer's chunk from
    carried rows, a full layer's decode step over two pages, or a sparse
    layer's routing gives another result than the sound program's, by far
    more than the 1e-4 the programs are held to. One layer's function alone:
    a whole program a control would be a minute of interpreter each."""
    fc, pc, w = model
    wrong = patches(sc)[control]
    h = jax.random.normal(jax.random.PRNGKey(4), (16, pc.d_model))

    def result():
        if set(wrong) & {"chunk_starts_fresh", "taps_of", "output_gate"}:
            _, mixer = conv_layer(model)
            pool = pc.paged.init_pool(pc, 4, PAGE)
            pool = {**pool, "conv": pool["conv"] + 0.7}
            at = jnp.arange(16)
            return mixer(h, pool, jnp.asarray([[1, 2, 3, 2]], jnp.int32),
                         16 + at, 17 + at, None, True)[0]
        if "route" in wrong:
            p = {n: a[0] for n, a in w["blocks"]["conv"].items()}
            ids, wts = sc.route(pc, p, h)
            return jnp.sum((ids[..., None] == jnp.arange(16))
                           * wts[..., None], axis=1)
        p = {n: a[0] for n, a in w["blocks"]["full"].items()}
        pool = pc.paged.init_pool(pc, 6, PAGE)
        pool = {**pool, "kv": jax.random.normal(jax.random.PRNGKey(5),
                                                pool["kv"].shape)}
        table = jnp.asarray([[1, 2, 3, 0]] * 2, jnp.int32)
        return sc._full_attention(
            0, pc, p, h[:2], 2, pool, table, jnp.asarray([9, 12]),
            jnp.asarray([10, 13]), None, False, lambda a, b, n: a @ b,
            None)[0]

    sound = result()
    with mock.patch.multiple(sc, **wrong):
        moved = result()
    assert sc.route is not wrong.get("route")       # put back
    assert float(jnp.abs(moved - sound).max()) > 0.02, control


# -- (e) what a state forbids -------------------------------------------------------------

@pytest.fixture(scope="module")
def idle_engine(model):
    """An engine that never runs (nothing is compiled)."""
    fc, pc, w = model
    return ServingEngine(w, pc, num_slots=3, page_size=PAGE, num_pages=20,
                         pages_per_seq=PPS, prefill_chunk=CHUNK)


@pytest.mark.parametrize("option", [{"prefix_cache": True},
                                    {"speculate": 2},
                                    {"ffn": lambda h, p: h}])
def test_what_the_short_conv_family_lacks_is_refused_by_name(model, option):
    fc, pc, w = model
    with pytest.raises(NotImplementedError, match="short_conv_moe"):
        ServingEngine(w, pc, num_slots=2, page_size=PAGE, num_pages=20,
                      pages_per_seq=PPS, prefill_chunk=CHUNK, **option)


@pytest.mark.parametrize("move", ["copy", "export", "import"])
def test_pages_do_not_move_without_their_rows(idle_engine, move):
    """A sequence is its pages AND its slot's conv rows: page copy, export
    and import (what prefix sharing, disaggregation and migration are made
    of) are refused by name rather than served from pages alone."""
    eng = idle_engine
    with pytest.raises(NotImplementedError, match="short_conv_moe.*state"):
        if move == "copy":
            eng._copy_page(1, 2)
        elif move == "export":
            eng._export_pages([1])
        else:
            eng._import_pages([1], None)


def test_the_adapter_holds_the_program_to_the_file_s_cache_and_pattern():
    fc = file_cfg()
    assert period_of(("full", "conv", "conv", "conv") * 2) == (
        "full", "conv", "conv", "conv")
    assert Adapter(fc)._program_config().layer_kinds == (
        "full", "conv", "conv", "conv")
    for key in ("kv_bytes_per_token_per_full_layer",
                "state_bytes_per_slot_per_conv_layer"):
        bad = dict(fc, cache=dict(fc["cache"], **{key: 1}))
        with pytest.raises(ValueError, match=key):
            Adapter(bad)._program_config()
    with pytest.raises(ValueError, match="leading dense conv layers"):
        Adapter(dict(fc, layer_types=["full_attention"] * 10)
                )._program_config()


def test_the_tiny_preset_serves():
    cfg = sc.bind(sc.ShortConvMoEConfig.tiny(held=4, first=8, n_layers=6),
                  2, CHUNK)
    params = sc.init_params(jax.random.PRNGKey(0), cfg)
    pool = cfg.paged.init_pool(cfg, 6, PAGE)
    assert set(pool) == {"kv", "conv"}
    bt = jnp.asarray([1, 2, 3, 4, 1], jnp.int32)
    toks = jnp.asarray(np.arange(CHUNK) + 1, jnp.int32)
    tok, pool = prefill_chunk_paged(params, toks, jnp.int32(0),
                                    jnp.int32(CHUNK), cfg, pool, bt)
    assert 0 <= int(tok) < cfg.vocab_size
    conv = pool["conv"].reshape(5, 3, -1)
    assert float(jnp.abs(conv[:, 1]).max()) > 0
    assert float(jnp.abs(conv[:, [0, 2]]).max()) == 0
