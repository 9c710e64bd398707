"""The looped family (ISSUE 46: ``models/looped.py``, ONE stack of layers that
every token walks several times over the same weights, each walk with cache
planes of its own, sandwich norms, a norm and an exit gate at every walk's end)
at a small size on the CPU, seeded weights, interpret-mode kernels: three
layers walked three times, four heads for four KV heads.

- (a) prefill in chunks, then decode, gives the LOGITS of the benchmark's plain
  reference's full forward pass (``benchmark/references/looped_lm.py``: imports
  nothing of the program, no cache: walk t over its own keys of the whole
  sequence): the programs across two chunk boundaries, and through
  ``ServingEngine``'s cache, with a slot a former tenant left its pages in;
- (b) the pool has walks x layers planes, and a write of one (walk, layer)
  leaves every other plane untouched TO THE BIT;
- (c) the layer loop is ONE compiled body: the decode program's jaxpr holds one
  ``gqa_decode_paged`` call, not one a walk or a (walk, layer);
- (d) the exit rule against the reference on a gate drawn to saturate in walk
  1 (the only test where a walk before the last is picked);
- (e) every control of ``benchmark/tools/loop_control.py`` (the PROGRAM wrong
  in one thing) moves the logits, but for the one that is the same
  mathematics;
- (f) the pages are plain K/V pages over more planes than layers: page copy /
  export / import and the prefix cache serve them; speculation and the hooks
  are refused by name.
"""

import contextlib
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conftest  # noqa: F401  (forces the CPU platform)
from benchmark.adapters.looped_engine import Adapter
from benchmark.references import looped_lm as ref
from benchmark.tools.loop_control import patches
from triton_dist_tpu.models import looped as lp
from triton_dist_tpu.models.llama import (decode_multistep_paged,
                                          decode_step_paged,
                                          prefill_chunk_paged)
from triton_dist_tpu.serving import ServingEngine

PAGE, CHUNK, PPS, PAGES = 8, 16, 8, 24
TINY = os.path.join(conftest.REPO_ROOT, "benchmark", "tests",
                    "rehearsal_looped", "configs", "tiny-looped.json")
L, WALKS = 3, 3
CONTROLS = [n for n in patches(lp) if n != "none"]


def file_cfg(dtype="float32"):
    """A configuration FILE's keys at test size (what the adapter and the
    reference read): the benchmark's own tiny rehearsal file; the cache's
    bytes follow the dtype."""
    with open(TINY) as f:
        cfg = json.load(f)
    size = jnp.dtype(dtype).itemsize
    key = 2 * 4 * 16 * size
    cfg.update(torch_dtype=dtype, cache={
        "planes": L * WALKS, "kv_bytes_per_key_and_plane": key,
        "kv_bytes_per_token": L * WALKS * key,
        "kv_bytes_per_page": PAGE * L * WALKS * key})
    return cfg


def weights_of(fc, seed=3):
    return jax.jit(lambda k: ref.init_weights(k, fc))(
        jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def model():
    """(file config, program config, weights), float32."""
    fc = file_cfg()
    return fc, Adapter(fc)._program_config(), weights_of(fc)


def tokens_of(n, seed=5):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 1,
                                         256), np.int32)


def reference_rows(w, fc, seq, **kw):
    """The reference's logits [len(seq), V]; every sequence padded to 64
    (attention is causal), so that the reference compiles once."""
    padded = np.zeros(64, np.int32)
    padded[:len(seq)] = seq
    return np.asarray(ref.logits(w, padded, fc, **kw)[:len(seq)])


def prefilled(pc, w, toks, n, pool, bt):
    """``toks[:n]`` through the chunk program into ``pool``; (token, pool)."""
    chunk = jax.jit(lambda t, s, pg: prefill_chunk_paged(
        w, t, s, jnp.int32(n), pc, pg, bt))
    for start in range(0, n, CHUNK):
        part = np.zeros(CHUNK, np.int32)
        part[:min(CHUNK, n - start)] = toks[start:min(start + CHUNK, n)]
        tok, pool = chunk(jnp.asarray(part), jnp.int32(start), pool)
    return tok, pool


def decode3(pc, w):
    """The decode step of three rows, jitted: (tokens, pos, pool, table rows,
    active) -> (logits, pool, counters)."""
    return jax.jit(lambda t, pos, pg, rows, active: decode_step_paged(
        w, t, pos, pc, pg, rows, active=active, counters=True))


# -- (a) against the reference ------------------------------------------------------

def test_chunks_then_decode_match_the_reference(model):
    """40 tokens in chunks of 16 + 16 + 8 into pages a former tenant left
    non-zero, then a decode step between a parked and a frozen row, float32
    against the reference's full forward pass. What is left is the order of
    summation (an online softmax a page at a time): 2e-6 on logits of order
    five; atol 1e-4 is fifty times that and far under what one wrong term
    gives (test (e)). The counters: a live row walks its 41 keys in each of
    the 9 planes, a parked or frozen row counts nothing."""
    fc, pc, w = model
    toks = tokens_of(41, seed=13)
    pool = jax.tree.map(lambda a: a + 1.0,
                        pc.paged.init_pool(pc, 3 + PPS, PAGE))
    bt = jnp.arange(3, 3 + PPS, dtype=jnp.int32)
    tok, pool = prefilled(pc, w, toks, 40, pool, bt)
    want = reference_rows(w, fc, toks)
    assert int(tok) == int(want[39].argmax())
    parked = jnp.zeros(PPS, jnp.int32)
    logits, _, counts = decode3(pc, w)(
        jnp.asarray([0, toks[40], 5]), jnp.asarray([0, 40, 3]), pool,
        jnp.stack([parked, bt, bt]), jnp.asarray([False, True, False]))
    assert float(np.abs(want).max()) > 1.0
    np.testing.assert_allclose(logits[1], want[40], atol=1e-4, rtol=1e-5)
    assert dict(zip(pc.paged.counters, map(int, counts))) == {
        "loop_plane_keys": 41 * L * WALKS, "loop_row_calls": L * WALKS,
        "loop_early_exit_rows": 0}


@pytest.fixture(scope="module")
def story():
    """ONE engine of two slots and three requests, one pass: A (28 tokens:
    chunks of 16 + 12, a chunk boundary) and B (9 tokens) are seated, C (12
    tokens) arrives when both slots are taken and is seated in the slot and
    on the pages of whoever finishes first. When A has decoded two tokens,
    the LOGITS of its next position are read through the engine's own pool
    and table row."""
    fc = file_cfg()
    pc, w = Adapter(fc)._program_config(), weights_of(fc)
    eng = ServingEngine(w, pc, num_slots=2, page_size=PAGE, num_pages=PAGES,
                        pages_per_seq=PPS, prefill_chunk=CHUNK,
                        decode_horizon=1)
    reqs = [(tokens_of(28, seed=13), 4), (tokens_of(9, seed=11), 3),
            (tokens_of(12, seed=7), 3)]
    rids = [eng.submit(prompt, n) for prompt, n in reqs]
    seen = {}
    while eng.step():
        for slot, req in enumerate(eng.sched.slots):
            if req is None:
                continue
            if req.rid == rids[2]:
                seen.setdefault("c_pages", list(eng.alloc.pages_of(req.rid)))
            elif req.rid == rids[1]:
                seen["b_pages"] = list(eng.alloc.pages_of(req.rid))
            if "logits" not in seen and req.rid == rids[0] \
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
    """Prefill in two chunks, then decode steps through the engine's 9-plane
    cache, float32 program against the float32 reference's full forward
    pass: the tolerance of the test above, for its reasons."""
    _, fc, w, reqs, _, seen = story
    served, logits = seen["logits"]
    seq = np.concatenate([reqs[0][0], served])
    want = reference_rows(w, fc, seq)
    assert float(np.abs(want).max()) > 1.0
    np.testing.assert_allclose(logits, want[len(seq) - 1], atol=1e-4,
                               rtol=1e-5)


def test_every_request_serves_the_reference_s_greedy_tokens_in_a_used_slot(
        story):
    """Every request serves the tokens the reference's greedy decoding gives;
    C among them, seated on pages B wrote in every plane."""
    eng, fc, w, reqs, tokens, seen = story
    assert set(seen["c_pages"]) & set(seen["b_pages"])
    for (prompt, n), got in zip(reqs, tokens):
        seq = np.concatenate([prompt, got]).astype(np.int32)
        want = reference_rows(w, fc, seq)[len(prompt) - 1:-1]
        assert want.argmax(-1).tolist() == got and len(got) == n


def test_the_engine_holds_walks_x_layers_planes_and_counts_their_walks(story):
    eng = story[0]
    assert eng._bt.shape == (2, PPS)                # plain pages: no column
    assert set(eng.pool) == {"k", "v"}
    assert eng.pool["k"].shape == (L * WALKS, PAGES + 1, 4, PAGE, 16)
    c = eng.metrics.counters
    assert c["kv_bytes_per_token"] == lp.kv_bytes_per_token(eng.cfg) \
        == L * WALKS * 2 * 4 * 16 * 4
    # a live row is L x WALKS calls a token-step, each over its context
    assert c["loop_row_calls"] % (L * WALKS) == 0
    assert 0 < c["loop_row_calls"] <= 2 * L * WALKS * c["decode_steps"]
    assert c["loop_plane_keys"] >= 10 * c["loop_row_calls"]
    assert c["loop_early_exit_rows"] == 0
    # the chunk walks are counted a plane: 9 planes x a chunk's row blocks
    assert c["chunk_walk_pages"] % (L * WALKS) == 0 and c["chunk_walk_pages"]


# -- (b) a plane a (walk, layer) -------------------------------------------------------

@pytest.mark.parametrize("walk,layer", [(0, 2), (1, 0), (2, 1)])
def test_a_write_of_one_walk_leaves_every_other_plane_untouched(model, walk,
                                                                layer):
    """One (walk, layer)'s attention over a pool of noise: its own plane
    gains the rows' keys and values, every other plane (the same layer's
    other walks among them) is the same bits."""
    _, pc, w = model
    plane = walk * L + layer
    p = {n: a[layer] for n, a in w["blocks"].items()}
    pool = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(2), a.shape),
        pc.paged.init_pool(pc, 6, PAGE))
    h = jax.random.normal(jax.random.PRNGKey(4), (2, pc.d_model))
    table = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    out, new, counts = lp._attention(
        pc, p, h, plane, pool, table, jnp.asarray([9, 12]),
        jnp.asarray([10, 13]), None, False, lambda a, b, n: a @ b, None)
    assert int(counts["loop_plane_keys"]) == 23
    others = np.arange(L * WALKS) != plane
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(new[leaf])[others],
                                      np.asarray(pool[leaf])[others])
        moved = np.asarray(new[leaf][plane] != pool[leaf][plane])
        # rows 9 % 8 of page 2 and 12 % 8 of page 5, every head
        at = [a.tolist() for a in moved.any(axis=(1, 3)).nonzero()]
        assert at == [[2, 5], [1, 4]]
    assert float(jnp.abs(out).max()) > 0.05


# -- (c) one compiled body ---------------------------------------------------------------

def test_the_decode_program_holds_one_walk_kernel_for_all_walks_and_layers(
        model):
    """The multistep program of horizon 2 over 3 layers x 3 walks: ONE
    ``gqa_decode_paged`` call in its jaxpr (the scan over layers inside the
    loop over walks inside the scan over inner steps), one ``pallas_call``
    whatever the planes."""
    _, pc, w = model
    pool = pc.paged.init_pool(pc, 4, PAGE)
    z = jnp.zeros(2, jnp.int32)
    jaxpr = str(jax.make_jaxpr(lambda pg: decode_multistep_paged(
        w, z, z, pc, pg, jnp.zeros((2, PPS), jnp.int32), z + 2, horizon=2))(
            pool))
    assert jaxpr.count("name=gqa_decode_paged") == 1
    assert jaxpr.count("pallas_call") == 1


# -- (d) the exit rule ---------------------------------------------------------------------

def test_a_saturated_gate_exits_early_as_the_reference_does(model):
    """A gate drawn to SATURATE in walk 1 on some rows (its weight scaled up
    and aligned with walk 1's rows: logits past 17, ``lam`` == 1.0 in float32,
    the running sum reaches the threshold 1.0): the head takes walk 1's rows
    there, in the program as in the reference, every walk is still computed
    (the cache holds all of them), and ``loop_early_exit_rows`` counts the
    live rows. The only test where a walk before the last is picked."""
    fc, pc, w = model
    toks = tokens_of(17, seed=21)
    base = ref.logits(w, np.pad(toks, (0, 64 - 17)), fc)
    # the gate's direction: the part of walk 1's normed row of the last
    # position that walk 0's row of it does not share
    h0, h1 = base.hidden[0, 16], base.hidden[1, 16]
    d = h1 - h0 * jnp.dot(h1, h0) / jnp.dot(h0, h0)
    loud = dict(w, exit_gate=d * 40.0 / jnp.dot(d, d),
                exit_bias=jnp.float32(-10.0))
    got = ref.logits(loud, np.pad(toks, (0, 64 - 17)), fc, early_ok=True)
    picked = np.asarray(got.picked)[:17]
    assert picked[16] == 1 and (picked == WALKS - 1).any()
    with pytest.raises(AssertionError, match="a gate saturated"):
        ref.logits(loud, np.pad(toks, (0, 64 - 17)), fc)[16:17]
    want = np.asarray(got[:17])
    pool = pc.paged.init_pool(pc, 3 + PPS, PAGE)
    bt = jnp.arange(3, 3 + PPS, dtype=jnp.int32)
    _, pool = prefilled(pc, loud, toks, 16, pool, bt)
    logits, _, counts = decode3(pc, loud)(
        jnp.asarray([toks[16], 0, 0]), jnp.asarray([16, 0, 0]), pool,
        jnp.stack([bt, bt * 0, bt * 0]), jnp.asarray([True, False, False]))
    np.testing.assert_allclose(logits[0], want[16], atol=1e-4, rtol=1e-5)
    assert int(counts[2]) == 1
    # and walk 2 was computed all the same: the last walk's rows differ
    last = np.asarray(ref._head(got.hidden[WALKS - 1, 16:17], w["lm_head"],
                                None))[0]
    assert float(np.abs(last - want[16]).max()) > 0.1


# -- (e) the controls ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def sound(model):
    """The sound program's logits of a decode step after 16 tokens (a
    chunk)."""
    return controlled(model, {})


def controlled(model, wrong):
    """A chunk of 16 tokens and a decode step through the cache with the
    attributes ``wrong`` of ``models.looped`` replaced: the step's logits."""
    _, pc, w = model
    toks = jnp.asarray(tokens_of(17, seed=17))
    bt = jnp.arange(3, 3 + PPS, dtype=jnp.int32)

    @jax.jit
    def both(pool):                 # ONE program a control: one compile
        _, pool = prefill_chunk_paged(w, toks[:16], jnp.int32(0),
                                      jnp.int32(16), pc, pool, bt)
        return decode_step_paged(w, toks[16:], jnp.asarray([16]), pc, pool,
                                 bt[None])[0][0]

    with mock.patch.multiple(lp, **wrong) if wrong \
            else contextlib.nullcontext():
        return np.asarray(both(pc.paged.init_pool(pc, 3 + PPS, PAGE)))


@pytest.mark.parametrize("control", CONTROLS)
def test_every_control_moves_the_logits_but_the_one_that_is_the_same_maths(
        model, sound, control):
    """The PROGRAM wrong in one thing (``benchmark/tools/loop_control``'s
    patches, what the chip-side controls run), a chunk and a decode step
    through the cache: the logits move by far more than the 1e-4 the programs
    are held to. ``rope-by-walk`` advances queries AND keys by the walk's
    number: rotary is relative and a plane holds one walk's keys, so it is
    the same mathematics and must NOT move them (no check can see it; it is a
    control of the controls)."""
    got = controlled(model, patches(lp)[control])
    assert lp.n_walks(model[1]) == WALKS             # put back
    moved = float(np.abs(got - sound).max())
    if control == "rope-by-walk":
        assert moved < 1e-4, moved
    else:
        assert moved > 0.05, (control, moved)


# -- (f) what the engine takes and refuses ---------------------------------------------------

@pytest.fixture(scope="module")
def idle_engine(model):
    """An engine that never runs (nothing is compiled), the story's shape."""
    fc, pc, w = model
    return ServingEngine(w, pc, num_slots=2, page_size=PAGE, num_pages=PAGES,
                         pages_per_seq=PPS, prefill_chunk=CHUNK,
                         decode_horizon=1)


@pytest.mark.parametrize("option", [{"speculate": 2},
                                    {"ffn": lambda h, p: h}])
def test_what_the_looped_family_lacks_is_refused_by_name(model, option):
    fc, pc, w = model
    with pytest.raises(NotImplementedError, match="looped"):
        ServingEngine(w, pc, num_slots=2, page_size=PAGE, num_pages=PAGES,
                      pages_per_seq=PPS, prefill_chunk=CHUNK, **option)


@pytest.mark.parametrize("move", ["copy", "export-import"])
def test_pages_move_over_all_the_planes(idle_engine, move):
    """Page copy, export and import map over the pool's leaves and never ask
    the config for its layers: a page moves in all 9 planes."""
    eng = idle_engine
    noise = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(8), a.shape), eng.pool)
    eng.pool = noise
    try:
        if move == "copy":
            eng._copy_page(3, 5)
        else:
            payload = eng._export_pages([3, 4])
            assert payload["k"].shape == (L * WALKS, 2, 4, PAGE, 16)
            eng._import_pages([5, 6], payload)
        for leaf in ("k", "v"):
            np.testing.assert_array_equal(eng.pool[leaf][:, 5],
                                          noise[leaf][:, 3])
            np.testing.assert_array_equal(eng.pool[leaf][:, 7],
                                          noise[leaf][:, 7])
    finally:
        eng.pool = jax.tree.map(jnp.zeros_like, noise)


def test_the_prefix_cache_shares_pages_of_every_plane(model):
    """Two requests with a common first 16 tokens (two full pages) through an
    engine with the prefix cache on, the story's shape (its programs): the
    second adopts the first's pages (a hit), and both serve the reference's
    greedy tokens, i.e. the adopted pages hold the right keys in all 9
    planes."""
    fc, pc, w = model
    eng = ServingEngine(w, pc, num_slots=2, page_size=PAGE, num_pages=PAGES,
                        pages_per_seq=PPS, prefill_chunk=CHUNK,
                        decode_horizon=1, prefix_cache=True)
    common = tokens_of(16, seed=31)
    prompts = [np.concatenate([common, tokens_of(5, seed=s)])
               for s in (32, 33)]
    served = []
    for prompt in prompts:                  # one after the other
        eng.submit(prompt, 2)
        while eng.step():
            pass
        served.append(list(eng._finished[-1].generated))
    assert eng.metrics.counters["prefix_hit_tokens"] == 16
    for prompt, got in zip(prompts, served):
        seq = np.concatenate([prompt, got]).astype(np.int32)
        want = reference_rows(w, fc, seq)[len(prompt) - 1:-1]
        assert want.argmax(-1).tolist() == got


def test_the_adapter_holds_the_program_to_the_file_s_cache():
    fc = file_cfg()
    pc = Adapter(fc)._program_config()
    assert (pc.n_layers, pc.n_walks, pc.n_planes) == (L, WALKS, L * WALKS)
    for key in ("planes", "kv_bytes_per_key_and_plane", "kv_bytes_per_token",
                "kv_bytes_per_page"):
        bad = dict(fc, cache=dict(fc["cache"], **{key: 1}))
        with pytest.raises(ValueError, match=key):
            Adapter(bad)._program_config()
    with pytest.raises(ValueError, match="walked"):
        Adapter(dict(fc, tie_word_embeddings=True))._program_config()


def test_the_tiny_preset_is_the_family_at_test_size():
    """``LoopedConfig.tiny`` with the program's own draw: the record, the
    pool's planes and both programs' shapes (traced, not run: the programs
    run above on the reference's weights)."""
    cfg = lp.LoopedConfig.tiny()
    fam = cfg.paged
    assert fam.name == "looped" and fam.lacks == ("speculate", "hooks")
    assert fam.walks(cfg).n == cfg.n_walks == 3
    assert fam.chunk_walks(cfg)[0][0] == cfg.n_planes == 9
    params = lp.init_params(jax.random.PRNGKey(0), cfg)
    assert params["blocks"]["wq"].shape == (3, 64, 64)      # ONE stack
    pool = fam.init_pool(cfg, 6, PAGE)
    assert pool["k"].shape == (9, 6, 4, PAGE, 16)
    z = jnp.zeros(2, jnp.int32)
    tok, out = jax.eval_shape(
        lambda pg: prefill_chunk_paged(
            params, jnp.zeros(CHUNK, jnp.int32), jnp.int32(0),
            jnp.int32(CHUNK), cfg, pg, jnp.zeros(4, jnp.int32)), pool)
    assert tok.shape == () and out["k"].shape == pool["k"].shape
    slab = jax.eval_shape(lambda pg: decode_multistep_paged(
        params, z, z, cfg, pg, jnp.zeros((2, 4), jnp.int32), z + 2,
        horizon=2), pool)[0]
    assert slab.shape == (2 + len(lp.COUNTERS), 2)   # the counters' rows
