"""The mixer-beside-attention family (ISSUE 32: ``models/hybrid_ssm.py``,
``ops/ssm.py``, the engine's per-slot state) at a small size on the CPU,
seeded weights, interpret-mode kernels:

- (a) the chunk's scan (chunked form) against the token-by-token recurrence,
  from a non-zero state, at lengths that are no multiples of its block;
- (b) ``ssm_decode_update`` against one step of the recurrence, and rows that
  are not live leave state and conv rows equal TO THE BIT;
- (c) through ``ServingEngine``: a prompt prefilled in three chunks while two
  other slots decode between its chunks, then decoded with K = 4, gives the
  benchmark's plain reference's full-forward logits
  (``benchmark/references/hybrid_ssm_lm.py``: imports nothing of the program,
  scans token by token);
- (d) a slot reused by a new request, and a preempted request that restarts,
  serve the tokens a fresh engine serves;
- (e) each multiplier of the published config moves the logits, and moves the
  reference's the same way;
- (f) a query group of 5 through ``gqa_decode_paged`` / ``gqa_prefill_paged``
  against dense attention;
- (g) what a state forbids is refused by name.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conftest  # noqa: F401  (forces the CPU platform)
from benchmark.adapters.hybrid_engine import Adapter
from benchmark.references import hybrid_ssm_lm as ref
from triton_dist_tpu.models import hybrid_ssm as hm
from triton_dist_tpu.models.llama import (decode_step_paged,
                                          prefill_chunk_paged)
from triton_dist_tpu.ops.flash_decode import (gqa_decode_paged,
                                              gqa_prefill_paged)
from triton_dist_tpu.ops.ssm import (ssd_chunk_scan, ssm_decode_update,
                                     ssm_step_reference)
from triton_dist_tpu.serving import ServingEngine

PAGE, CHUNK, PPS = 8, 16, 12
TINY = os.path.join(conftest.REPO_ROOT, "benchmark", "tests",
                    "rehearsal_hybrid", "configs", "tiny-hybrid.json")


def file_cfg(dtype="float32", **changed):
    """A configuration FILE's keys at test size (what the adapter and the
    reference read): the benchmark's own tiny rehearsal file (a query group
    of 5, two state groups, every multiplier away from 1 but the one the
    source has at 1)."""
    with open(TINY) as f:
        cfg = json.load(f)
    cfg.update(torch_dtype=dtype, **changed)
    # the conv rows are held in the activations' dtype
    cfg["cache"] = {"state_bytes_per_slot_per_layer":
                    4 * 32 * 16 * 4 + 3 * 192 * jnp.dtype(dtype).itemsize}
    return cfg


def weights_of(fc, seed=3):
    return jax.jit(lambda k: ref.init_weights(k, fc))(
        jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def model():
    """(file config, program config bound to 3 slots, weights), float32."""
    fc = file_cfg()
    return fc, Adapter(fc)._program_config(), weights_of(fc)


def tokens_of(n, seed=5):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,), 1,
                                         256), np.int32)


# -- (a) the chunk's scan ---------------------------------------------------------

def scan_inputs(T, H=4, P=16, G=2, N=32, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(k[0], (T, H, P)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (T, H))),
        A=-jnp.exp(jax.random.normal(k[2], (H,)) - 1.0),
        b=jax.random.normal(k[3], (T, G, N)),
        c=jax.random.normal(k[4], (T, G, N)),
        h0=jax.random.normal(k[5], (H, N, P)))


def token_by_token(x, dt, A, b, c, h0):
    def step(h, t):
        xt, dtt, bt, ct = t
        y, h = ssm_step_reference(h[None], (xt * dtt[:, None])[None],
                                  jnp.exp(dtt * A)[None], bt[None], ct[None])
        return h[0], y[0]
    hT, y = jax.lax.scan(step, h0, (x, dt, b, c))
    return y, hT


@pytest.mark.parametrize("T,live,block", [(24, 24, 8), (40, 33, 16),
                                          (16, 5, 8), (48, 1, 16),
                                          (21, 21, 8)])
def test_the_chunked_scan_is_the_token_by_token_recurrence(T, live, block):
    """From a non-zero state; ``live`` rows of T (the rest padding: dt = 0),
    ending in the middle of a block; T = 21 is no multiple of the block (the
    block becomes gcd(T, block) = 1). float32 at HIGHEST: what is left is the
    order of summation, 1e-5 of values of order 30."""
    a = scan_inputs(T)
    a["dt"] = a["dt"].at[live:].set(0.0)
    y, hT = jax.jit(lambda a: ssd_chunk_scan(**a, block=block))(a)
    y_want, h_want = token_by_token(**a)
    scale = float(jnp.abs(y_want).max())
    np.testing.assert_allclose(y[:live], y_want[:live], atol=2e-5 * scale)
    np.testing.assert_allclose(hT, h_want, atol=2e-5 * float(
        jnp.abs(h_want).max()))
    # the state after the chunk is the state after its last LIVE row
    _, h_live = token_by_token(**{k: v[:live] if k not in ("A", "h0") else v
                                  for k, v in a.items()})
    np.testing.assert_allclose(hT, h_live, atol=2e-5 * float(
        jnp.abs(h_live).max()))


# -- (b) the decode rows' update ---------------------------------------------------

@pytest.mark.parametrize("live", [(True, False, True, True),
                                  (False, False, False, False),
                                  (True, True, True, True)])
def test_the_decode_update_is_one_step_and_idle_rows_move_nothing(live):
    L, S, H, N, P, G, R = 2, 6, 4, 32, 16, 2, 4
    k = jax.random.split(jax.random.PRNGKey(1), 6)
    state = jax.random.normal(k[0], (L, S, H, N, P))
    slots = jnp.asarray([3, 5, 1, 4], jnp.int32)
    live = jnp.asarray(live)
    xdt = jax.random.normal(k[1], (R, H, P))
    decay = jax.nn.sigmoid(jax.random.normal(k[2], (R, H)))
    b, c = (jax.random.normal(k[i], (R, G, N)) for i in (3, 4))
    y, new = jax.jit(lambda s: ssm_decode_update(
        s, 1, slots, live, xdt, decay, b, c))(state)
    y_want, h_want = ssm_step_reference(state[1][slots], xdt, decay, b, c)
    want = np.asarray(state).copy()
    for r in range(R):
        if live[r]:
            want[1, int(slots[r])] = np.asarray(h_want[r])
    np.testing.assert_allclose(new, want, atol=1e-5)
    np.testing.assert_allclose(
        y, jnp.where(live[:, None, None], y_want, 0.0), atol=1e-4)
    # every state but the live rows' own, layer 0 and the scratch row among
    # them, is the same to the bit
    touched = np.zeros((L, S), bool)
    touched[1, np.asarray(slots)[np.asarray(live)]] = True
    assert np.array_equal(np.asarray(new)[~touched],
                          np.asarray(state)[~touched])


def test_rows_that_are_not_live_keep_state_and_conv_rows_to_the_bit(model):
    """Through the decode program: slot 2 decodes; slot 1's row is frozen
    (``active`` False) though its table names its state, slot 3's is parked
    on the scratch row. The states and conv rows of slots 1 and 3, set to
    arbitrary values, come back the same to the bit, in every layer."""
    fc, pc, w = model
    pool = pc.paged.init_pool(pc, 3 + PPS, PAGE)
    k = jax.random.split(jax.random.PRNGKey(2), 2)
    pool = {**pool, "ssm": jax.random.normal(k[0], pool["ssm"].shape),
            "conv": jax.random.normal(k[1], pool["conv"].shape)}
    pages = jnp.arange(3, 3 + PPS, dtype=jnp.int32)
    rows = jnp.stack([jnp.append(pages, 2), jnp.append(pages * 0, 1),
                      jnp.zeros(PPS + 1, jnp.int32)])
    _, new, counts = jax.jit(lambda pg: decode_step_paged(
        w, jnp.asarray([7, 9, 0]), jnp.asarray([0, 4, 0]), pc, pg, rows,
        active=jnp.asarray([True, False, False]), counters=True))(pool)
    assert [int(c) for c in counts] == [pc.n_layers]        # one live row
    for leaf in ("ssm", "conv"):
        a, b = (np.asarray(p[leaf]).reshape(pc.n_layers, 4, -1)
                for p in (pool, new))
        assert np.array_equal(a[:, [0, 1, 3]], b[:, [0, 1, 3]]), leaf
        assert not np.array_equal(a[:, 2], b[:, 2]), leaf


# -- (c) through the engine, against the reference -----------------------------------

def serve_three(fc, pc, w, horizon=4):
    """Two short prompts decode in slots 0 and 1 while a 40-token prompt
    enters slot 2 in three chunks (16 + 16 + 8), one a step; every request
    then decodes under K = ``horizon``. Returns (engine, the long request's
    prompt, its served tokens so far, the logits of its next position through
    the engine's own pool, table row and state)."""
    eng = ServingEngine(w, pc, num_slots=3, page_size=PAGE, num_pages=36,
                        pages_per_seq=PPS, prefill_chunk=CHUNK,
                        decode_horizon=horizon)
    prompts = [tokens_of(9, seed=11), tokens_of(13, seed=12),
               tokens_of(40, seed=13)]
    rids = [eng.submit(p, 30) for p in prompts[:2]]
    eng.step(), eng.step()                   # both short prompts now decode
    rids.append(eng.submit(prompts[2], 30))
    chunks_between = []
    for _ in range(5):
        before = eng.metrics.counters["decode_steps"]
        eng.step()
        chunks_between.append(eng.metrics.counters["decode_steps"] - before)
    req = next(r for r in eng.sched.slots if r is not None
               and r.rid == rids[2])
    slot = eng.sched.slots.index(req)
    assert req.state.value == "active" and len(req.generated) >= 5
    assert eng.metrics.counters["prefill_chunks"] == 2 + 3
    assert all(n > 0 for n in chunks_between[:3])   # decode ran between chunks
    served = list(req.generated)
    pos = len(prompts[2]) + len(served) - 1
    row = jnp.asarray(eng._device_bt_row(req.rid, slot))
    logits, _ = decode_step_paged(
        w, jnp.asarray([served[-1]]), jnp.asarray([pos]), eng.cfg, eng.pool,
        row[None])
    return eng, prompts[2], served, np.asarray(logits[0])


@pytest.fixture(scope="module")
def served32(model):
    return serve_three(*model)


def test_three_chunks_between_decoding_slots_then_k4_match_the_reference(
        model, served32):
    """float32 program against the float32 reference: what is left is the
    order of summation (the chunked scan against the token scan, an online
    softmax a page at a time): 1e-5 here on logits of order 30. atol 1e-4
    is ten times that and a hundred times under what bfloat16 gives (next
    test). Every served token is the reference's argmax at its position."""
    fc, _, w = model
    _, prompt, served, logits = served32
    seq = np.concatenate([prompt, served])
    want = np.asarray(ref.logits(w, seq, fc))
    np.testing.assert_allclose(logits, want[len(seq) - 1], atol=1e-4,
                               rtol=1e-5)
    rows = want[len(prompt) - 1:len(seq) - 1]
    assert rows.argmax(-1).tolist() == served


def test_bfloat16_weights_and_activations_stay_inside_their_rounding(model):
    """bfloat16 params and activations (float32 state) against the float32
    reference ON THE SAME bfloat16 weights: every product's inputs are
    rounded to 8 bits of mantissa, 0.4 % a value, over 2 layers of 3 terms:
    the logits (std 6, order 30) read 0.05-0.15 off; atol 0.5 is three times
    that and fifteen times under what a wrong term gives (e: >= 7)."""
    fc = file_cfg("bfloat16")
    pc, w = Adapter(fc)._program_config(), weights_of(fc)
    _, prompt, served, logits = serve_three(fc, pc, w)
    seq = np.concatenate([prompt, served])
    want = np.asarray(ref.logits(w, seq, fc)[len(seq) - 1:len(seq)])[0]
    assert float(np.abs(want).max()) > 10
    np.testing.assert_allclose(logits, want, atol=0.5)


# -- (d) a reused slot, a restarted victim --------------------------------------------

@pytest.fixture(scope="module")
def replay(model):
    """Four requests through ONE engine of two slots (so every slot is
    reused by a later tenant), twice: undisturbed, and with the oldest
    request preempted in the middle of its prefill and a decoding one
    preempted later."""
    fc, pc, w = model
    rng = np.random.default_rng(7)
    reqs = [(rng.integers(1, 256, n), m) for n, m in
            ((50, 6), (20, 9), (37, 6), (18, 7))]
    eng = ServingEngine(w, dataclasses.replace(pc, state_slots=0),
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
            if disturb and steps == 8:
                slot = next(s for s, r in slots if r is not None
                            and r.state.value == "active")
                eng._preempt(slot)
                seen["mid_decode"] = True
        done = {r.rid: list(r.generated) for r in eng._finished}
        return {i: done[rid] for i, rid in enumerate(rids)}

    return eng, reqs, serve(False), serve(True), seen


def test_a_reused_slot_serves_what_a_fresh_engine_serves(model, replay):
    """Requests 2 and 3 enter slots whose states the first two tenants left
    behind (and, in the second pass, every request does): each serves the
    tokens the reference's greedy decoding gives, which is what an engine
    that never held another request serves."""
    fc, _, w = model
    _, reqs, golden, _, _ = replay
    for i, (prompt, n) in enumerate(reqs):
        seq = np.concatenate([prompt, golden[i]]).astype(np.int32)
        want = np.asarray(ref.logits(w, seq, fc))[len(prompt) - 1:-1]
        assert want.argmax(-1).tolist() == golden[i], i
        assert len(golden[i]) == n


def test_a_preempted_sequence_restarts_and_replays_its_tokens(replay):
    """A state cannot be rewound to a cursor: a victim preempted in the
    middle of its prefill keeps NO page and restarts at cursor 0 (a family
    of pages alone keeps its filled pages and resumes); a decoding victim
    re-prefills. The tokens are the undisturbed run's either way."""
    eng, _, golden, again, seen = replay
    assert seen == {"mid_prefill": (0, 0), "mid_decode": True}
    assert eng.metrics.counters["preemptions"] == 2
    conftest.assert_replay_identical(again, golden, 4)


def test_the_engine_sizes_the_state_and_counts_it(replay):
    eng = replay[0]
    assert eng.cfg.state_slots == 2 and eng._bt.shape == (2, PPS + 1)
    assert eng.pool["ssm"].shape[:2] == (eng.cfg.n_layers, 3)
    assert eng.pool["ssm"].dtype == jnp.float32
    per_slot = hm.slot_state_bytes(eng.cfg)
    assert per_slot == eng.cfg.n_layers * (4 * 32 * 16 * 4 + 3 * 192 * 4)
    h = eng.metrics.hist["state_bytes"]
    assert h.count > 0 and 0 < h.total <= h.count * 2 * per_slot
    c = eng.metrics.counters
    assert c["ssm_state_rows"] > 0
    # (tokens the chunk program scanned: ``step_prefill_tokens``, as ever)
    assert eng.metrics.hist["step_prefill_tokens"].total >= 2 * (
        50 + 20 + 37 + 18)
    # live rows only: never more than slots x layers x token-steps
    assert c["ssm_state_rows"] <= 2 * eng.cfg.n_layers * c["decode_steps"]


# -- (e) every multiplier is computed --------------------------------------------------

MULTIPLIERS = [("embedding_multiplier", None), ("lm_head_multiplier", None),
               ("attention_in_multiplier", None),
               ("attention_out_multiplier", None), ("key_multiplier", None),
               ("ssm_in_multiplier", None), ("ssm_out_multiplier", None),
               ("ssm_multipliers", 0), ("ssm_multipliers", 1),
               ("ssm_multipliers", 2), ("ssm_multipliers", 3),
               ("ssm_multipliers", 4), ("mlp_multipliers", 0),
               ("mlp_multipliers", 1)]


def two_steps(fc, w):
    """Logits of the second of two decode steps of one fresh slot (two keys:
    with one the softmax is 1 whatever the key; the second step reads the
    state the first left)."""
    pc = Adapter(fc)._program_config()
    pool = pc.paged.init_pool(pc, 4, PAGE)
    row = jnp.asarray([[1, 2, 1]], jnp.int32)
    step = jax.jit(lambda t, pos, pg: decode_step_paged(w, t, pos, pc, pg,
                                                        row))
    _, pool = step(jnp.asarray([17]), jnp.asarray([0]), pool)
    logits, _ = step(jnp.asarray([99]), jnp.asarray([1]), pool)
    return np.asarray(logits[0])


@pytest.fixture(scope="module")
def baseline(model):
    fc, _, w = model
    return two_steps(dict(fc, engine=dict(fc["engine"], num_slots=1)), w)


@pytest.mark.parametrize("name,index", MULTIPLIERS,
                         ids=[n if i is None else f"{n}[{i}]"
                              for n, i in MULTIPLIERS])
def test_each_multiplier_moves_the_logits_as_the_reference_s(model, baseline,
                                                             name, index):
    """Set to 1 (``attention_in_multiplier``, 1 in the source, to 0.5), the
    multiplier moves the program's logits, and the reference's to the same
    place: none is folded away or applied on the wrong side of a norm."""
    fc, _, w = model
    new = 0.5 if name == "attention_in_multiplier" else 1.0
    value = new if index is None else [
        new if i == index else m for i, m in enumerate(fc[name])]
    changed = dict(fc, engine=dict(fc["engine"], num_slots=1),
                   **{name: value})
    got = two_steps(changed, w)
    assert float(np.abs(got - baseline).max()) > 0.005, "nothing moved"
    want = np.asarray(ref.logits(w, np.asarray([17, 99], np.int32),
                                 changed))[1]
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-5)


# -- (f) a query group of 5 --------------------------------------------------------------

def dense_attention(q, k, v, kv_len):
    """q [R, Hq, D] each row against keys [0, kv_len_r) of k, v [T, Hkv, D]."""
    G = q.shape[1] // k.shape[1]
    kk, vv = (jnp.repeat(a, G, axis=1) for a in (k, v))
    s = jnp.einsum("rhd,thd->rht", q, kk) / np.sqrt(q.shape[-1])
    seen = jnp.arange(k.shape[0])[None, None] < kv_len[:, None, None]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    p = jnp.where(kv_len[:, None, None] > 0, p, 0.0)
    return jnp.einsum("rht,thd->rhd", p, vv)


@pytest.fixture(scope="module")
def kv5():
    """One sequence of 40 keys in 5 pages of 8 of a 2-layer pool, 2 KV heads
    under 10 query heads."""
    k = jax.random.split(jax.random.PRNGKey(4), 3)
    keys, vals = (jax.random.normal(k[i], (40, 2, 16)) for i in (0, 1))
    paged = lambda a: jnp.zeros((2, 9, 2, 8, 16)).at[1, 2:7].set(  # noqa: E731
        a.reshape(5, 8, 2, 16).swapaxes(1, 2))
    return keys, vals, paged(keys), paged(vals), k[2]


def test_decode_rows_with_a_query_group_of_five(kv5):
    keys, vals, kp, vp, k = kv5
    q = jax.random.normal(k, (4, 10, 16))
    table = jnp.broadcast_to(jnp.arange(2, 8, dtype=jnp.int32), (4, 6))
    kv_len = jnp.asarray([40, 0, 17, 8], jnp.int32)
    out, _ = gqa_decode_paged(q, kp, vp, table, kv_len, layer=1)
    np.testing.assert_allclose(out, dense_attention(q, keys, vals, kv_len),
                               atol=2e-6)


@pytest.mark.parametrize("rows", [4, 16])
def test_a_chunk_with_a_query_group_of_five(kv5, rows):
    keys, vals, kp, vp, k = kv5
    q = jax.random.normal(k, (16, 10, 16))
    # positions 19 .. 31 of the sequence, then three rows of padding
    kv_len = jnp.where(jnp.arange(16) < 13, jnp.arange(16) + 20, 0)
    out = gqa_prefill_paged(q, kp, vp, jnp.arange(2, 8, dtype=jnp.int32),
                            kv_len, layer=1, rows_per_block=rows)
    np.testing.assert_allclose(out, dense_attention(q, keys, vals, kv_len),
                               atol=2e-6)


# -- (g) what a state forbids ---------------------------------------------------------

@pytest.mark.parametrize("option", [{"prefix_cache": True},
                                    {"speculate": 2},
                                    {"ffn": lambda h, p: h}])
def test_what_the_hybrid_family_lacks_is_refused_by_name(model, option):
    fc, pc, w = model
    with pytest.raises(NotImplementedError, match="hybrid_ssm"):
        ServingEngine(w, pc, num_slots=2, page_size=PAGE, num_pages=20,
                      pages_per_seq=PPS, prefill_chunk=CHUNK, **option)


@pytest.mark.parametrize("move", ["copy", "export", "import"])
def test_pages_do_not_move_without_their_state(replay, move):
    """A sequence is its pages AND its slot's state: page copy, export and
    import (what prefix sharing, disaggregation and migration are made of)
    are refused by name rather than served from pages alone."""
    eng = replay[0]
    with pytest.raises(NotImplementedError, match="hybrid_ssm.*state"):
        if move == "copy":
            eng._copy_page(1, 2)
        elif move == "export":
            eng._export_pages([1])
        else:
            eng._import_pages([1], None)


def test_the_tiny_preset_serves():
    cfg = hm.bind(hm.HybridSSMConfig.tiny(), 2, CHUNK)
    params = hm.init_params(jax.random.PRNGKey(0), cfg)
    pool = cfg.paged.init_pool(cfg, 6, PAGE)
    assert set(pool) == {"k", "v", "ssm", "conv"}
    assert pool["ssm"].shape == (2, 3, 4, 32, 16)
    assert pool["conv"].shape == (2 * 3, 3 * 192)
    bt = jnp.asarray([1, 2, 3, 4, 1], jnp.int32)
    toks = jnp.asarray(np.arange(CHUNK) + 1, jnp.int32)
    tok, pool = prefill_chunk_paged(params, toks, jnp.int32(0),
                                    jnp.int32(CHUNK), cfg, pool, bt)
    assert 0 <= int(tok) < cfg.vocab_size
    assert float(jnp.abs(pool["ssm"][:, 1]).max()) > 0
    assert float(jnp.abs(pool["ssm"][:, [0, 2]]).max()) == 0
