"""The host's path from a token's readback to the decode dispatch does work
in proportion to what changed (PR 45).

- the ledger stamps a sequence's page list at every mutation, and the stamp
  is no part of the digest or the snapshot;
- ``ServingEngine._grow`` looks at a slot's table row again only when the
  stamp moved, so the mirror is held to a row built from scratch after EVERY
  step, for a family whose slots own rings, one whose slots own state and the
  plain one, through every way a sequence's pages change;
- ``Histogram.observe_n(v, n)`` leaves what ``n`` calls of ``observe(v)`` do.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_dist_tpu.models import hybrid_ssm, llama, window_moe
from triton_dist_tpu.serving import ServingEngine
from triton_dist_tpu.serving.kv_pool import KVPagePool
from triton_dist_tpu.serving.metrics import Histogram, ServingMetrics
from triton_dist_tpu.serving.scheduler import RequestState

# -- the ledger's stamp -----------------------------------------------------


def _share(pool):
    """Two pages of "a" that the prefix index holds and "b" adopts."""
    for p in pool.alloc("a", 2):
        pool.mark_cacheable(p)
    pool.acquire("b", pool.pages_of("a"))


# every way ``_owned[seq]`` changes, a ledger call a step
SCRIPTS = {
    "alloc": [lambda p: p.alloc("a", 2), lambda p: p.alloc("a", 1)],
    "ensure": [lambda p: p.ensure("a", 9), lambda p: p.ensure("a", 17)],
    "acquire": [_share, lambda p: p.acquire("c", p.pages_of("a")[:1])],
    "free_tail": [lambda p: p.alloc("a", 3),
                  lambda p: p.free_tail("a", keep=1),
                  lambda p: p.free_tail("a", keep=0)],
    "free_seq": [lambda p: p.alloc("a", 3), lambda p: p.free_seq("a"),
                 lambda p: p.alloc("a", 3)],
    "cow_page": [_share, lambda p: p.cow_page("b", 1)],
}


@pytest.mark.parametrize("how", sorted(SCRIPTS))
def test_every_mutation_of_a_page_list_moves_its_stamp(how):
    """Whatever changes a sequence's page list gives it a stamp the pool
    never gave before; a sequence that holds nothing reads 0."""
    pool = KVPagePool(12, 8, reserved=1)
    given, pages = set(), {}
    for call in SCRIPTS[how]:
        call(pool)
        moved = 0
        for sid in ("a", "b", "c"):
            now = tuple(pool.pages_of(sid))
            assert (pool.stamp(sid) == 0) == (not pool.holds(sid))
            if now != pages.get(sid, ()):
                moved += 1
                assert pool.stamp(sid) not in given - {0}, (how, sid)
            pages[sid] = now
        given.update(pool.stamp(sid) for sid in ("a", "b", "c"))
        assert moved, "the step changed no page list"


def test_a_no_op_leaves_the_stamp():
    pool = KVPagePool(12, 8, reserved=1)
    pool.alloc("a", 2)
    was = pool.stamp("a")
    assert pool.ensure("a", 16) and pool.stamp("a") == was
    assert pool.alloc("a", 99) is None and pool.stamp("a") == was
    assert pool.n_pages_of("a") == 2 and pool.n_pages_of("nobody") == 0


def test_a_stamp_is_never_given_twice():
    """Not across a sequence's free and re-allocation of the SAME pages."""
    pool = KVPagePool(12, 8, reserved=1)
    first = pool.alloc("a", 2)
    was = pool.stamp("a")
    pool.free_seq("a")
    assert sorted(pool.alloc("a", 2)) == sorted(first)
    assert pool.stamp("a") > was


def test_the_stamp_is_no_allocation_decision():
    """Two pools with one ledger state and different histories of stamps
    digest and snapshot alike; a pool rebuilt from a snapshot stamps what it
    holds."""
    a, b = KVPagePool(12, 8, reserved=1), KVPagePool(12, 8, reserved=1)
    a.alloc("x", 3)
    b.alloc("x", 1), b.alloc("x", 1), b.alloc("x", 1)
    assert a.stamp("x") != b.stamp("x")
    assert a.digest() == b.digest() and a.snapshot() == b.snapshot()
    assert "stamp" not in " ".join(a.snapshot())
    c = KVPagePool.from_snapshot(a.snapshot(), 12, 8, reserved=1)
    assert c.digest() == a.digest() and c.stamp("x") > 0


# -- Histogram.observe_n ----------------------------------------------------

def _state(h):
    return (h.count, h.total, h.min, h.max, list(h._samples), h._stride)


@pytest.mark.parametrize("max_samples", [4, 16, 512])
def test_observe_n_is_n_calls_of_observe(max_samples):
    """Across the reservoir's thinning, from any count: equal count, total
    (the calls' own rounding), min, max, retained samples and stride."""
    rng = np.random.default_rng(max_samples)
    once, each = Histogram(max_samples), Histogram(max_samples)
    for _ in range(120):
        v = float(rng.random())
        n = int(rng.choice([0, 1, 2, 3, 7, 64, 500, 1300]))
        once.observe_n(v, n)
        for _ in range(n):
            each.observe(v)
        assert _state(once) == _state(each)
    assert once._stride > 1 and once.summary() == each.summary()


def test_metrics_observe_takes_a_count():
    m, ref = ServingMetrics(), ServingMetrics()
    m.observe("tok_latency_s", 0.25, 5)
    m.observe("tok_latency_s", 0.5)
    m.observe("tok_latency_s", 9.0, 0)
    for v in (0.25,) * 5 + (0.5,):
        ref.hist["tok_latency_s"].observe(v)
    assert _state(m.hist["tok_latency_s"]) == _state(
        ref.hist["tok_latency_s"])
    # a class that emitted nothing makes no series, as no call made none
    m.observe_class("itl_s", "chat", 0.1, 0)
    m.observe_class("itl_s", None, 0.1, 3)
    assert not m.classes()
    m.observe_class("itl_s", "chat", 0.1, 3)
    assert m.hist["itl_s{class=chat}"].count == 3


# -- the table mirror, held to a row built from scratch ---------------------
# The HOST's path is under test, so the two programs are stand-ins (numpy, no
# model): the decode one checks what it is HANDED against the ledger before it
# answers, and keeps the device's carry as the real one does. The real
# programs' tokens under preemption, replay and recovery are the goldens of
# tests/test_serving.py, test_checkpoint.py and the family files.

PAGE, CHUNK, PPS, SLOTS, PAGES, K = 8, 16, 10, 3, 17, 4


class Interleaved(ServingEngine):
    """The long-context layout's ledger (ids round-robin over two shards),
    without a mesh: rows 0..17 are a bijection of the ids."""
    _pool_layout = "interleaved"
    _pool_sp_ranks = 2


def _plain():
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(n_layers=1),
                              dtype=jnp.float32)
    return cfg, llama.init_params(jax.random.key(0), cfg)


def _ring():
    cfg = window_moe.WindowMoEConfig.tiny(n_layers=4)
    return cfg, window_moe.init_params(jax.random.key(0), cfg)


def _state_family():
    cfg = hybrid_ssm.HybridSSMConfig.tiny(n_layers=1)
    return cfg, hybrid_ssm.init_params(jax.random.key(0), cfg)


# family -> (model, engine class, prefix cache)
FAMILIES = {"plain": (_plain, ServingEngine, True),
            "interleaved": (_plain, Interleaved, False),
            "ring": (_ring, ServingEngine, False),
            "state": (_state_family, ServingEngine, False)}


def _token_at(pos):
    """The stand-ins' token for the position it is written at."""
    return pos % 251 + 1


def _scratch_rows(eng):
    """[(slot, the row built from the ledger now)] of the decoding slots."""
    return [(slot, eng._device_bt_row(req.rid, slot))
            for slot, req in enumerate(eng.sched.slots)
            if req is not None and req.state is RequestState.ACTIVE]


def _stand_in_programs(eng):
    """Replace ``eng``'s two programs. Returns the list the decode stand-in
    appends the number of live rows it was handed to, a dispatch."""
    extra = len(eng._family.counters)
    handed = []

    def step(params, token, pos, pool, bt, limits):
        token, pos, bt, limits = (np.asarray(a) for a in
                                  (token, pos, bt, limits))
        # what the program is handed IS the ledger's rows and the mirrors
        live = _scratch_rows(eng)
        assert [s for s, _ in live] == np.flatnonzero(limits).tolist()
        for slot, row in live:
            np.testing.assert_array_equal(bt[slot], row)
        np.testing.assert_array_equal(bt, eng._bt)
        np.testing.assert_array_equal(pos, eng._pos)
        np.testing.assert_array_equal(token, eng._token)
        handed.append(len(live))
        # a token is a function of its position; a row stops at its limit,
        # or behind the first EOS it emits (the device freezes it there)
        toks = _token_at(pos[None] + np.arange(1, K + 1)[:, None])
        ends = (toks == eng.eos_id).argmax(axis=0) + 1
        took = np.where((toks == eng.eos_id).any(axis=0),
                        np.minimum(limits, ends), limits)
        kept = toks[np.maximum(took - 1, 0), np.arange(len(pos))]
        slab = np.concatenate([toks, np.zeros((extra, len(pos)))])
        return (jnp.asarray(slab, jnp.int32),
                jnp.asarray(np.where(took > 0, kept, token), jnp.int32),
                jnp.asarray(pos + took, jnp.int32), pool)

    def chunk(params, toks, start, n_eff, pool, row):
        slot, req = eng._oldest_prefilling()
        np.testing.assert_array_equal(np.asarray(row),
                                      eng._device_bt_row(req.rid, slot))
        return jnp.asarray(_token_at(int(n_eff)), jnp.int32), pool

    eng._step, eng._chunk_step = step, chunk
    return handed


def _hold_mirror_to_scratch(eng):
    """Every seated decoding slot's row is the row built from the ledger
    now; every other slot's row is parked."""
    live = dict(_scratch_rows(eng))
    for slot in range(eng.num_slots):
        if slot in live:
            np.testing.assert_array_equal(
                eng._bt[slot], live[slot], err_msg=f"slot {slot} at step "
                f"{eng._steps}")
            if eng._slot_owned:
                assert eng._bt[slot, -1] == 1 + slot * (eng._ring or 1)
        else:
            assert not eng._bt[slot].any(), (slot, eng._bt[slot])


def _engine(family, **kw):
    model, cls, cache = FAMILIES[family]
    cfg, params = model()
    eng = cls(params, cfg, num_slots=SLOTS, page_size=PAGE, num_pages=PAGES,
              pages_per_seq=PPS, prefill_chunk=CHUNK, decode_horizon=K,
              prefix_cache=cache, **kw)
    assert eng._bt.shape == (SLOTS, PPS + (family in ("ring", "state")))
    return eng, cfg, _stand_in_programs(eng)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_table_mirror_is_the_ledgers_rows_after_every_step(family):
    """Seating, growth across page boundaries (pages of 8, dispatches of 4
    tokens), a dry pool's preemption, a victim taken in the middle of its
    prefill, (the plain family, which alone allows it) a prefix-cache
    adoption with a copy-on-write, finishes and re-seats: the decode program
    is handed the from-scratch rows at every dispatch, the mirror is those
    rows after every step, and not every row a dispatch looked at was built
    again."""
    eng, cfg, handed = _engine(family)
    cache = eng.prefix_cache is not None
    rng = np.random.default_rng(7)
    prompt = lambda n: rng.integers(1, cfg.vocab_size, n)      # noqa: E731
    shared = prompt(16)                 # two whole pages: a whole-prompt hit
    eng.submit(prompt(26), 38)          # 8 pages at its end
    eng.submit(prompt(18), 34)          # 7
    long_one = eng.submit(prompt(42), 10)   # three chunks, 7 pages
    todo = ["victim", "share", "adopt", "reseat"]
    state_of = lambda rid: next(                               # noqa: E731
        (r.state for r in eng.sched.slots if r is not None and r.rid == rid),
        None)
    for _ in range(200):
        slot, req = eng._oldest_prefilling()
        if "victim" in todo and req is not None and req.rid == long_one \
                and req.prefill_cursor > 0:
            # the long prompt is mid-prefill (its next chunk launched ahead):
            # take it as a victim
            eng._preempt(slot)
            todo.remove("victim")
        if "share" in todo and eng.metrics.counters["dispatches"] >= 4:
            first = eng.submit(shared, 26)
            todo.remove("share")
        if todo[:1] == ["adopt"] and (
                not cache or state_of(first) is RequestState.ACTIVE):
            # the same prompt while its first holder still decodes: adopted
            # whole, its last page copied before the final chunk writes it
            if cache:
                eng.submit(shared, 17)
            todo.remove("adopt")
        if "reseat" in todo and eng.metrics.counters["requests_finished"]:
            eng.submit(prompt(9), 21)   # into a slot somebody finished in
            todo.remove("reseat")
        busy = eng.step()
        _hold_mirror_to_scratch(eng)
        if not busy:
            break
    c = eng.metrics.counters
    assert eng.sched.idle and c["requests_finished"] == 5 + cache
    assert c["preemptions"] >= 2 and not todo   # the victim and a dry pool
    assert c["prefills"] > c["requests_finished"]      # somebody re-seated
    if cache:
        assert c["prefix_hits"] >= 1 and c["cow_copies"] >= 1
    # growth crossed page boundaries and the mechanism engaged: with pages
    # of 8 a row meets a boundary in every other dispatch of 4, and the
    # others cost a compare
    assert 0 < c["table_rows_rebuilt"] < c["table_rows_checked"]
    assert c["table_rows_checked"] == sum(handed)
    assert c["host_syncs"] <= c["dispatches"] == len(handed)
    eng.alloc.check()


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_soak_of_arrivals_never_hands_over_a_stale_row(family, seed):
    """Sixty requests of random lengths through three slots and a pool that
    runs dry again and again, a third of them repeats of an earlier prompt
    (adopted where the family has a prefix cache), under a journal: the
    stand-in checks every hand-over, and a row is built again only for a
    page taken (a ``grow`` record each) or a seat taken."""
    from triton_dist_tpu.serving.journal import ControlJournal
    journal = ControlJournal()
    eng, cfg, handed = _engine(family, journal=journal)
    rng = np.random.default_rng(seed)
    prompts, left, step = [], 60, 0
    while left or not eng.sched.idle:
        for _ in range(min(left, int(rng.integers(0, 3)))):
            if prompts and rng.random() < 0.33:
                p = prompts[int(rng.integers(len(prompts)))]
            else:
                p = rng.integers(1, cfg.vocab_size, int(rng.integers(3, 49)))
                prompts.append(p)
            eng.submit(p, int(rng.integers(1, PPS * PAGE - len(p))))
            left -= 1
        eng.step()
        _hold_mirror_to_scratch(eng)
        step += 1
        assert step < 3000
    c = eng.metrics.counters
    assert c["requests_finished"] == 60 and c["preemptions"] > 0
    assert 0 < c["table_rows_rebuilt"] < c["table_rows_checked"]
    # a row is looked at again for a page taken or a seat taken, no more
    grows = sum(e["kind"] == "grow" for e in journal.entries)
    assert grows <= c["table_rows_rebuilt"] <= grows + c["prefills"]
    eng.alloc.check()


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_restored_engine_builds_every_row_again(family):
    """A restore rebuilds the ledger, whose clock starts over: the same
    requests come back to the same slots under stamps the OLD ledger may
    have given, over rows the restore parked. Parking forgets what a slot's
    row was mirrored from, so every row is built again."""
    from triton_dist_tpu.serving import checkpoint as ckpt_mod
    from triton_dist_tpu.serving.journal import ControlJournal
    journal = ControlJournal()
    eng, cfg, handed = _engine(family, journal=journal)
    rng = np.random.default_rng(3)
    for n in (20, 12, 30):
        eng.submit(rng.integers(1, cfg.vocab_size, n), 40)
    for i in range(200):
        if i in (8, 14):
            assert any(eng._bt_seen)
            ckpt_mod.restore(eng, None, journal)     # from the journal alone
            assert not eng._bt.any() and eng._bt_seen == [None] * SLOTS
        busy = eng.step()
        _hold_mirror_to_scratch(eng)
        if not busy:
            break
    assert eng.sched.idle and eng.metrics.counters["restores"] == 2
    assert len(eng._finished) == 3


@pytest.mark.parametrize("eos", [None, 25, 40])
def test_a_slots_column_is_committed_up_to_the_token_that_ends_it(eos):
    """``_reconcile`` takes a slot's column of the slab once: what a request
    is served is the token-by-token rule (``Request.done``: the budget, then
    EOS), whichever of a dispatch's four tokens ends it, and every counter
    that was bumped a token reads what it read."""
    eng, cfg, _ = _engine("interleaved", eos_id=eos)
    rng = np.random.default_rng(5)
    want = {}
    for _ in range(24):
        n, budget = int(rng.integers(3, 40)), int(rng.integers(1, 39))
        rid = eng.submit(rng.integers(1, cfg.vocab_size, n), budget)
        # position p's token, from the prompt's end, until it ends the request
        toks = []
        for p in range(n, n + budget):
            toks.append(int(_token_at(p)))
            if toks[-1] == eos:
                break
        want[rid] = toks
    served = eng.run(max_steps=600)
    assert served == want
    if eos is not None:
        assert any(t[-1] == eos for t in want.values())
    c, h = eng.metrics.counters, eng.metrics.hist
    assert c["tokens_generated"] >= sum(map(len, want.values()))  # restarts
    assert h["tok_latency_s"].count == c["tokens_generated"] - c["prefills"]
