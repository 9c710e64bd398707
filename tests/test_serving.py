"""Serving-runtime tests (ISSUE 2): allocator + scheduler invariants, the
chunk program's pages against the contiguous reference cache, and the
headline end-to-end property — a contended continuous-batching trace (with
forced preemptions) produces per-request tokens BIT-IDENTICAL to decoding
each request alone."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TEST_WORLD, serve_noting_victims  # noqa: F401
from triton_dist_tpu.models.llama import (LlamaConfig, decode_step, generate,
                                          init_kv_cache, init_page_pool,
                                          init_params, prefill,
                                          prefill_chunk_paged)
from triton_dist_tpu.serving import (ContinuousBatchingScheduler, KVPagePool,
                                     PageLedgerError, Request, ServingEngine,
                                     programs)

pytestmark = pytest.mark.serving


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------

def test_pool_no_double_allocation():
    """A page id is owned by at most one sequence; alloc is all-or-nothing;
    reserved ids are never handed out; frees return exactly what was
    owned."""
    pool = KVPagePool(num_pages=8, page_size=16, reserved=1)
    a = pool.alloc("a", 3)
    b = pool.alloc("b", 4)
    assert a is not None and b is not None
    assert 0 not in a + b                      # reserved page never leaves
    assert len(set(a) | set(b)) == 7           # disjoint ownership
    assert pool.free_pages == 0
    assert pool.alloc("c", 1) is None          # dry: all-or-nothing None
    assert not pool.holds("c")
    assert pool.free_seq("a") == 3
    got = pool.alloc("c", 2)
    assert got is not None and set(got) <= set(a)   # recycled, still unique
    assert set(got).isdisjoint(pool.pages_of("b"))
    with pytest.raises(AssertionError):        # double free is a bug, loudly
        pool._free.append(got[0])
        pool.free_seq("c")


def test_pool_ensure_growth_math():
    pool = KVPagePool(num_pages=6, page_size=8, reserved=1)
    assert pool.ensure("s", 1) and len(pool.pages_of("s")) == 1
    assert pool.ensure("s", 8) and len(pool.pages_of("s")) == 1   # no-op
    assert pool.ensure("s", 9) and len(pool.pages_of("s")) == 2
    assert pool.ensure("s", 40) and len(pool.pages_of("s")) == 5  # 5*8=40
    assert not pool.ensure("s", 41)            # pool is 5 usable pages
    assert len(pool.pages_of("s")) == 5        # failed ensure changed nothing
    row = pool.block_table_row("s", pages_per_seq=8)
    assert len(row) == 8 and row[5:] == [0, 0, 0]


def test_pool_free_tail_partial_fill_invariants():
    """The mid-prefill preemption primitive hardened (ISSUE 6): a
    partially-filled slot keeps exactly its first ``keep`` pages in
    allocation order, the freed tail is reusable, out-of-range keeps are
    loud, and a second tail-free of already-freed pages is a detected
    double free, not silent free-list corruption."""
    pool = KVPagePool(num_pages=10, page_size=8, reserved=1)
    got = pool.alloc("s", 6)
    assert got is not None
    assert pool.free_tail("s", keep=2) == 4
    assert pool.pages_of("s") == got[:2]       # filled prefix, exact order
    assert pool.free_pages == 7
    assert pool.free_tail("s", keep=2) == 0    # idempotent no-op tail
    with pytest.raises(PageLedgerError):       # keep > owned: loud
        pool.free_tail("s", keep=3)
    with pytest.raises(PageLedgerError):
        pool.free_tail("s", keep=-1)
    # keep=0 drops ownership entirely (full-restart preemption)
    assert pool.free_tail("s", keep=0) == 2
    assert not pool.holds("s")
    assert pool.free_pages == 9
    # double free through either path is a PageLedgerError (an
    # AssertionError subclass, so it still fails python -O-less asserts)
    pool2 = KVPagePool(num_pages=6, page_size=8, reserved=1)
    mine = pool2.alloc("t", 3)
    pool2._free.append(mine[-1])               # simulate ledger corruption
    with pytest.raises(PageLedgerError, match="double free"):
        pool2.free_tail("t", keep=0)
    pool3 = KVPagePool(num_pages=6, page_size=8, reserved=1)
    mine = pool3.alloc("u", 2)
    pool3._free.append(mine[0])
    with pytest.raises(PageLedgerError, match="double free"):
        pool3.free_seq("u")


def test_pool_scratch_pages_never_migrate():
    """Migration preconditions (ISSUE 6): reserved scratch pages and
    foreign pages are refused loudly; owned non-reserved pages pass."""
    pool = KVPagePool(num_pages=8, page_size=8, reserved=2)
    a = pool.alloc("a", 3)
    pool.alloc("b", 2)
    pool.check_migratable("a", a)              # the happy path
    with pytest.raises(PageLedgerError, match="scratch"):
        pool.check_migratable("a", [0])
    with pytest.raises(PageLedgerError, match="scratch"):
        pool.check_migratable("a", [1])        # every reserved id, not just 0
    with pytest.raises(PageLedgerError, match="foreign"):
        pool.check_migratable("a", pool.pages_of("b")[:1])
    with pytest.raises(PageLedgerError, match="foreign"):
        pool.check_migratable("nobody", [a[0]])


def test_pool_landed_row_exposes_prefix_only():
    """Signal-gated block-table patching: a row exposes the landed PREFIX
    of a sequence's pages — a hole means everything after it stays hidden
    (pages are positional), and the fill id pads the rest."""
    pool = KVPagePool(num_pages=10, page_size=8, reserved=1)
    got = pool.alloc("s", 4)
    assert pool.landed_row("s", set(), 6) == [0] * 6
    assert pool.landed_row("s", set(got), 6) == got + [0, 0]
    # a hole at position 1 hides pages 2 and 3 even though they landed
    holey = {got[0], got[2], got[3]}
    assert pool.landed_row("s", holey, 6) == [got[0]] + [0] * 5
    assert pool.landed_row("s", set(got[:2]), 6, fill=9) == got[:2] + [9] * 4
    assert pool.landed_row("unknown", {1, 2}, 4) == [0] * 4


def test_pool_deterministic_replay():
    """Same alloc/free trace => same page assignment (LIFO free list)."""
    def trace():
        p = KVPagePool(12, 8, reserved=1)
        out = [tuple(p.alloc("x", 3)), tuple(p.alloc("y", 2))]
        p.free_seq("x")
        out.append(tuple(p.alloc("z", 4)))
        return out
    assert trace() == trace()


# ---------------------------------------------------------------------------
# ONE pool contract (ISSUE 12): SP-sharded AND migratable, same ledger
# ---------------------------------------------------------------------------

def test_pool_sp_padding_never_migratable():
    """An SP-aware pool pads the DEVICE array to a multiple of sp_ranks
    but the allocator never hands the pad ids out — and
    ``check_migratable`` refuses them loudly, so no migration can land
    KV in a padding slot no block table will ever expose."""
    pool = KVPagePool(num_pages=10, page_size=8, reserved=1, sp_ranks=4)
    assert pool.device_pages == 12                  # 10 padded up to 12
    got = pool.alloc("a", 3)
    pool.check_migratable("a", got)                 # real pages pass
    for pad_id in (10, 11):                         # the two padding slots
        with pytest.raises(PageLedgerError, match="padding"):
            pool.check_migratable("a", [pad_id])
    with pytest.raises(PageLedgerError, match="padding"):
        pool.check_migratable("a", [12])            # out of range entirely
    # the shard map covers the PADDED range: every device page has a home
    assert [pool.page_shard(p) for p in (0, 2, 3, 5, 6, 8, 9, 11)] == \
        [0, 0, 1, 1, 2, 2, 3, 3]
    with pytest.raises(PageLedgerError, match="outside"):
        pool.page_shard(12)


@pytest.mark.parametrize("sp_ranks", [1, 2, 4])
def test_pool_digest_layout_independent_across_sp_ranks(sp_ranks):
    """The FNV-1a control digest hashes page OWNERSHIP, not device
    layout: the same alloc / landed_row / free_tail trace digests
    identically at every sp_ranks — which is what lets the sharded
    engine's replicated-decision guard and the disagg journal compare
    digests across differently-laid-out pools."""
    def trace(n_sp):
        p = KVPagePool(num_pages=10, page_size=8, reserved=1,
                       sp_ranks=n_sp)
        a = p.alloc("a", 4)
        p.alloc("b", 2)
        out = [p.digest()]
        assert p.landed_row("a", set(a[:2]), 6) == a[:2] + [0] * 4
        p.free_tail("a", keep=2)
        p.free_seq("b")
        out.append(p.digest())
        out.append(p.snapshot())
        return out
    assert trace(sp_ranks) == trace(1)


def test_pool_free_tail_after_cross_mesh_migration():
    """The disagg-on-sharded handoff shape (compose.py): pages migrate
    from a prefill-side ledger into an SP-sharded decode-side ledger,
    then the SOURCE is partially reclaimed mid-prefill (free_tail). Both
    ledgers must stay audit-clean and the destination's landed_row must
    expose exactly the migrated prefix."""
    src = KVPagePool(num_pages=10, page_size=8, reserved=1, sp_ranks=2)
    dst = KVPagePool(num_pages=10, page_size=8, reserved=1, sp_ranks=4)
    s = src.alloc("r", 4)
    d = dst.alloc("r", 4)                   # remote reservation at admit
    src.check_migratable("r", s[:2])        # chunk 0 finalized 2 pages
    dst.check_migratable("r", d[:2])
    covered = set(d[:2])                    # ...and their signals fired
    assert dst.landed_row("r", covered, 6) == d[:2] + [0] * 4
    # mid-prefill preemption on the source: keep the 2 migrated pages
    freed = src.free_tail("r", keep=2)
    assert freed == 2 and src.pages_of("r") == s[:2]
    src.check()
    dst.check()
    # the already-migrated pages are still re-sendable (retry rung)...
    src.check_migratable("r", s[:2])
    # ...but the freed tail is not: those ids went back to the free list
    with pytest.raises(PageLedgerError, match="foreign"):
        src.check_migratable("r", s[2:])
    # full reclaim on finish frees the reservation on both sides
    src.free_seq("r")
    dst.free_seq("r")
    assert src.free_pages == src.num_pages - src.reserved
    assert dst.landed_row("r", covered, 6) == [0] * 6


# ---------------------------------------------------------------------------
# scheduler invariants
# ---------------------------------------------------------------------------

def _req(rid, plen=4, mnt=4):
    return Request(rid=rid, prompt=tuple(range(1, plen + 1)),
                   max_new_tokens=mnt)


def test_scheduler_fifo_head_of_line():
    """Admission is strict FIFO: a head request that does not fit blocks
    later (smaller) requests — no starvation-by-reordering."""
    s = ContinuousBatchingScheduler(num_slots=2)
    big, small = _req(0, plen=100), _req(1, plen=2)
    s.submit(big)
    s.submit(small)
    fits = lambda r: len(r.prompt) <= 10        # noqa: E731
    assert s.admissible(fits) is None           # big blocks the line
    slot, req = s.admissible(lambda r: True)
    assert req is big
    s.activate(slot, req)
    slot2, req2 = s.admissible(fits)
    assert req2 is small and slot2 != slot


def test_scheduler_victim_is_youngest_and_requeues_front():
    s = ContinuousBatchingScheduler(num_slots=3)
    reqs = [_req(i) for i in range(3)]
    for r in reqs:
        s.submit(r)
        slot, q = s.admissible(lambda _: True)
        s.activate(slot, q)
    assert s.pick_victim() == 2                      # youngest ticket
    assert s.pick_victim(exclude_slot=2) == 1        # next youngest
    victim = s.slots[2]
    victim.generated.extend([7, 8, 9])
    s.evict(2)
    assert s.queue[0] is victim                      # requeued at the FRONT
    assert victim.generated == [] and victim.preemptions == 1
    assert s.slots[2] is None
    # re-admission goes back into the freed slot before anything else
    slot, q = s.admissible(lambda _: True)
    assert q is victim and slot == 2


# ---------------------------------------------------------------------------
# the page pool's layout
# ---------------------------------------------------------------------------

def test_page_pool_shapes_match_kernel_contract():
    cfg = LlamaConfig.tiny()
    pool = init_page_pool(cfg, num_pages=5, page_size=8)
    assert pool["k"].shape == (cfg.n_layers, 5, cfg.n_kv_heads, 8,
                               cfg.head_dim)
    assert pool["k"].dtype == cfg.dtype


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    cfg = dataclasses.replace(LlamaConfig.tiny(n_layers=2),
                              dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    return cfg, params


def _mk_requests(cfg, n, seed=0, mnt_lo=2, mnt_hi=10):
    rng = np.random.RandomState(seed)
    return [(list(rng.randint(1, cfg.vocab_size,
                              size=int(rng.randint(3, 20)))),
             int(rng.randint(mnt_lo, mnt_hi)))
            for _ in range(n)]


@pytest.mark.quick
def test_engine_smoke(tiny_model):
    """Quick-tier smoke: a few requests through a 2-slot engine finish,
    tokens match the contiguous prefill+decode_step reference, and the
    metrics JSON line carries the counters."""
    import json

    cfg, params = tiny_model
    reqs = _mk_requests(cfg, 3, seed=1, mnt_hi=6)

    ref_prefill = jax.jit(lambda p, t, c: prefill(p, t, cfg, c))
    ref_step = jax.jit(lambda p, tk, ps, c: decode_step(p, tk, ps, cfg, c))

    def reference(prompt, mnt):
        cache = init_kv_cache(cfg, 1, 32)
        logits, cache = ref_prefill(params, jnp.asarray([prompt], jnp.int32),
                                    cache)
        toks = [int(jnp.argmax(logits[0]))]
        pos = len(prompt)
        while len(toks) < mnt:
            logits, cache = ref_step(
                params, jnp.asarray([toks[-1]], jnp.int32),
                jnp.int32(pos), cache)
            toks.append(int(jnp.argmax(logits[0])))
            pos += 1
        return toks

    eng = ServingEngine(params, cfg, num_slots=2, page_size=8, num_pages=16,
                        pages_per_seq=4, prefill_chunk=8)
    rids = [eng.submit(p, m) for p, m in reqs]
    res = eng.run(max_steps=500)
    for rid, (p, m) in zip(rids, reqs):
        assert res[rid] == reference(p, m), f"rid {rid} diverged"
    snap = json.loads(eng.metrics.json_line())
    assert snap["requests_finished"] == len(reqs)
    assert snap["tokens_generated"] == sum(m for _, m in reqs)
    assert snap["ttft_s"]["count"] == len(reqs)


@pytest.fixture(scope="module")
def golden_trace(tiny_model):
    """Golden for the acceptance trace: 8 requests through ONE
    single-slot engine with an ample pool — requests run strictly one at
    a time (per-request single-batch decoding, horizon 1)."""
    cfg, params = tiny_model
    reqs = _mk_requests(cfg, 8, seed=2, mnt_lo=6, mnt_hi=14)
    gold_eng = ServingEngine(params, cfg, num_slots=1, page_size=8,
                             num_pages=8, pages_per_seq=4, prefill_chunk=8)
    gold_rids = [gold_eng.submit(p, m) for p, m in reqs]
    gold = gold_eng.run(max_steps=5000)
    assert gold_eng.metrics.counters["preemptions"] == 0
    return reqs, gold_rids, gold


@pytest.mark.parametrize("horizon", [1, 4])
@pytest.mark.parametrize("chunk", [4, 8, 16])  # under a page; a page; few
def test_trace_bit_identical_under_preemption(tiny_model, golden_trace,
                                              chunk, horizon):
    """The acceptance trace: 8 requests through a 4-slot engine with a
    pool small enough to force preemptions. Every request's tokens must be
    bit-identical to the same request decoded in a single-batch engine
    with an uncontended pool — including every preempted request, at
    every decode horizon (K=1 per-token semantics, K=4 scanned) and chunk
    size: under a page (a prompt of 3-19 tokens takes one to five chunks
    and a chunk ends mid-page), a page, and the longest prompt in two
    dispatches. The golden's own engine ran chunks of 8."""
    cfg, params = tiny_model
    reqs, gold_rids, gold = golden_trace

    # contended: 4 slots, pool deliberately too small for 4 long tails —
    # growth must preempt. Arrivals staggered so admission interleaves
    # with decode of earlier requests.
    eng = ServingEngine(params, cfg, num_slots=4, page_size=8, num_pages=7,
                        pages_per_seq=4, decode_horizon=horizon,
                        prefill_chunk=chunk)
    arrivals = [(i // 2, p, m) for i, (p, m) in enumerate(reqs)]
    res = eng.run(max_steps=5000, arrivals=arrivals)
    snap = eng.metrics.snapshot()
    assert snap["requests_finished"] == len(reqs)
    assert snap["preemptions"] >= 1, "trace was meant to force preemption"
    # every finished request went through the chunk program at least once
    # (admissions preempted at cursor 0 may dispatch no chunk)
    assert snap["prefill_chunks"] >= len(reqs)
    assert eng.compile_stats["prefill_chunk_compiles"] == 1

    preempted = [r for r in eng._finished if r.preemptions > 0]
    assert preempted, "no request actually lost work to preemption"
    rids = sorted(res)
    assert rids == sorted(gold_rids)
    for rid, grid_ in zip(rids, sorted(gold_rids)):
        assert res[rid] == gold[grid_], f"request {rid} not bit-identical"
    # spot-check: the preempted ones specifically
    for r in preempted:
        assert res[r.rid] == gold[r.rid]
    if horizon > 1:
        # the multi-token win: far fewer host dispatches than tokens, and
        # quiet dispatches re-upload nothing
        decode_toks = (snap["tokens_generated"] - snap["prefills"])
        assert snap["dispatches"] < decode_toks
        assert snap["host_syncs"] <= snap["dispatches"]


@pytest.mark.parametrize("bad", [None, 0, -8, 8.0, True, "8"])
def test_prefill_chunk_is_a_positive_int(tiny_model, bad):
    """``prefill_chunk`` is a compiled shape like ``page_size``: there is no
    second admission path for ``None`` (or anything else) to select."""
    cfg, params = tiny_model
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingEngine(params, cfg, prefill_chunk=bad)


def test_engine_refuses_impossible_request(tiny_model):
    cfg, params = tiny_model
    eng = ServingEngine(params, cfg, num_slots=2, page_size=8, num_pages=4,
                        pages_per_seq=8, prefill_chunk=8)
    with pytest.raises(AssertionError):
        eng.submit(list(range(1, 50)), 8)      # needs 7 pages, pool has 4


def test_truncated_run_returns_only_finished(tiny_model):
    """run() with a small step budget must return ONLY finished requests —
    no None placeholders for work still in flight — and a follow-up run()
    finishes the rest."""
    cfg, params = tiny_model
    eng = ServingEngine(params, cfg, num_slots=2, page_size=8, num_pages=16,
                        pages_per_seq=4, prefill_chunk=8)
    reqs = _mk_requests(cfg, 5, seed=4, mnt_lo=6, mnt_hi=9)
    rids = [eng.submit(p, m) for p, m in reqs]
    res = eng.run(max_steps=3)
    assert all(v is not None for v in res.values())
    assert set(res) == {r.rid for r in eng._finished}
    assert len(res) < len(reqs)                # budget was really too small
    res2 = eng.run(max_steps=5000)
    assert set(res2) == set(rids)
    assert all(len(res2[r]) == m for r, (_, m) in zip(rids, reqs))


REF_PROMPT_LENS = (3, 8, 13, 19)   # under a chunk of 4 ... over two pages
REF_NEW_TOKENS = 6


@pytest.fixture(scope="module")
def reference_tokens(tiny_model):
    """Fixed prompts and what ``models.llama.generate`` makes of each on
    the contiguous cache: a golden that shares no paged code with the
    engine."""
    cfg, params = tiny_model
    rng = np.random.RandomState(7)
    prompts = [[int(t) for t in rng.randint(1, cfg.vocab_size, size=n)]
               for n in REF_PROMPT_LENS]
    gen = jax.jit(lambda p, t: generate(p, t, cfg, REF_NEW_TOKENS,
                                        max_seq=32))
    return prompts, [
        [int(t) for t in gen(params, jnp.asarray([pr], jnp.int32))[0]]
        for pr in prompts]


@pytest.mark.parametrize("chunk", [4, 8, 24])   # under a page; a page; three
def test_chunk_size_never_changes_tokens(tiny_model, reference_tokens, chunk):
    """The chunk size is a shape, never a result: whatever it is (24 is
    more than any prompt here, still under ``pages_per_seq`` x page), the
    engine's tokens are the contiguous reference's."""
    cfg, params = tiny_model
    prompts, want = reference_tokens
    eng = ServingEngine(params, cfg, num_slots=2, page_size=8, num_pages=16,
                        pages_per_seq=4, prefill_chunk=chunk)
    rids = [eng.submit(p, REF_NEW_TOKENS) for p in prompts]
    res = eng.run(max_steps=2000)
    assert [res[r] for r in rids] == want


def test_chunk_writes_the_rows_the_reference_cache_holds(tiny_model):
    """The pool's layout contract: after ``prefill_chunk_paged`` over a
    prompt (three chunks, the last one short, pages out of order), the K/V
    rows read back through the block table are ``models.llama.prefill``'s
    contiguous cache, position for position, and the first token is its
    logits' argmax."""
    cfg, params = tiny_model
    ps, C, n = 8, 8, 19
    prompt = np.random.RandomState(12).randint(1, cfg.vocab_size, size=n)
    logits, cache = jax.jit(lambda p, t, c: prefill(p, t, cfg, c))(
        params, jnp.asarray(prompt[None], jnp.int32),
        init_kv_cache(cfg, 1, 24))

    pool = init_page_pool(cfg, num_pages=6, page_size=ps)
    row = np.array([4, 1, 3, 0], np.int32)     # page 0: the scratch fill
    chunk = jax.jit(lambda p, t, s, m, pg, bt: prefill_chunk_paged(
        p, t, s, m, cfg, pg, bt))
    for start in range(0, n, C):
        toks = np.zeros(C, np.int32)
        part = prompt[start:start + C]
        toks[:len(part)] = part
        tok, pool = chunk(params, jnp.asarray(toks), jnp.int32(start),
                          jnp.int32(n), pool, jnp.asarray(row))
    assert int(tok) == int(jnp.argmax(logits[0]))
    for name in ("k", "v"):
        got = pool[name][:, row[:3]]           # [L, 3, Hkv, ps, Dh]
        got = got.transpose(0, 2, 1, 3, 4).reshape(
            cfg.n_layers, cfg.n_kv_heads, 3 * ps, cfg.head_dim)
        np.testing.assert_allclose(
            np.asarray(got[:, :, :n]), np.asarray(cache[name][:, 0, :, :n]),
            atol=2e-3, rtol=2e-3)
        # the pages the table does not name were never written
        assert not np.asarray(pool[name][:, [2, 5]]).any()


@pytest.mark.quick
@pytest.mark.parametrize("horizon", [1, 4], ids=["k1", "k4"])
def test_compile_count_guard(tiny_model, monkeypatch, horizon):
    """A trace with 10 DISTINCT prompt lengths builds and compiles exactly
    two programs: ONE decode step (the scanned K=4 program is another
    program than the K=1 one, and as shape-stable) and ONE chunk program
    (start offset and prompt length are its runtime scalars). A second
    engine of the shape enters ``jax.jit`` for neither."""
    cfg, params = tiny_model
    real_jit = jax.jit
    made = []

    def counting_jit(fun, *a, **k):
        made.append(fun)
        return real_jit(fun, *a, **k)

    monkeypatch.setattr(jax, "jit", counting_jit)
    monkeypatch.setattr(programs, "_MEMO", {})
    mk = lambda: ServingEngine(                             # noqa: E731
        params, cfg, num_slots=4, page_size=8, num_pages=32, pages_per_seq=4,
        decode_horizon=horizon, prefill_chunk=8)
    eng = mk()
    rng = np.random.RandomState(3)
    arrivals = []
    for i, plen in enumerate(range(3, 23, 2)):  # 10 distinct prompt lengths
        prompt = [int(t) for t in rng.randint(1, cfg.vocab_size, size=plen)]
        arrivals.append((i, prompt, int(rng.randint(2, 8))))
    res = eng.run(max_steps=5000, arrivals=arrivals)
    assert len(res) == 10
    assert eng.compile_stats == {"decode_compiles": 1,
                                 "prefill_chunk_compiles": 1,
                                 "params_relaid_bytes": 0,
                                 "params_relaid_leaves": []}
    # the jit-entry hook agrees (pallas interpret mode jits its own internal
    # wrappers — not ours)
    ours = lambda: [f for f in made if "engine_programs" in getattr(  # noqa: E731
        f, "__qualname__", "")]
    assert len(ours()) == 2
    del made[:]
    twin = mk()
    assert twin._step is eng._step and twin._chunk_step is eng._chunk_step
    assert not made


def test_eos_truncation_multistep(tiny_model):
    """With eos_id set, generation stops right after the first EOS even
    mid-scan at K=4 — the frozen-lane mask must not let a finished row
    keep decoding (or keep writing KV) inside the horizon."""
    cfg, params = tiny_model
    prompt, _ = _mk_requests(cfg, 1, seed=5)[0]
    mnt = 12
    base = ServingEngine(params, cfg, num_slots=1, page_size=8, num_pages=8,
                         pages_per_seq=8, decode_horizon=4, prefill_chunk=8)
    rid = base.submit(prompt, mnt)
    toks = base.run(max_steps=1000)[rid]
    assert len(toks) == mnt

    eos = toks[len(toks) // 2]                 # a token we KNOW gets emitted
    first = toks.index(eos)
    eng = ServingEngine(params, cfg, num_slots=1, page_size=8, num_pages=8,
                        pages_per_seq=8, decode_horizon=4, eos_id=eos,
                        prefill_chunk=8)
    rid2 = eng.submit(prompt, mnt)
    got = eng.run(max_steps=1000)[rid2]
    assert got == toks[:first + 1]             # truncated AT the EOS


@pytest.mark.parametrize("horizon", [1, 4])
def test_dispatch_count_bound(tiny_model, horizon):
    """One request alone: dispatches == ceil(decode_tokens / K) exactly,
    and host re-uploads stay rare (device state is authoritative between
    control-plane changes)."""
    cfg, params = tiny_model
    prompt, _ = _mk_requests(cfg, 1, seed=6)[0]
    mnt = 13
    eng = ServingEngine(params, cfg, num_slots=1, page_size=8, num_pages=8,
                        pages_per_seq=8, decode_horizon=horizon,
                        prefill_chunk=8)
    rid = eng.submit(prompt, mnt)
    res = eng.run(max_steps=1000)
    assert len(res[rid]) == mnt
    c = eng.metrics.counters
    decode_tokens = mnt - 1                    # token 0 comes from prefill
    assert c["dispatches"] == -(-decode_tokens // horizon)
    assert c["host_syncs"] <= c["dispatches"]
    if horizon == 1:
        # only admission + page growth dirty the mirrors; the steady-state
        # dispatch re-uploads nothing
        assert c["host_syncs"] < c["dispatches"]


# ---------------------------------------------------------------------------
# chunked paged prefill (ISSUE 5)
# ---------------------------------------------------------------------------

@pytest.mark.quick
def test_mid_prefill_preemption_resumes_at_cursor(tiny_model):
    """A request preempted MID-prefill (cursor between chunks) resumes at
    its chunk cursor, not from chunk 0: pages already filled survive the
    eviction (free_tail keeps them) and total chunk dispatches equal the
    zero-rework count ceil(10/4) + ceil(40/4) = 13. A from-scratch restart
    would dispatch strictly more. Tokens stay bit-identical to solo."""
    cfg, params = tiny_model
    rng = np.random.RandomState(11)
    pa = [int(t) for t in rng.randint(1, cfg.vocab_size, size=10)]
    pb = [int(t) for t in rng.randint(1, cfg.vocab_size, size=40)]

    def solo(prompt, mnt):
        e = ServingEngine(params, cfg, num_slots=1, page_size=8, num_pages=8,
                          pages_per_seq=7, prefill_chunk=4)
        rid = e.submit(prompt, mnt)
        return e.run(max_steps=2000)[rid]

    gold_a, gold_b = solo(pa, 21), solo(pb, 2)

    # contended: B's 40-token prompt needs 5 pages mid-prefill while A's
    # decode tail grows — the pool (6 usable pages) forces a mid-prefill
    # eviction of B, whose cursor + filled pages must survive.
    eng = ServingEngine(params, cfg, num_slots=2, page_size=8, num_pages=7,
                        pages_per_seq=6, prefill_chunk=4)
    ra = eng.submit(pa, 21)
    rb = eng.submit(pb, 2)
    res = eng.run(max_steps=4000)
    snap = eng.metrics.snapshot()
    assert snap["preemptions"] >= 1
    assert res[ra] == gold_a and res[rb] == gold_b
    assert snap["prefill_chunks"] == 13        # ceil(10/4)+ceil(40/4): no rework


@pytest.mark.quick
def test_admit_no_host_argmax(tiny_model):
    """Admission never argmaxes on host (the chunk program samples on
    device): every request is one ``prefills`` count and at least one
    chunk, and host syncs only re-upload control-plane state."""
    cfg, params = tiny_model
    eng = ServingEngine(params, cfg, num_slots=2, page_size=8, num_pages=16,
                        pages_per_seq=4, prefill_chunk=8)
    reqs = _mk_requests(cfg, 4, seed=9, mnt_lo=2, mnt_hi=6)
    rids = [eng.submit(p, m) for p, m in reqs]
    res = eng.run(max_steps=2000)
    assert all(rid in res for rid in rids)
    snap = eng.metrics.snapshot()
    assert snap["prefills"] == len(reqs)
    assert snap["prefill_chunks"] >= len(reqs)
    # sampling stays on device: syncs only re-upload control-plane state
    assert snap["host_syncs"] <= snap["dispatches"]


@pytest.mark.quick
def test_decode_stall_bounded_by_chunk(tiny_model):
    """The headline scheduling property: no single step prefills more
    than ``prefill_chunk`` prompt tokens (running decodes stall for at most
    one chunk), and the bound is the chunk's, not the trace's: twice the
    chunk lets a step take more."""
    cfg, params = tiny_model
    C = 8
    reqs = _mk_requests(cfg, 8, seed=10, mnt_lo=2, mnt_hi=5)
    assert max(len(p) for p, _ in reqs) > C    # trace must exceed the chunk

    def run(chunk):
        eng = ServingEngine(params, cfg, num_slots=2, page_size=8,
                            num_pages=16, pages_per_seq=4,
                            prefill_chunk=chunk)
        arrivals = [(i, p, m) for i, (p, m) in enumerate(reqs)]
        res = eng.run(max_steps=4000, arrivals=arrivals)
        assert len(res) == len(reqs)
        return eng.metrics.snapshot()["step_prefill_tokens"]["max"]

    assert run(C) <= C                         # stall bounded by the chunk
    assert C < run(2 * C) <= 2 * C


# ---------------------------------------------------------------------------
# the step's phases (ISSUE 35): spans and exact totals from one primitive
# ---------------------------------------------------------------------------

STEP_PHASES = {"admit", "chunk_prep", "chunk_wait", "grow", "sync",
               "dispatch", "decode_wait", "reconcile", "post"}


class SpanRecorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps every span
    opened as (name, ids, the names of the spans open around it)."""

    def __init__(self):
        self.opened = []
        self._stack = []

    def __call__(self, name, **ids):
        rec = self

        class Span:
            def __enter__(self):
                rec.opened.append((name, ids, tuple(rec._stack)))
                rec._stack.append(name)

            def __exit__(self, *exc):
                assert rec._stack.pop() == name

        return Span()


@pytest.fixture(scope="module", params=[1, 4], ids=["k1", "k4"])
def phased_run(request, tiny_model, reference_tokens):
    """The reference prompts through an engine whose span factory is a
    recorder, polled once while idle before and after."""
    cfg, params = tiny_model
    prompts, want = reference_tokens
    eng = ServingEngine(params, cfg, num_slots=2, page_size=8, num_pages=16,
                        pages_per_seq=4, prefill_chunk=8,
                        decode_horizon=request.param)
    rec = eng.metrics.span = SpanRecorder()
    assert eng.step() is False                 # idle: nothing to do
    idle_spans, rec.opened = rec.opened, []
    idle_counts = {k: h.count for k, h in eng.metrics.hist.items()}
    rids = [eng.submit(p, REF_NEW_TOKENS) for p in prompts]
    res = eng.run(max_steps=2000)
    return {"eng": eng, "spans": rec.opened, "idle_spans": idle_spans,
            "idle_counts": idle_counts, "rids": rids,
            "tokens": [res[r] for r in rids], "want": want}


def test_idle_step_observes_nothing(phased_run):
    """A poll that finds the engine idle opens its two spans and leaves
    every histogram as it was: the rag cell's empty steps must not dilute
    a mean."""
    assert [s[0] for s in phased_run["idle_spans"]] == ["engine.step",
                                                        "engine.admit"]
    assert not any(phased_run["idle_counts"].values())


def test_step_opens_exactly_the_named_phases(phased_run):
    """Every span is ``engine.submit`` (with its rid), ``engine.step`` or
    one of the nine phases; a phase opens directly inside ``engine.step``
    and carries its step number; the chunk's two carry the rid being
    prefilled and its cursor; and the tokens served under the recorder are
    the contiguous reference's."""
    spans = phased_run["spans"]
    names = {name for name, _, _ in spans}
    assert names == {"engine.submit", "engine.step"} | {
        "engine." + p for p in STEP_PHASES}
    submits = [ids for name, ids, _ in spans if name == "engine.submit"]
    assert [ids["rid"] for ids in submits] == phased_run["rids"]
    step_no = None
    for name, ids, around in spans:
        if name == "engine.submit":
            assert around == ()
        elif name == "engine.step":
            assert around == ()
            step_no = ids["step"]
        else:
            assert around == ("engine.step",), (name, around)
            assert ids["step"] == step_no
    chunks = [(name, ids) for name, ids, _ in spans
              if name in ("engine.chunk_prep", "engine.chunk_wait")]
    length = dict(zip(phased_run["rids"], REF_PROMPT_LENS))
    by_rid, awaited = {}, []
    chunk_of = lambda ids: (ids["rid"], ids["cursor"])      # noqa: E731
    for i, (name, ids) in enumerate(chunks):
        if name == "engine.chunk_wait":
            # a wait follows the prep of the same chunk, and only a last one:
            # in the same step, or in the one before where the chunk was
            # launched ahead, behind that step's decode dispatch
            prep_name, prep_ids = chunks[i - 1]
            assert i and prep_name == "engine.chunk_prep"
            assert chunk_of(prep_ids) == chunk_of(ids)
            assert ids["step"] - prep_ids["step"] in (0, 1)
            assert ids["cursor"] + 8 >= length[ids["rid"]]
            awaited.append(ids["rid"])
            continue
        assert ids["cursor"] % 8 == 0          # whole chunks of 8 so far
        by_rid.setdefault(ids["rid"], []).append(ids["cursor"])
        if ids["cursor"] + 8 >= length[ids["rid"]]:     # the prompt's last
            assert chunks[i + 1][0] == "engine.chunk_wait"
            assert chunk_of(chunks[i + 1][1]) == chunk_of(ids)
    # a prompt's chunks are its cursor advancing from 0; one wait a prompt
    for rid, n in length.items():
        assert by_rid[rid] == list(range(0, n, 8))
    assert sorted(awaited) == sorted(phased_run["rids"])
    assert phased_run["tokens"] == phased_run["want"]


def test_phase_totals_tile_the_step(phased_run):
    """Host work and waits add up to ``step_s.total`` within 2 %: what a
    step does outside every phase is a handful of statements."""
    hist = phased_run["eng"].metrics.hist
    parts = sum(hist[f"phase_{p}_s"].total for p in STEP_PHASES)
    whole = hist["step_s"].total
    assert 0.98 * whole <= parts <= whole
    counters = phased_run["eng"].metrics.counters
    assert hist["phase_dispatch_s"].count == counters["dispatches"]
    assert hist["phase_decode_wait_s"].count == counters["dispatches"]
    assert hist["phase_reconcile_s"].count == counters["dispatches"]
    assert hist["phase_chunk_prep_s"].count == counters["prefill_chunks"]
    # only a prompt's last chunk is waited for: 1 + 1 + 2 + 3 chunks, 4 waits
    assert counters["chunks_not_awaited"] == 3
    assert hist["phase_chunk_wait_s"].count == len(phased_run["rids"]) \
        == counters["prefill_chunks"] - counters["chunks_not_awaited"]
    snap = phased_run["eng"].metrics.snapshot()
    assert snap["chunks_not_awaited"] == 3
    assert hist["phase_sync_s"].count == counters["host_syncs"]
    assert hist["phase_admit_s"].count == hist["step_s"].count


def test_older_timers_are_differences_of_the_phase_stamps(phased_run):
    """``step_device_s`` is dispatch + decode wait and the prep of a chunk
    launched ahead between them, ``prefill_stall_s`` a chunk's prep where the
    step itself launched it (+ its wait where it is a prompt's last),
    ``decode_stall_s`` the step up to the chunk's launch or token,
    ``step_host_s`` the rest of a dispatching step before ``post``: the names
    and counts of before, from the same stamps. Every prep lies in exactly
    one of ``step_device_s`` and ``prefill_stall_s``."""
    m = phased_run["eng"].metrics
    h = m.hist
    total = lambda *names: sum(h[n].total for n in names)   # noqa: E731
    near = lambda a, b: abs(a - b) <= 0.02 * max(a, b)      # noqa: E731
    assert h["step_device_s"].count == h["step_host_s"].count \
        == m.counters["dispatches"]
    assert 0 < m.counters["chunks_prelaunched"] < m.counters["prefill_chunks"]
    device = total("phase_dispatch_s", "phase_decode_wait_s")
    assert device <= h["step_device_s"].total \
        <= 1.02 * (device + h["phase_chunk_prep_s"].total)
    assert h["prefill_stall_s"].count == m.counters["prefill_chunks"]
    assert h["phase_chunk_wait_s"].total <= h["prefill_stall_s"].total \
        <= 1.02 * total("phase_chunk_prep_s", "phase_chunk_wait_s")
    assert near(total("step_device_s", "prefill_stall_s"),
                total("phase_dispatch_s", "phase_decode_wait_s",
                      "phase_chunk_prep_s", "phase_chunk_wait_s"))
    assert h["decode_stall_s"].count == h["step_s"].count
    assert near(total("decode_stall_s", "step_device_s"),
                total("phase_admit_s", "phase_chunk_prep_s",
                      "phase_chunk_wait_s", "phase_dispatch_s",
                      "phase_decode_wait_s"))
    # host + device is a dispatching step without its closing phase (at
    # k4 one step of this run is a chunk alone, and is in neither)
    both = total("step_host_s", "step_device_s")
    rest = h["step_s"].total - h["phase_post_s"].total
    assert both <= rest
    if h["step_s"].count == m.counters["dispatches"]:
        assert near(both, rest)


def test_phase_without_a_histogram_is_a_span_alone():
    from triton_dist_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics()
    rec = m.span = SpanRecorder()
    before = {k: h.count for k, h in m.hist.items()}
    with m.phase("submit", rid=3) as ph:
        pass
    assert rec.opened == [("engine.submit", {"rid": 3}, ())]
    assert ph.t1 >= ph.t0
    assert {k: h.count for k, h in m.hist.items()} == before
    with m.phase("grow", step=0) as ph:
        ph.drop()
    assert m.hist["phase_grow_s"].count == 0
    with m.phase("grow", step=1):
        pass
    assert m.hist["phase_grow_s"].count == 1


# ---------------------------------------------------------------------------
# a chunk whose token nobody reads is not waited for (ISSUE 36)
# ---------------------------------------------------------------------------

class Poison:
    """Stands in for the token of a chunk that is not its prompt's last:
    any way of bringing it to the host raises."""

    def _read(self, *a, **kw):
        raise AssertionError("a non-final chunk's token was read on the host")

    __int__ = __index__ = __array__ = _read


class Watched:
    """An engine whose two programs' results are watched: ``events`` keeps
    every launch and every host read, in order. A non-final chunk's token is
    ``Poison``; a final chunk's and the decode slab come through proxies
    that note the read and hand over the real value."""

    def __init__(self, eng):
        self.eng, self.events = eng, []
        chunk_step, step = eng._chunk_step, eng._step
        events = self.events

        class Read:
            def __init__(self, what, value):
                self.what, self.value = what, value

            def __int__(self):
                events.append("read " + self.what)
                return int(self.value)

            def __array__(self, dtype=None, copy=None):
                events.append("read " + self.what)
                return np.asarray(self.value, dtype)

        def watched_chunk(params, toks, start, n_eff, pool, row):
            req = eng._oldest_prefilling()[1]
            tok, pool = chunk_step(params, toks, start, n_eff, pool, row)
            last = int(n_eff) >= len(req.prompt)
            events.append("final chunk" if last else "chunk")
            return (Read("token", tok) if last else Poison()), pool

        def watched_step(*args):
            toks, *rest = step(*args)
            events.append("decode")
            return Read("slab", toks), *rest

        eng._chunk_step, eng._step = watched_chunk, watched_step

    def step_events(self):
        """One ``step()``: the events it added."""
        n = len(self.events)
        assert self.eng.step()
        return self.events[n:]


def _chunk4_engine(tiny_model, horizon, **kw):
    cfg, params = tiny_model
    kw = {"num_slots": 2, "num_pages": 16, "pages_per_seq": 4, **kw}
    return ServingEngine(params, cfg, page_size=8, prefill_chunk=4,
                         decode_horizon=horizon, **kw)


HORIZONS = pytest.mark.parametrize("horizon", [1, 4], ids=["k1", "k4"])


@HORIZONS
def test_a_non_final_chunks_token_is_never_read(tiny_model, reference_tokens,
                                                horizon):
    """With every non-final chunk's token poisoned the engine serves the
    contiguous reference's tokens: it reads one token a prompt, the last
    chunk's, through the same wrapper."""
    prompts, want = reference_tokens
    w = Watched(_chunk4_engine(tiny_model, horizon))
    rids = [w.eng.submit(p, REF_NEW_TOKENS) for p in prompts]
    res = w.eng.run(max_steps=2000)
    assert [res[r] for r in rids] == want
    chunks = sum(-(-n // 4) for n in REF_PROMPT_LENS)
    assert w.events.count("chunk") == chunks - len(prompts) \
        == w.eng.metrics.counters["chunks_not_awaited"]
    assert w.events.count("final chunk") == w.events.count("read token") \
        == len(prompts)
    # a final chunk's token is read before the next DECODE program is
    # launched (at once where the step launched the chunk itself; after the
    # slab where it was launched ahead, behind the step before's dispatch)
    for i, e in enumerate(w.events):
        if e == "final chunk":
            rest = w.events[i + 1:]
            assert "read token" in rest[:rest.index("decode")]


@HORIZONS
def test_decode_is_launched_behind_a_running_chunk(tiny_model,
                                                   reference_tokens, horizon):
    """A row decoding while a five-chunk prompt prefills: in a step with a
    non-final chunk the decode program is launched before the step's first
    (and only) host read, the slab's."""
    prompts, want = reference_tokens
    w = Watched(_chunk4_engine(tiny_model, horizon))
    short = w.eng.submit(prompts[0], REF_NEW_TOKENS)          # one chunk
    assert w.step_events()[:2] == ["final chunk", "read token"]
    long = w.eng.submit(prompts[3], REF_NEW_TOKENS)           # 19: five
    # (the chunk behind the decode program is the NEXT step's, launched ahead)
    assert w.step_events() == ["chunk", "decode", "chunk", "read slab"]
    res = w.eng.run(max_steps=2000)
    assert [res[short], res[long]] == [want[0], want[3]]
    assert "read token" not in w.events[2:w.events.index("final chunk", 2)]


@HORIZONS
def test_a_chunk_alone_leaves_its_step_in_flight(tiny_model, reference_tokens,
                                                 horizon):
    """A four-chunk prompt into an empty engine: three steps that are a
    chunk alone, each returning with nothing read, then the last chunk's
    token and the first dispatch."""
    prompts, want = reference_tokens
    w = Watched(_chunk4_engine(tiny_model, horizon))
    rid = w.eng.submit(prompts[2], REF_NEW_TOKENS)            # 13 tokens
    for cursor in (4, 8, 12):
        assert w.step_events() == ["chunk"]
        assert w.eng.sched.slots[0].prefill_cursor == cursor
    assert w.eng._steps == 3                   # the clock ran all the same
    assert w.step_events() == ["final chunk", "read token", "decode",
                               "read slab"]
    assert w.eng.run(max_steps=2000)[rid] == want[2]
    h = w.eng.metrics.hist
    assert (h["phase_chunk_prep_s"].count, h["phase_chunk_wait_s"].count) \
        == (4, 1)


@pytest.fixture(scope="module")
def victim_case(tiny_model):
    """A 10-token prompt decoding 21 tokens beside a 40-token prompt in a
    pool of 6 pages, and the contiguous reference's tokens for both."""
    cfg, params = tiny_model
    rng = np.random.RandomState(11)
    reqs = [([int(t) for t in rng.randint(1, cfg.vocab_size, size=n)], m)
            for n, m in ((10, 21), (40, 2))]
    want = [[int(t) for t in jax.jit(
        lambda p, t, m=m: generate(p, t, cfg, m, max_seq=48))(
            params, jnp.asarray([prompt], jnp.int32))[0]]
        for prompt, m in reqs]
    return reqs, want


@HORIZONS
def test_a_victim_whose_chunk_was_not_awaited(tiny_model, victim_case,
                                              horizon):
    """``_grow`` preempts the prefilling slot in the very step that commits
    a chunk nobody waited for, launched in the step BEFORE behind its decode
    dispatch (ISSUE 40): the victim keeps its filled pages and its cursor,
    the tokens are the reference's, and the control plane's digest after
    every step is that of a run which fences every chunk."""
    reqs, want = victim_case

    def serve(fence):
        eng = _chunk4_engine(tiny_model, horizon, num_pages=7,
                             pages_per_seq=6)
        return serve_noting_victims(eng, reqs, fence)

    tokens, digests, hit, counters, hit_ahead = serve(fence=False)
    assert hit and hit_ahead and counters["preemptions"] >= 1
    assert counters["prefill_chunks"] == 13    # ceil(10/4) + ceil(40/4)
    assert 0 < counters["chunks_prelaunched"] <= 13
    assert tokens == want
    fenced = serve(fence=True)
    assert fenced[:2] == (tokens, digests) and fenced[4]


# ---------------------------------------------------------------------------
# the next step's chunk is launched behind this step's decode dispatch,
# before the host waits for the slab (ISSUE 40)
# ---------------------------------------------------------------------------

def test_a_chunks_walked_and_edge_pages_are_counted_at_its_commit(
        tiny_model, victim_case):
    """``chunk_walk_pages`` / ``chunk_walk_edge_pages`` move at a chunk's
    commit alone, by what ``ops.flash_decode.chunk_walk_counts`` (the plan the
    kernel walks by) says of the chunk's cursor and length, times the layers
    that walk. Chunks of 4 over pages of 8: the 10-token prompt's three
    chunks walk 1 + 1 + 2 pages a layer, of which 1 + 1 + 1 are edge (the
    third chunk's first page is interior: both its rows see all 8 keys)."""
    from triton_dist_tpu.ops.flash_decode import chunk_walk_counts
    reqs, want = victim_case
    eng = _chunk4_engine(tiny_model, 4, pages_per_seq=6)
    cfg, c = eng.cfg, eng.metrics.counters
    assert eng._chunk_walks == ((cfg.n_layers, 64, None),)
    chunks, commit = [], eng._commit_chunk

    def recording(slot, req, tok0, n_eff, row):
        chunks.append((req.prefill_cursor, n_eff - req.prefill_cursor))
        before = c["chunk_walk_pages"], c["chunk_walk_edge_pages"]
        commit(slot, req, tok0, n_eff, row)
        pages, edge = chunk_walk_counts(*chunks[-1], 4, 64, 8, None, 6)
        assert (c["chunk_walk_pages"] - before[0],
                c["chunk_walk_edge_pages"] - before[1]) == (
                    cfg.n_layers * pages, cfg.n_layers * edge)

    eng._commit_chunk = recording
    a = eng.submit(*reqs[0])
    res = eng.run(max_steps=2000)
    assert chunks == [(0, 4), (4, 4), (8, 2)] and res[a] == want[0]
    assert (c["chunk_walk_pages"], c["chunk_walk_edge_pages"]) == (
        4 * cfg.n_layers, 3 * cfg.n_layers)
    b = eng.submit(*reqs[1])                   # 40 tokens: ten chunks of 4
    assert eng.run(max_steps=2000)[b] == want[1] and len(chunks) == 13
    snap = eng.metrics.snapshot()
    assert 0 < snap["chunk_walk_edge_pages"] < snap["chunk_walk_pages"]


@HORIZONS
def test_the_next_chunk_is_launched_before_the_slab_is_read(
        tiny_model, victim_case, horizon):
    """With a request mid-prefill at dispatch time a step launches the decode
    program, then the chunk the NEXT step would launch, and only then reads
    the slab; the next step launches no chunk of its own. Nothing of the
    launched chunk is committed until that step: the cursor, the counters and
    the digest are a step's of the parent's order. Tokens are the
    reference's, and ``chunks_prelaunched <= prefill_chunks`` throughout."""
    reqs, want = victim_case
    w = Watched(_chunk4_engine(tiny_model, horizon, pages_per_seq=6))
    eng, c = w.eng, w.eng.metrics.counters
    a = eng.submit(*reqs[0])       # 10 tokens; decodes 21: 5 dispatches at K=4
    while "read token" not in w.events:
        w.step_events()
    assert eng._ahead is None                  # nobody else was prefilling
    b = eng.submit(*reqs[1])                   # 40 tokens: ten chunks of 4
    # its first chunk at the usual place (nothing was in flight), its second
    # behind the decode program
    assert w.step_events() == ["chunk", "decode", "chunk", "read slab"]
    assert eng.sched.slots[1].prefill_cursor == 4 and eng._ahead.start == 4
    assert (c["prefill_chunks"], c["chunks_prelaunched"]) == (4, 0)
    for cursor in (8, 12):
        assert w.step_events() == ["decode", "chunk", "read slab"]
        assert eng.sched.slots[1].prefill_cursor == cursor
        assert c["chunks_prelaunched"] <= c["prefill_chunks"]
    assert c["chunks_prelaunched"] == 2
    h = eng.metrics.hist                       # one prep a chunk, as before
    assert h["phase_chunk_prep_s"].count == c["prefill_chunks"] + 1
    res = eng.run(max_steps=2000)
    assert [res[a], res[b]] == want
    snap = eng.metrics.snapshot()
    assert 0 < snap["chunks_prelaunched"] <= snap["prefill_chunks"] == 13
    assert h["phase_chunk_prep_s"].count == 13 and eng._ahead is None
    # the 40-token prompt's last chunk was launched at the usual place or
    # ahead: either way its token was read before the next decode launch
    assert w.events.count("final chunk") == w.events.count("read token") == 2


@HORIZONS
def test_a_checkpoint_with_a_final_chunk_unread(tiny_model, reference_tokens,
                                                horizon):
    """A prompt's LAST chunk launched ahead holds a token the next step
    reads. ``checkpoint()`` taken between the two steps is host-only and sees
    an uncommitted launch: restored in place, the engine drops it, re-prefills
    and serves the reference's tokens; ``_preempt`` of the owner drops it too."""
    from triton_dist_tpu.serving import ControlJournal
    from triton_dist_tpu.serving import checkpoint as ckpt_mod
    prompts, want = reference_tokens
    journal = ControlJournal()
    w = Watched(_chunk4_engine(tiny_model, horizon, journal=journal))
    eng = w.eng
    short = eng.submit(prompts[0], REF_NEW_TOKENS)            # one chunk
    assert w.step_events()[:2] == ["final chunk", "read token"]
    two = eng.submit(prompts[1], REF_NEW_TOKENS)              # 8: two chunks
    assert w.step_events() == ["chunk", "decode", "final chunk", "read slab"]
    assert eng._ahead is not None and "read token" not in w.events[2:]
    ck = eng.checkpoint()
    chunks = eng.metrics.counters["prefill_chunks"]
    ckpt_mod.restore(eng, ck, journal)
    assert eng._ahead is None
    res = eng.run(max_steps=2000)
    assert [res[short], res[two]] == want[:2]
    # the dropped launch was never committed: both prompts prefilled again
    # (at K=4 the one-chunk prompt had finished before the checkpoint)
    assert eng.metrics.counters["prefill_chunks"] \
        == chunks + 2 + (horizon == 1)
    # a victim with a launch in flight: the launch goes with the seat
    third = eng.submit(prompts[0], REF_NEW_TOKENS)
    w.step_events()
    long = eng.submit(prompts[3], REF_NEW_TOKENS)             # 19: five
    assert w.step_events() == ["chunk", "decode", "chunk", "read slab"]
    slot = next(i for i, r in enumerate(eng.sched.slots)
                if r is not None and r.rid == long)
    eng._preempt(slot)
    assert eng._ahead is None
    res = eng.run(max_steps=2000)
    assert [res[third], res[long]] == [want[0], want[3]]


@HORIZONS
def test_nothing_is_launched_ahead_under_a_stall_budget(tiny_model,
                                                        reference_tokens,
                                                        horizon):
    """While a class with a ``stall_budget`` decodes, the next chunk's size
    depends on who still decodes after the slab: it is sized and launched at
    the usual place, and nothing is launched ahead."""
    from triton_dist_tpu.serving import SLOPolicy
    prompts, want = reference_tokens
    w = Watched(_chunk4_engine(
        tiny_model, horizon, slo=SLOPolicy.chat_batch(chat_stall_budget=2)))
    eng, c = w.eng, w.eng.metrics.counters
    chat = eng.submit(prompts[0], REF_NEW_TOKENS, tenant="c0", cls="chat")
    assert w.step_events()[:2] == ["final chunk", "read token"]
    batch = eng.submit(prompts[3], REF_NEW_TOKENS, tenant="b0", cls="batch")
    while any(r is not None and r.rid == chat for r in eng.sched.slots):
        assert w.step_events() == ["chunk", "decode", "read slab"]
        assert eng._ahead is None
    assert c["chunks_prelaunched"] == 0 and c["chunk_shrinks"] > 0
    res = eng.run(max_steps=2000)
    assert [res[chat], res[batch]] == [want[0], want[3]]


# ---------------------------------------------------------------------------
# a program belongs to a configuration and a shape (serving/programs.py)
# ---------------------------------------------------------------------------

def count_traces(monkeypatch):
    """``traces``: how often each of the two model functions under the
    engines' programs has been entered (a trace enters it; a dispatch of a
    compiled program does not)."""
    traces = {"decode_multistep_paged": 0, "prefill_chunk_paged": 0}

    def counting(name, fn):
        def counted(*a, **kw):
            traces[name] += 1
            return fn(*a, **kw)
        return counted

    for name in traces:
        monkeypatch.setattr(programs, name,
                            counting(name, getattr(programs, name)))
    return traces


def test_a_second_engine_of_a_shape_traces_nothing(tiny_model,
                                                   reference_tokens,
                                                   own_programs, monkeypatch):
    """Two engines of one configuration and one shape hold the SAME two
    programs: the second serves its first request without entering either
    model function again, and serves the first one's tokens."""
    prompts, want = reference_tokens
    own_programs()
    traces = count_traces(monkeypatch)
    first = _chunk4_engine(tiny_model, 4)
    rid = first.submit(prompts[1], REF_NEW_TOKENS)
    assert first.run(max_steps=500)[rid] == want[1]
    seen = dict(traces)
    assert all(seen.values())
    twin = _chunk4_engine(tiny_model, 4)
    assert twin._step is first._step
    assert twin._chunk_step is first._chunk_step
    assert twin.pool is not first.pool
    rid = twin.submit(prompts[1], REF_NEW_TOKENS)
    assert twin.run(max_steps=500)[rid] == want[1]
    assert traces == seen
    assert twin.compile_stats == first.compile_stats
    assert twin.compile_stats["decode_compiles"] == 1
    assert twin.compile_stats["prefill_chunk_compiles"] == 1


@pytest.mark.parametrize("differs", [
    {"num_pages": 17}, {"horizon": 4}, {"eos_id": 5},
    {"ffn": lambda h, p: jnp.zeros_like(h)}], ids=lambda kw: next(iter(kw)))
def test_an_engine_of_another_key_has_its_own_programs(tiny_model, differs):
    """One component of the key differs (a shape, the horizon, ``eos_id``, a
    hook): the engine gets programs of its own, and each of the two has
    compiled ONE decode and ONE chunk program after serving."""
    cfg, _ = tiny_model
    base = _chunk4_engine(tiny_model, 1)
    other = _chunk4_engine(tiny_model, **{"horizon": 1, **differs})
    assert other._step is not base._step
    assert other._chunk_step is not base._chunk_step
    for eng in (base, other):
        eng.submit(list(range(1, 7)), 3)
        eng.run(max_steps=200)
        stats = eng.compile_stats
        assert (stats["decode_compiles"],
                stats["prefill_chunk_compiles"]) == (1, 1)


def test_a_wrapped_program_stays_on_its_engine(tiny_model):
    """``Watched`` wraps the two attributes of ITS engine: the next engine of
    the shape gets the programs, not the wrappers."""
    w = Watched(_chunk4_engine(tiny_model, 4))
    plain = _chunk4_engine(tiny_model, 4)
    assert plain._step is not w.eng._step
    assert plain._chunk_step is not w.eng._chunk_step
    assert plain._step._cache_size() >= 0       # a ``jax.jit`` object
    assert _chunk4_engine(tiny_model, 4)._step is plain._step


def test_a_second_disagg_engine_of_a_shape_traces_nothing(tiny_model,
                                                          own_programs,
                                                          monkeypatch):
    """The disaggregated engine's three programs belong to the configuration,
    the mesh and the shapes too."""
    from triton_dist_tpu.serving.disagg import DisaggServingEngine
    from triton_dist_tpu.shmem.context import initialize_distributed
    cfg, params = tiny_model
    own_programs()
    traces = count_traces(monkeypatch)
    # (a context is its mesh: two contexts of one mesh are one key)
    mk = lambda: DisaggServingEngine(                       # noqa: E731
        params, cfg, num_slots=2, num_prefill_slots=2, page_size=8,
        num_pages=32, pages_per_seq=8, prefill_chunk=8,
        ctx=initialize_distributed(axis_names=("role",), mesh_shape=(2,)))
    prompt = list(range(1, 12))
    first = mk()
    rid = first.submit(prompt, 4)
    tokens = first.run(max_steps=500)[rid]
    seen = dict(traces)
    assert all(seen.values())
    twin = mk()
    assert (twin._chunk_step, twin._dec_step, twin._migrate) == (
        first._chunk_step, first._dec_step, first._migrate)
    rid = twin.submit(prompt, 4)
    assert twin.run(max_steps=500)[rid] == tokens
    assert traces == seen
    assert twin.compile_stats == first.compile_stats == {
        "prefill_chunk_compiles": 1, "decode_compiles": 1,
        "migrate_compiles": 1}
