"""Prefix cache (ISSUE 13): ref-counted copy-on-write KV pages, the
token-keyed radix index, LRU eviction, and the bit-identity contract.

THE contract: serving with ``prefix_cache=True`` is a pure OPTIMIZATION —
every request's tokens are bit-identical to the cache-off run of the same
trace, on the colocated engine and on the sharded engine at n∈{1,2,4},
including traces that force LRU eviction, growth-driven preemption, and
mid-prefill preemption of a request that adopted cached pages. Greedy
decode makes KV a pure function of the token prefix, so adopting a
cached page IS recomputing it; everything here checks that the ledger
mechanics (refcounts, COW, retention, eviction) never violate that.

Ledger invariants under test (kv_pool.py):
- a page's refcount never goes negative and a shared page is never freed
  or migrated while referenced;
- COW refuses sole-owned pages (in-place write is correct there) and
  never lets a writer touch a refcount>1 page;
- cached (refcount-0, index-retained) pages live on the LRU list, never
  the free list, and ``check()``/``digest()`` audit all of it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import N_REQUESTS, sharded_engine
from triton_dist_tpu.models.llama import LlamaConfig, init_params
from triton_dist_tpu.serving import (KVPagePool, PageLedgerError,
                                     PrefixCache, ReplicaPrefixIndex,
                                     ServingEngine)
from triton_dist_tpu.serving.scheduler import RequestState

pytestmark = [pytest.mark.prefix, pytest.mark.serving]

# conftest's N_REQUESTS of the template trace below is also the fewest that
# still preempt, hit AND evict (asserted)
MAX_STEPS = 100_000       # engine's own stall watchdog trips far earlier


# ------------------------------------------------------ pool refcount units
def test_pool_acquire_shared_page_never_freed_while_referenced():
    pool = KVPagePool(8, 8, reserved=1)
    pages = pool.alloc("a", 2)
    pool.acquire("b", pages)
    assert [pool.refcount(p) for p in pages] == [2, 2]
    pool.check()
    pool.free_seq("a")                    # b still reads these pages
    assert [pool.refcount(p) for p in pages] == [1, 1]
    assert all(p not in pool._free for p in pages)
    pool.check()
    pool.free_seq("b")                    # last reference → free list
    assert [pool.refcount(p) for p in pages] == [0, 0]
    assert pool.free_pages == 7
    pool.check()


def test_pool_acquire_refuses_free_and_duplicate_pages():
    pool = KVPagePool(8, 8, reserved=1)
    pages = pool.alloc("a", 1)
    with pytest.raises(PageLedgerError, match="no live KV"):
        pool.acquire("b", [pool._free[-1]])
    with pytest.raises(PageLedgerError, match="already holds"):
        pool.acquire("a", pages)
    # refused acquires mutated nothing
    assert pool.refcount(pages[0]) == 1
    pool.check()


def test_pool_release_underflow_is_loud():
    pool = KVPagePool(8, 8, reserved=1)
    (p,) = pool.alloc("a", 1)
    pool.free_seq("a")
    with pytest.raises(PageLedgerError, match="underflow"):
        pool._release_page("a", p)


def test_pool_cacheable_parks_on_lru_not_free_list():
    pool = KVPagePool(10, 8, reserved=1)
    pa = pool.alloc("a", 2)
    pb = pool.alloc("b", 1)
    for p in pa + pb:
        pool.mark_cacheable(p)
    pool.free_seq("a")
    pool.free_seq("b")
    # release order IS the LRU order (oldest first), free list untouched
    assert pool.lru_cached() == pa + pb
    assert pool.cached_pages == 3
    assert all(p not in pool._free for p in pa + pb)
    pool.check()
    # adoption revives a cached page off the LRU list
    pool.acquire("c", [pa[0]])
    assert pool.refcount(pa[0]) == 1 and pool.lru_cached() == pa[1:] + pb
    # uncache reclaims a cached page NOW, a referenced one only later
    assert pool.uncache(pa[1]) is True
    assert pool.uncache(pa[0]) is False   # still referenced by c
    pool.free_seq("c")
    assert pa[0] in pool._free            # retention mark was dropped
    pool.check()


def test_pool_mark_cacheable_refuses_free_pages():
    pool = KVPagePool(8, 8, reserved=1)
    with pytest.raises(PageLedgerError, match="free page"):
        pool.mark_cacheable(pool._free[-1])


def test_pool_cow_only_for_shared_pages():
    pool = KVPagePool(8, 8, reserved=1)
    pages = pool.alloc("a", 2)
    with pytest.raises(PageLedgerError, match="copy-on-write is only"):
        pool.cow_page("a", 0)             # sole-owned: write in place
    pool.acquire("b", pages)
    old, new = pool.cow_page("b", 1)
    assert old == pages[1] and new != old
    assert pool.refcount(old) == 1 and pool.refcount(new) == 1
    assert pool.pages_of("b") == [pages[0], new]
    assert pool.pages_of("a") == pages    # a's view untouched
    pool.check()


def test_pool_cow_dry_pool_returns_none():
    pool = KVPagePool(3, 8, reserved=1)   # 2 usable pages
    pages = pool.alloc("a", 2)
    pool.acquire("b", pages)
    assert pool.cow_page("b", 0) is None  # caller evicts/preempts
    assert pool.refcount(pages[0]) == 2   # nothing mutated
    pool.check()


def test_pool_migration_refuses_shared_pages():
    pool = KVPagePool(8, 8, reserved=1)
    pages = pool.alloc("a", 2)
    pool.check_migratable("a", pages)     # sole-owned: fine
    pool.acquire("b", pages)
    with pytest.raises(PageLedgerError, match="sole ownership"):
        pool.check_migratable("a", pages)


def test_pool_digest_and_snapshot_cover_cache_state():
    pool = KVPagePool(8, 8, reserved=1)
    pages = pool.alloc("a", 2)
    d0 = pool.digest()
    pool.mark_cacheable(pages[0])
    d1 = pool.digest()
    assert d1 != d0                       # retention mark folds in
    pool.free_seq("a")
    d2 = pool.digest()
    assert d2 != d1                       # cached LRU list folds in
    back = KVPagePool.from_snapshot(pool.snapshot(), 8, 8, 1)
    assert back.digest() == d2
    assert back.lru_cached() == pool.lru_cached()
    assert back._cacheable == pool._cacheable
    back.check()


# ---------------------------------------------------------- radix index units
def test_cache_match_insert_full_page_runs_only():
    pool = KVPagePool(10, 4, reserved=1)
    cache = PrefixCache(pool, 4)
    prompt = list(range(1, 11))           # 10 tokens = 2 full runs + 2
    pages = pool.alloc("a", 3)
    assert cache.insert(prompt, pages[:2]) == 2
    assert cache.match(prompt) == pages[:2]
    assert cache.match(prompt[:7]) == pages[:1]   # 1 full run of 4
    assert cache.match(prompt[:3]) == []          # no full run
    assert cache.match([9] + prompt[1:]) == []    # first run differs
    assert cache.indexed_pages == 2


def test_cache_insert_first_writer_wins():
    pool = KVPagePool(10, 4, reserved=1)
    cache = PrefixCache(pool, 4)
    prompt = list(range(1, 9))
    pa = pool.alloc("a", 2)
    pb = pool.alloc("b", 2)
    assert cache.insert(prompt, pa) == 2
    assert cache.insert(prompt, pb) == 0  # duplicate compute: not indexed
    assert cache.match(prompt) == pa
    # b's pages free normally at finish — never retained
    pool.free_seq("b")
    assert pool.cached_pages == 0 and pool.free_pages == 7


def test_cache_insert_refusals():
    pool = KVPagePool(10, 4, reserved=1)
    cache = PrefixCache(pool, 4)
    pages = pool.alloc("a", 3)
    with pytest.raises(PageLedgerError, match="full-page runs"):
        cache.insert([1, 2, 3, 4, 5], pages[:2])  # 5 tokens = 1 run
    cache.insert([1, 2, 3, 4], pages[:1])
    with pytest.raises(PageLedgerError, match="already indexed"):
        cache.insert([9, 9, 9, 9], pages[:1])     # same page, other run


def test_cache_evict_lru_order_and_subtrees():
    pool = KVPagePool(12, 4, reserved=1)
    cache = PrefixCache(pool, 4)
    # chain A: two runs deep; chain B: one run — released A-then-B, so
    # A's root is the LRU victim and its CHILD must leave with it
    pa = pool.alloc("a", 2)
    pb = pool.alloc("b", 1)
    cache.insert([1, 2, 3, 4, 5, 6, 7, 8], pa)
    cache.insert([9, 10, 11, 12], pb)
    pool.free_seq("a")
    pool.free_seq("b")
    assert cache.evictable == 3
    assert cache.evict(1) == 2            # victim + its child run
    assert cache.indexed_pages == 1
    assert cache.match([1, 2, 3, 4, 5, 6, 7, 8]) == []
    assert cache.match([9, 10, 11, 12]) == pb
    pool.check()
    # asking for more than exists reclaims what's there and reports it
    assert cache.evict(10) == 1
    assert cache.evictable == 0 and pool.free_pages == 11
    pool.check()


def test_cache_evict_referenced_subtree_page_frees_on_release():
    pool = KVPagePool(12, 4, reserved=1)
    cache = PrefixCache(pool, 4)
    pa = pool.alloc("a", 2)
    cache.insert([1, 2, 3, 4, 5, 6, 7, 8], pa)
    pool.acquire("r", pa)                 # a reader adopted both pages
    pool.free_seq("a")
    assert cache.evictable == 0           # refcount 1: nothing cached
    assert cache.evict(1) == 0
    pool.free_seq("r")
    # retention marks survived the failed evict → pages park cached
    assert pool.cached_pages == 2
    assert cache.evict(1) == 2
    assert pool.free_pages == 11
    pool.check()


def test_cache_clear_reclaims_everything():
    pool = KVPagePool(12, 4, reserved=1)
    cache = PrefixCache(pool, 4)
    pa = pool.alloc("a", 2)
    cache.insert([1, 2, 3, 4, 5, 6, 7, 8], pa)
    pool.free_seq("a")
    assert cache.clear() == 2
    assert cache.indexed_pages == 0 and pool.free_pages == 11
    pool.check()


def test_cache_snapshot_digest_tamper():
    from triton_dist_tpu.serving import checkpoint as ckpt_mod
    from triton_dist_tpu.serving.checkpoint import CheckpointIntegrityError

    pool = KVPagePool(12, 4, reserved=1)
    cache = PrefixCache(pool, 4)
    pa = pool.alloc("a", 2)
    cache.insert([1, 2, 3, 4, 5, 6, 7, 8], pa)
    snap, dig = cache.snapshot(), cache.digest()
    ckpt_mod.audit_prefix_snapshot(snap, dig)     # clean
    snap[0][2] = 99                               # tamper one page id
    with pytest.raises(CheckpointIntegrityError):
        ckpt_mod.audit_prefix_snapshot(snap, dig)


def test_replica_prefix_index_deepest_hit():
    ix = ReplicaPrefixIndex(4)
    ix.insert([1, 2, 3, 4, 5, 6, 7, 8], 0)
    ix.insert([1, 2, 3, 4, 9, 9, 9, 9], 2)        # shares run 0 — first
    depth, owner = ix.match([1, 2, 3, 4, 5, 6, 7, 8, 11])
    assert (depth, owner) == (2, 0)
    depth, owner = ix.match([1, 2, 3, 4, 9, 9, 9, 9])
    assert (depth, owner) == (2, 2)               # deepest hit wins
    assert ix.match([1, 2, 3, 4, 0, 0])[0] == 1   # partial: run-0 owner
    assert ix.match([5, 5, 5, 5]) == (0, None)
    ix.insert([1, 2, 3, 4], 3)                    # first-writer-wins
    assert ix.match([1, 2, 3, 4]) == (1, 0)


# ----------------------------------------------------- colocated bit-identity
@pytest.fixture(scope="module")
def tiny_model():
    cfg = dataclasses.replace(LlamaConfig.tiny(n_layers=2),
                              dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    return cfg, params


def _template_trace(vocab, n=N_REQUESTS, page_size=8, templates=3):
    """The acceptance trace: Zipf-ish template reuse so the cache actually
    fires — page-aligned shared prefixes + tiny unique tails, staggered
    arrivals, against a pool too small for the working set (forces both
    preemption and LRU eviction)."""
    rng = np.random.RandomState(77)
    tpls = [rng.randint(1, vocab, size=2 * page_size).tolist()
            for _ in range(templates)]
    out = []
    for i in range(n):
        t = int(rng.randint(0, templates))
        tail = rng.randint(1, vocab,
                           size=int(rng.randint(1, 5))).tolist()
        out.append((i // 2, tpls[t] + tail, int(rng.randint(4, 9))))
    return out


def _colocated(tiny_model, **kw):
    cfg, params = tiny_model
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 9)          # tight: forces preempt + evict
    kw.setdefault("pages_per_seq", 4)
    kw.setdefault("prefill_chunk", 8)
    return ServingEngine(params, cfg, **kw)


@pytest.fixture(scope="module")
def colocated_golden(tiny_model):
    cfg, _ = tiny_model
    eng = _colocated(tiny_model)
    res = eng.run(max_steps=MAX_STEPS,
                  arrivals=_template_trace(cfg.vocab_size))
    assert eng.metrics.counters["preemptions"] >= 1
    return res, eng.compile_stats


@pytest.mark.quick
def test_colocated_trace_bit_identical_cache_on(tiny_model,
                                                colocated_golden):
    """The acceptance trace, cache ON: template-sharing requests with
    forced preemption AND forced LRU eviction replay the cache-off run
    bit-for-bit, with zero extra compiled programs."""
    cfg, _ = tiny_model
    gold, gold_compiles = colocated_golden
    eng = _colocated(tiny_model, prefix_cache=True)
    res = eng.run(max_steps=MAX_STEPS,
                  arrivals=_template_trace(cfg.vocab_size))
    assert res == gold, "prefix cache changed tokens"
    c = eng.metrics.counters
    assert c["prefix_hits"] >= 1, "trace never hit the cache"
    assert c["prefix_evictions"] >= 1, "pool sizing no longer forces " \
                                       "eviction"
    assert c["preemptions"] >= 1
    assert eng.compile_stats == gold_compiles, \
        "the cache compiled extra programs"
    eng.alloc.check()
    # conservation: every indexed page is referenced or cached, never free
    for p in eng.prefix_cache._node_of:
        assert eng.alloc.refcount(p) > 0 or p in eng.alloc._cached


def test_colocated_whole_prompt_hit_cows_last_page(tiny_model):
    """An EXACT repeat prompt is a whole-prompt hit: the engine resumes at
    sp-1 (the final chunk recomputes only the on-device argmax), COWs the
    final adopted page when shared, and the tokens still match a cold
    engine's."""
    cfg, _ = tiny_model
    rng = np.random.RandomState(3)
    prompt = rng.randint(1, cfg.vocab_size, size=16).tolist()  # 2 pages
    cold = _colocated(tiny_model, num_pages=16, pages_per_seq=4)
    cold.submit(prompt, 4)
    gold = cold.run(max_steps=MAX_STEPS)
    eng = _colocated(tiny_model, num_pages=16, pages_per_seq=4,
                     prefix_cache=True)
    r0 = eng.submit(prompt, 4)
    first = eng.run(max_steps=MAX_STEPS)
    r1 = eng.submit(prompt, 4)            # identical prompt → whole hit
    second = eng.run(max_steps=MAX_STEPS)
    assert first[r0] == second[r1] == gold[next(iter(gold))]
    c = eng.metrics.counters
    assert c["prefix_hits"] == 1 and c["prefix_misses"] == 1
    # prompt is 16 tokens: the whole-prompt hit resumes at sp-1 = 15
    assert c["prefix_hit_tokens"] == 15
    # the adopted final page was cached (refcount 0) at adoption, so the
    # sole-owner fast path wrote in place — no COW needed
    assert c["cow_copies"] == 0
    eng.alloc.check()


def test_colocated_concurrent_whole_prompt_hits_cow(tiny_model):
    """TWO simultaneous whole-prompt hits on the same cached prefix: the
    second adopter shares the final page at refcount 2, so its sp-1
    rewrite MUST copy-on-write — and both requests still match the cold
    tokens."""
    cfg, _ = tiny_model
    rng = np.random.RandomState(4)
    prompt = rng.randint(1, cfg.vocab_size, size=16).tolist()
    cold = _colocated(tiny_model, num_pages=16, pages_per_seq=4)
    cold.submit(prompt, 4)
    gold = cold.run(max_steps=MAX_STEPS)
    gold_toks = gold[next(iter(gold))]
    eng = _colocated(tiny_model, num_pages=16, pages_per_seq=4,
                     prefix_cache=True)
    eng.submit(prompt, 4)
    eng.run(max_steps=MAX_STEPS)          # seeds the index
    ra, rb = eng.submit(prompt, 4), eng.submit(prompt, 4)
    res = eng.run(max_steps=MAX_STEPS)
    assert res[ra] == gold_toks and res[rb] == gold_toks
    assert eng.metrics.counters["cow_copies"] >= 1, \
        "second adopter should have COWed the shared final page"
    eng.alloc.check()


def test_colocated_mid_prefill_preemption_of_cache_hit(tiny_model):
    """A request that ADOPTED cached pages is preempted mid-prefill: the
    free_tail path must keep its filled prefix (including the adopted
    pages), requeue it at its chunk cursor, and the resumed request's
    tokens must still match a cold single-request run."""
    cfg, _ = tiny_model
    rng = np.random.RandomState(5)
    tpl = rng.randint(1, cfg.vocab_size, size=16).tolist()
    long_prompt = tpl + rng.randint(1, cfg.vocab_size, size=14).tolist()
    cold = _colocated(tiny_model, num_pages=16, pages_per_seq=8)
    cold.submit(long_prompt, 4)
    gold = cold.run(max_steps=MAX_STEPS)
    gold_toks = gold[next(iter(gold))]

    eng = _colocated(tiny_model, num_pages=16, pages_per_seq=8,
                     prefix_cache=True)
    eng.submit(tpl, 2)
    eng.run(max_steps=MAX_STEPS)          # seeds 2 pages of the template
    rid = eng.submit(long_prompt, 4)
    # one step: admission adopts the 2 template pages (cursor jumps to
    # 16) and dispatches one chunk → cursor 24 of 30
    eng.step()
    slot, req = next((i, r) for i, r in enumerate(eng.sched.slots)
                     if r is not None and r.rid == rid)
    assert req.state is RequestState.PREFILLING
    assert req.cache_hit_tokens == 16 and req.prefill_cursor == 24
    eng._preempt(slot)                    # forced mid-prefill preemption
    eng.alloc.check()
    # filled prefix (3 pages for cursor 24) survived the eviction
    assert len(eng.alloc.pages_of(rid)) == 3
    res = eng.run(max_steps=MAX_STEPS)
    assert res[rid] == gold_toks
    assert req.preemptions == 1
    eng.alloc.check()


def test_colocated_capture_restore_carries_prefix_audit(tiny_model):
    """Checkpoint state includes the prefix-index snapshot + digest; the
    restore contract starts with an EMPTY cache (KV is re-earned by
    re-prefill) and the audit rejects a tampered snapshot."""
    from triton_dist_tpu.serving import ControlJournal
    from triton_dist_tpu.serving.checkpoint import CheckpointIntegrityError

    cfg, _ = tiny_model
    journal = ControlJournal()
    eng = _colocated(tiny_model, prefix_cache=True, journal=journal,
                     checkpoint_every=8)
    eng.run(max_steps=MAX_STEPS,
            arrivals=_template_trace(cfg.vocab_size, n=6))
    state = eng._capture_state()
    assert state["prefix_digest"] == \
        PrefixCache.snapshot_digest(state["prefix_index"])
    eng._restore_state(state)
    assert eng.prefix_cache.indexed_pages == 0    # restored EMPTY
    assert eng.alloc.free_pages == eng.alloc.num_pages - 1
    state["prefix_index"][0][2] ^= 1
    with pytest.raises(CheckpointIntegrityError):
        eng._restore_state(state)


# ------------------------------------------------------- sharded bit-identity
def _sharded_serve(moe_model, tp, sp, ep, n=N_REQUESTS, **kw):
    cfg, _ = moe_model
    eng = sharded_engine(moe_model, tp, sp, ep, **kw)
    tokens = eng.run(max_steps=MAX_STEPS,
                     arrivals=_template_trace(cfg.base.vocab_size, n=n))
    return tokens, dict(eng.metrics.counters), eng.compile_stats


@pytest.fixture(scope="module")
def sharded_golden(moe_model):
    """Cache-OFF n=1 golden for the sharded acceptance trace."""
    tokens, counters, compiles = _sharded_serve(moe_model, 1, 1, 1)
    assert counters["preemptions"] >= 1
    return tokens, compiles


def _assert_sharded_cache_run(moe_model, tp, sp, ep, golden, n=N_REQUESTS,
                              **kw):
    """``n`` < N_REQUESTS replays the trace's first n (a request's tokens
    are a function of the request alone): enough to hit, not to evict."""
    gold, gold_compiles = golden
    tokens, counters, compiles = _sharded_serve(
        moe_model, tp, sp, ep, n=n, prefix_cache=True, **kw)
    assert tokens == {r: gold[r] for r in range(n)}, \
        f"cache-on {tp}x{sp}x{ep} diverged from the cache-off golden"
    assert counters["prefix_hits"] >= 1
    assert counters["prefix_evictions"] >= 1 or n < N_REQUESTS
    assert compiles == gold_compiles


@pytest.mark.quick
def test_sharded_cache_bit_identical_n1(moe_model, sharded_golden):
    _assert_sharded_cache_run(moe_model, 1, 1, 1, sharded_golden)


def test_sharded_cache_bit_identical_n2(moe_model, sharded_golden):
    _assert_sharded_cache_run(moe_model, 1, 1, 2, sharded_golden, n=6)


@pytest.mark.slow          # 5-10 s an interpreter step at n=4: over a minute
def test_sharded_cache_bit_identical_n4(moe_model, sharded_golden):
    _assert_sharded_cache_run(moe_model, 1, 2, 2, sharded_golden,
                              decode_horizon=4)


def test_sharded_cache_at_n4_indexes_the_sp_padded_pool(moe_model):
    """Tier 1's stand-in for the n=4 replay (`slow`), nothing dispatched:
    on the 1x2x2 mesh the cache indexes the engine's sp-aware pool, an
    indexed page parks on the cached LRU when released, and the padded
    pool still audits clean."""
    eng = sharded_engine(moe_model, 1, 2, 2, prefix_cache=True)
    pool, cache = eng.alloc, eng.prefix_cache
    assert eng.n_ranks == 4 and pool.sp_ranks == 2
    pages = pool.alloc("r0", 2)
    prompt = list(range(1, 17))                      # two full pages
    cache.insert(prompt, pages)
    assert cache.match(prompt + [99]) == pages
    pool.free_seq("r0")
    assert pool.cached_pages == 2 and pool.free_pages == pool.num_pages - 3
    pool.check()


# --------------------------------------------------------------- sigcheck
def test_sigcheck_lint_clean_with_cache_on(tiny_model, monkeypatch):
    """TDT_SIGCHECK=1 engine construction with the cache on: adoption and
    COW are host ledger ops plus eager device copies, so the linted
    program set is unchanged and the determinism lint stays clean."""
    monkeypatch.setenv("TDT_SIGCHECK", "1")
    eng = _colocated(tiny_model, prefix_cache=True)
    assert eng.prefix_cache is not None
