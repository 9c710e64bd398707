"""Cluster-wide prefix sharing (ISSUE 17): the KV page-lending tier.

THE contract, three rungs:

- **hit rate**: on a Zipf template mix with router affinity DISABLED
  (full-prompt rendezvous — same-prefix requests scatter across the
  fleet, the adversarial placement), the lending cluster's prefix hit
  rate matches the single-replica hit rate, because a remote hit turns
  into a lend and the lend turns into an ordinary local cached hit.
- **re-warm**: a restored replica re-warms its empty cache from peers
  (kill-time tombstones → deepest-exporter lends), so post-restore
  template TTFT lands in the cached band, NOT the cold band — and
  router affinity returns to the restored home replica warm.
- **degrade, never stall**: a dead/slow/lossy lender burns its Backoff
  rungs and DEGRADES to local re-prefill (typed, audited) — tokens stay
  bit-identical to the ``expected_tokens`` closed form either way,
  because greedy-decode determinism makes lent bytes indistinguishable
  from re-prefilled ones.

Plus the kernel in isolation (``ops.lend_pages`` — the transport copy
where the LENDER KEEPS its pages, unlike migration) and the ledger /
index units underneath (``check_lendable`` sole-ownership gating,
``ReplicaPrefixIndex.prune``/``reassign``).
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TEST_WORLD  # noqa: F401
from triton_dist_tpu.ops import lend_pages
from triton_dist_tpu.serving import Cluster, SimEngine, expected_tokens
from triton_dist_tpu.serving.kv_pool import KVPagePool, PageLedgerError
from triton_dist_tpu.serving.prefix_cache import ReplicaPrefixIndex
from triton_dist_tpu.shmem import FaultPlan
from triton_dist_tpu.shmem.context import initialize_distributed

pytestmark = [pytest.mark.lending, pytest.mark.serving]

PS = 8                        # page size everywhere below
BORROWER_ROLE = 1             # 2-rank lend mesh: lender=0, borrower=1


@pytest.fixture(scope="module")
def role_ctx():
    """One 2-rank role mesh for the kernel-in-isolation test."""
    return initialize_distributed(axis_names=("role",), mesh_shape=(2,))


def _mk_cluster(replicas=3, tmp_path=None, **kw):
    def factory(journal):
        return SimEngine(num_slots=4, page_size=PS, num_pages=33,
                         pages_per_seq=8, journal=journal,
                         prefix_cache=True, prefill_chunk=PS)

    return Cluster(factory, replicas=replicas,
                   journal_dir=None if tmp_path is None else str(tmp_path),
                   **kw)


def _templates(n=4, seed=23):
    """n distinct 24-token (3 full pages) prompt templates."""
    rng = np.random.RandomState(seed)
    return [tuple(int(t) for t in rng.randint(1, 997, size=3 * PS))
            for _ in range(n)]


def _hit_rate(cl):
    hits = sum(r.engine.metrics.counters["prefix_hits"]
               for r in cl.replicas)
    miss = sum(r.engine.metrics.counters["prefix_misses"]
               for r in cl.replicas)
    return hits / max(hits + miss, 1)


def _zipf_stream(cl, templates, n, seed):
    """Submit n Zipf-weighted template requests, draining between
    submits so the previous request's pages are CACHED (refcount-0)
    before the next may borrow them — in-flight prefill pages are not
    lendable by the sole-ownership rule. Returns {gid: (prompt, mnt)}."""
    rng = np.random.RandomState(seed)
    w = np.array([1.0 / (i + 1) ** 1.2 for i in range(len(templates))])
    w /= w.sum()
    sent = {}
    for _ in range(n):
        t = templates[int(rng.choice(len(templates), p=w))]
        prompt = t + tuple(int(x) for x in rng.randint(1, 997, size=3))
        mnt = int(rng.randint(2, 5))
        gid = cl.submit(list(prompt), mnt)
        sent[gid] = (prompt, mnt)
        cl.drain()
    return sent


def _assert_golden(cl, sent):
    res = cl.results()
    for gid, (prompt, mnt) in sent.items():
        assert res[gid] == expected_tokens(prompt, mnt), (
            f"gid {gid}: tokens diverged from the closed-form golden")


# ---------------------------------------------------------------------------
# the lend kernel, in isolation
# ---------------------------------------------------------------------------

@pytest.mark.quick
def test_lend_pages_kernel_exact_copy(role_ctx):
    """Lender-side pages land bit-exactly at the borrower's dst ids
    (every layer), padding beyond n_pages never moves, the borrower's
    landed report carries (count, tag) — and, the lend-vs-migrate
    distinction, the LENDER'S OWN PAGES ARE UNTOUCHED: a lend is a
    replication, the lender keeps serving its copies."""
    ctx = role_ctx
    L, Pg, H, ps, D = 2, 8, 2, 4, 8
    shape = (L, Pg, H, ps, D)
    host_k = np.zeros((2,) + shape, np.float32)
    host_v = np.zeros((2,) + shape, np.float32)
    for p in range(Pg):                        # distinct stamp per page
        host_k[0, :, p] = 100 + p
        host_v[0, :, p] = 200 + p
    pool_k = ctx.shard(jnp.asarray(host_k),
                       jax.sharding.PartitionSpec("role"))
    pool_v = ctx.shard(jnp.asarray(host_v),
                       jax.sharding.PartitionSpec("role"))

    src = jnp.array([3, 5, 1, 7], jnp.int32)   # entry past n is padding
    dst = jnp.array([2, 6, 4, 7], jnp.int32)
    pool_k, pool_v, landed = lend_pages(
        ctx, pool_k, pool_v, src, dst, jnp.array([3], jnp.int32),
        axis="role", lender=0, borrower=1, tag=7)
    assert int(np.asarray(landed)[BORROWER_ROLE, 0]) == 3
    assert int(np.asarray(landed)[BORROWER_ROLE, 1]) == 7
    hk, hv = np.asarray(pool_k), np.asarray(pool_v)
    for s, d in [(3, 2), (5, 6), (1, 4)]:
        assert (hk[1, :, d] == 100 + s).all()
        assert (hv[1, :, d] == 200 + s).all()
    assert not hk[1, :, 7].any(), "padding entry must not be lent"
    # the lender keeps its pages: shard 0 is untouched, scratch page
    # (id 0) included — the transport is one-sided
    for p in range(Pg):
        assert (hk[0, :, p] == 100 + p).all()
        assert (hv[0, :, p] == 200 + p).all()


# ---------------------------------------------------------------------------
# the ledger and index units underneath
# ---------------------------------------------------------------------------

@pytest.mark.quick
def test_check_lendable_sole_ownership():
    """A page is lendable iff refcount-0 AND cached-LRU-retained; the
    lendable run is the POSITIONAL PREFIX up to the first page that is
    not; out-of-range ids are ledger corruption, not a short count."""
    pool = KVPagePool(9, PS, reserved=1)
    got = pool.alloc("s", 3)
    for p in got:
        pool.mark_cacheable(p)
    # live sequence still references them — nothing is lendable yet
    assert pool.check_lendable(got) == 0
    pool.free_seq("s")          # refcount-0 + cacheable → cached LRU
    assert pool.check_lendable(got) == 3
    # a reader pins the middle page: the run stops right before it
    pool.acquire("t", [got[1]])
    assert pool.check_lendable(got) == 1
    # a refcount-0 page that is NOT index-retained is not lendable
    free = pool.alloc("u", 1)
    pool.free_seq("u")
    assert pool.check_lendable(free) == 0
    # out-of-range / reserved ids are loud
    with pytest.raises(PageLedgerError, match="check_lendable"):
        pool.check_lendable([0])
    with pytest.raises(PageLedgerError, match="check_lendable"):
        pool.check_lendable([9])


@pytest.mark.quick
def test_prefix_index_prune_and_reassign():
    """kill() prunes a dead replica's entries (returning tombstone
    paths); restore() reassigns them back — reassign OVERWRITES owners
    claimed by peers mid-death and creates missing nodes."""
    idx = ReplicaPrefixIndex(PS)
    a = tuple(range(100, 100 + 2 * PS))        # replica 0's prefix
    b = tuple(range(300, 300 + 2 * PS))        # replica 1's prefix
    idx.insert(a, 0)
    idx.insert(b, 1)
    assert idx.match(a) == (2, 0)              # (depth in runs, owner)
    tombs = idx.prune(0)
    assert tombs and all(isinstance(t, tuple) for t in tombs)
    assert {len(t) for t in tombs} <= {PS, 2 * PS}   # full token paths
    _, owner = idx.match(a)
    assert owner is None, "pruned entries must not route"
    assert idx.match(b) == (2, 1), "peer entries must survive"
    # a peer claims the prefix while 0 is dead (first-writer-wins insert)
    idx.insert(a, 1)
    assert idx.match(a) == (2, 1)
    # restore: reassign returns ownership to the re-warmed replica
    for t in tombs:
        idx.reassign(t, 0)
    assert idx.match(a) == (2, 0), "affinity did not return"
    # reassign on a never-inserted path creates it
    c = tuple(range(500, 500 + PS))
    idx.reassign(c, 2)
    assert idx.match(c) == (1, 2)


@pytest.mark.quick
def test_export_adopt_between_engines():
    """The host lend surface engine-to-engine: the lender exports its
    cached lendable prefix, the borrower adopts it as ordinary cached
    pages (classified REWARMED on first hit), tokens stay bit-identical
    to the closed form, and both ledgers audit clean."""
    lender = SimEngine(num_slots=2, page_size=PS, num_pages=17,
                       pages_per_seq=8, prefix_cache=True,
                       prefill_chunk=PS)
    borrower = SimEngine(num_slots=2, page_size=PS, num_pages=17,
                         pages_per_seq=8, prefix_cache=True,
                         prefill_chunk=PS)
    t = _templates(1)[0]
    prompt = t + (7, 8, 9)
    lender.submit(list(prompt), 3)
    lender.run()
    toks, ids, payload = lender.export_prefix(prompt)
    assert toks == 3 * PS and len(ids) == 3 and payload is None
    assert borrower.adopt_prefix(prompt, toks, payload) == 3
    # adopting again is a no-op, not an error (already as warm)
    assert borrower.adopt_prefix(prompt, toks, payload) == 0
    rid = borrower.submit(list(prompt), 3)
    out = borrower.run()
    assert out[rid] == expected_tokens(prompt, 3)
    assert borrower.metrics.hist["ttft_rewarmed_steps"].count == 1
    assert borrower.metrics.counters["prefix_hits"] == 1
    lender.alloc.check()
    borrower.alloc.check()


@pytest.mark.quick
def test_adopt_prefix_pins_local_hit_under_pool_pressure():
    """Regression: the borrower's PARTIAL local hit sits refcount-0 on
    the cached LRU, so the reclaim that makes room for the lent pages
    could evict it out from under the insert (re-popping the hit page
    into the fresh allocation → 'already indexed', or indexing a
    free-listed page). adopt_prefix must PIN the hit before reclaiming:
    under pressure the eviction takes another cached page — never the
    hit — and the lend deepens the existing prefix cleanly."""
    lender = SimEngine(num_slots=2, page_size=PS, num_pages=17,
                       pages_per_seq=8, prefix_cache=True,
                       prefill_chunk=PS)
    borrower = SimEngine(num_slots=2, page_size=PS, num_pages=17,
                         pages_per_seq=8, prefix_cache=True,
                         prefill_chunk=PS)
    t = _templates(1, seed=13)[0]
    prompt = t + (7, 8, 9)
    lender.submit(list(prompt), 3)
    lender.run()
    toks, _, payload = lender.export_prefix(prompt)
    assert toks == 3 * PS

    # the borrower caches ONLY the template's first page (the partial
    # hit, oldest on the LRU)...
    borrower.submit(list(t[:PS] + (1, 2, 3)), 2)
    borrower.run()
    assert len(borrower.prefix_cache.match(t)) == 1
    # ...then an unrelated page lands behind it on the LRU
    rng = np.random.RandomState(5)
    u = tuple(int(x) for x in rng.randint(1, 997, size=PS)) + (4, 5, 6)
    borrower.submit(list(u), 2)
    borrower.run()
    assert borrower.alloc.cached_pages == 2

    # soak the free list down to ONE page: landing the 2 missing pages
    # forces a reclaim, and the unpinned LRU victim would be the hit
    free = borrower.alloc.free_pages
    assert free >= 1
    if free > 1:
        assert borrower.alloc.alloc("soak", free - 1) is not None

    assert borrower.adopt_prefix(prompt, toks, payload) == 2
    # the hit survived (the decoy was evicted instead) and was deepened
    assert len(borrower.prefix_cache.match(t)) == 3
    assert not borrower.prefix_cache.match(u), \
        "the decoy page should have been the eviction victim"
    borrower.alloc.check()

    borrower.alloc.free_seq("soak")    # give the pool room to decode
    rid = borrower.submit(list(prompt), 2)
    out = borrower.run()
    assert out[rid] == expected_tokens(prompt, 2)


# ---------------------------------------------------------------------------
# acceptance: cluster hit rate == single-replica hit rate, affinity OFF
# ---------------------------------------------------------------------------

@pytest.mark.quick
def test_cluster_hit_rate_matches_single_replica_affinity_off():
    """The ISSUE 17 acceptance: with router affinity DISABLED (full-
    prompt rendezvous scatters same-template requests across the fleet),
    the lending cluster's hit rate matches the single-replica rate —
    every remote hit becomes a lend becomes a local hit — and beats the
    lend-less scattered baseline by a wide margin. All traces bitwise."""
    templates = _templates()
    n = 30

    single = _mk_cluster(replicas=1)
    sent_1 = _zipf_stream(single, templates, n, seed=41)
    rate_1 = _hit_rate(single)

    base = _mk_cluster(replicas=3, affinity=False)
    sent_b = _zipf_stream(base, templates, n, seed=41)
    rate_b = _hit_rate(base)

    lend = _mk_cluster(replicas=3, affinity=False, lend=True)
    sent_l = _zipf_stream(lend, templates, n, seed=41)
    rate_l = _hit_rate(lend)

    # scattering without lending costs real hits; lending wins them back
    assert rate_b < rate_1 - 0.05, (
        f"baseline not adversarial enough: {rate_b:.3f} vs {rate_1:.3f}")
    assert rate_l >= rate_b + 0.05
    assert abs(rate_l - rate_1) <= 0.02, (
        f"cluster hit rate {rate_l:.3f} != single-replica {rate_1:.3f}")
    assert lend.metrics.counters["lends"] > 0
    assert lend.metrics.counters["lent_pages"] >= \
        3 * lend.metrics.counters["lends"] - 2 * len(templates)
    assert lend.metrics.hist["lend_us_per_page"].count == \
        lend.metrics.counters["lends"]
    for cl, sent in ((single, sent_1), (base, sent_b), (lend, sent_l)):
        _assert_golden(cl, sent)
        for rep in cl.replicas:
            rep.engine.alloc.check()


# ---------------------------------------------------------------------------
# acceptance: restored replica re-warms from peers
# ---------------------------------------------------------------------------

@pytest.mark.quick
def test_restore_rewarms_from_peers(tmp_path):
    """Kill the template's home replica, serve the template elsewhere
    during the downtime, restore: the restored replica re-warms its
    cache FROM THE PEER (tombstones → deepest-exporter lend), affinity
    returns to it, and its post-restore template TTFT lands in the
    cached band — strictly below the fallback's cold band. A second
    kill/restore cycle then replays a journal that CONTAINS lend events
    (replay ignores them — re-warm re-earns the pages from peers)."""
    cl = _mk_cluster(replicas=3, tmp_path=tmp_path, lend=True)
    t = _templates(1, seed=91)[0]
    rng = np.random.RandomState(7)

    def tpl_prompt():
        return t + tuple(int(x) for x in rng.randint(1, 997, size=3))

    sent = {}

    def go(prompt, mnt=3):
        gid = cl.submit(list(prompt), mnt)
        sent[gid] = (tuple(prompt), mnt)
        cl.drain()
        return gid

    go(tpl_prompt())
    home = cl.prefix_index.match(t)[1]
    assert home is not None
    go(tpl_prompt())               # cached hit on home
    assert cl.replicas[home].engine.metrics.counters["prefix_hits"] >= 1

    cl.kill(home)
    assert cl._tombstones[home], "kill must tombstone the pruned paths"
    go(tpl_prompt())               # fallback serves the template COLD
    go(tpl_prompt())               # ... then cached
    fb = cl.prefix_index.match(t)[1]
    assert fb is not None and fb != home
    fb_m = cl.replicas[fb].engine.metrics
    cold_floor = fb_m.hist["ttft_cold_steps"].min
    cached_ceil = fb_m.hist["ttft_cached_steps"].max
    assert cold_floor is not None and cached_ceil is not None
    assert cold_floor > cached_ceil   # the bands are actually separated

    cl.restore(home)
    assert cl.metrics.counters["rewarmed_prefixes"] >= 1
    assert cl.metrics.counters["lends"] >= 1
    # affinity returned to the (re-warmed) home replica
    assert cl.route(list(tpl_prompt())).index == home
    go(tpl_prompt())               # post-restore: REWARMED, not cold
    hm = cl.replicas[home].engine.metrics
    rew = hm.hist["ttft_rewarmed_steps"]
    assert rew.count >= 1
    assert rew.max <= cached_ceil, (
        f"post-restore TTFT {rew.max} above the cached band "
        f"{cached_ceil}")
    assert rew.max < cold_floor, (
        f"post-restore TTFT {rew.max} in the cold band (floor "
        f"{cold_floor}) — the re-warm did not take")

    # second cycle: home's journal now holds "lend" events — replay must
    # ignore them (adopted pages are cache state, re-earned from peers)
    cl.kill(home)
    cl.restore(home)
    assert cl.metrics.counters["rewarmed_prefixes"] >= 2
    gid = go(tpl_prompt())
    assert cl.results()[gid] == expected_tokens(*sent[gid])

    _assert_golden(cl, sent)
    for rep in cl.replicas:
        rep.engine.alloc.check()


@pytest.mark.quick
def test_cold_restore_does_not_steal_claimed_prefixes(tmp_path):
    """With lending OFF a restored replica's cache is empty by contract
    (no re-warm ran): a prefix a peer claimed — and re-earned — during
    the downtime must STAY with that warm peer; reassigning it to the
    cold restoree would route template traffic at an empty cache. An
    UNCLAIMED tombstone still returns home: both sides are equally cold
    there, and affinity entries are never dropped."""
    cl = _mk_cluster(replicas=3, tmp_path=tmp_path)
    assert cl.lending is None
    rng = np.random.RandomState(3)
    sent = {}

    def go(t):
        prompt = t + tuple(int(x) for x in rng.randint(1, 997, size=3))
        gid = cl.submit(list(prompt), 2)
        sent[gid] = (prompt, 2)
        cl.drain()
        return gid

    # find two templates rendezvous-routed to the SAME home (pigeonhole
    # over 6 templates × 3 replicas guarantees a pair; deterministic)
    homes: dict[int, list[tuple]] = {}
    for t in _templates(6, seed=17):
        go(t)
        homes.setdefault(cl.prefix_index.match(t)[1], []).append(t)
    a, b = next(v for v in homes.values() if len(v) >= 2)[:2]
    home = cl.prefix_index.match(a)[1]

    cl.kill(home)
    go(a)                        # a fallback peer claims + re-earns `a`
    peer = cl.prefix_index.match(a)[1]
    assert peer is not None and peer != home
    assert cl.prefix_index.match(b)[1] is None, "pruned, nobody claimed"

    cl.restore(home)
    assert cl.prefix_index.match(a)[1] == peer, (
        "cold restoree stole a prefix its peer holds warm")
    assert cl.prefix_index.match(b)[1] == home, (
        "unclaimed affinity did not return to the restored replica")
    _assert_golden(cl, sent)


# ---------------------------------------------------------------------------
# acceptance: lender death mid-lend degrades, never stalls
# ---------------------------------------------------------------------------

@pytest.mark.quick
def test_lender_death_degrades_to_local_prefill():
    """A seeded dead-peer schedule kills every lend attempt in flight:
    the ladder burns its rungs, records a TYPED degradation, and the
    borrower prefills locally — tokens bit-identical to the closed-form
    golden, zero stalls. The whole drill replays from the seed: two runs
    produce identical degradation audit trails."""
    plan = FaultPlan(seed=3, dead_peer_after=0)

    def run():
        cl = _mk_cluster(replicas=3, affinity=False, lend=True,
                         lend_plan=plan)
        sent = _zipf_stream(cl, _templates(seed=61), 16, seed=5)
        _assert_golden(cl, sent)
        return (cl.metrics.counters["lends"],
                cl.metrics.counters["lend_degradations"],
                cl.metrics.counters["retries"],
                list(cl.lending.degraded))

    lends, degr, retries, audit = run()
    assert lends == 0, "a dead lender must never complete a lend"
    assert degr >= 1 and len(audit) == degr
    assert retries >= degr, "each degradation burned at least one retry"
    for lender, borrower, head in audit:
        assert lender != borrower and isinstance(head, tuple)
    assert run() == (lends, degr, retries, audit), (
        "the drill must replay from the seed alone")


@pytest.mark.quick
def test_lend_ladder_drop_delay_then_success():
    """The ladder rung by rung: total signal loss and over-deadline
    delivery both burn every rung and degrade (delay also marks the
    report stale); with the plan lifted the very same lend succeeds,
    and a repeat lend is a no-op because the borrower is already warm."""
    cl = _mk_cluster(replicas=2, lend=True)
    t = _templates(1, seed=77)[0]
    prompt = t + (5, 6, 7)
    cl.submit(list(prompt), 2)
    cl.drain()
    owner = cl.prefix_index.match(t)[1]
    borrower = cl.replicas[1 - owner]

    cl.lending._plan = FaultPlan(seed=2, p_drop=1.0)
    assert cl.lending.lend(borrower, prompt) == 0
    assert cl.metrics.counters["lend_degradations"] == 1

    cl.lending._plan = FaultPlan(seed=2, p_delay=1.0, max_delay_steps=99)
    assert cl.lending.lend(borrower, prompt) == 0
    assert cl.metrics.counters["lend_degradations"] == 2
    assert cl.metrics.counters["stale_signals"] >= 1

    cl.lending._plan = FaultPlan(seed=2)       # healthy transport
    assert cl.lending.lend(borrower, prompt) == 3
    assert cl.metrics.counters["lends"] == 1
    assert cl.lending.lend(borrower, prompt) == 0, (
        "an already-warm borrower must not borrow again")
    borrower.engine.alloc.check()
