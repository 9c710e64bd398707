"""Disaggregated prefill/decode tests (ISSUE 6): the signal-protocol
ledger, the page-migration kernel, and the headline end-to-end property —
a two-role disaggregated trace produces per-request tokens BIT-IDENTICAL
to the colocated chunked engine, including under forced mid-prefill
preemption on the prefill worker; a lost signal times out loudly instead
of admitting a slot over unlanded pages."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TEST_WORLD  # noqa: F401
from triton_dist_tpu.models.llama import LlamaConfig, init_params
from triton_dist_tpu.ops import migrate_pages
from triton_dist_tpu.serving import (ChunkSignalLedger, DisaggServingEngine,
                                     MigrationSignalTimeout, PageLedgerError,
                                     PageMigrationChannel, ServingEngine)
from triton_dist_tpu.shmem import FaultPlan
from triton_dist_tpu.serving.disagg import DECODE_ROLE
from triton_dist_tpu.serving.metrics import ServingMetrics
from triton_dist_tpu.serving.scheduler import RequestState
from triton_dist_tpu.shmem.context import initialize_distributed

pytestmark = pytest.mark.disagg


@pytest.fixture(scope="module")
def role_ctx():
    """One 2-rank role mesh shared by every engine in this module (each
    engine allocates its own symmetric pools inside it)."""
    return initialize_distributed(axis_names=("role",), mesh_shape=(2,))


@pytest.fixture(scope="module")
def tiny_model():
    cfg = dataclasses.replace(LlamaConfig.tiny(n_layers=2),
                              dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    return cfg, params


def _mk_requests(cfg, n, seed=0, mnt_lo=2, mnt_hi=10, plen_lo=3, plen_hi=20):
    rng = np.random.RandomState(seed)
    return [(list(rng.randint(1, cfg.vocab_size,
                              size=int(rng.randint(plen_lo, plen_hi)))),
             int(rng.randint(mnt_lo, mnt_hi)))
            for _ in range(n)]


def _disagg(params, cfg, ctx, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("num_prefill_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 32)
    kw.setdefault("pages_per_seq", 8)
    kw.setdefault("prefill_chunk", 8)
    return DisaggServingEngine(params, cfg, ctx=ctx, **kw)


# ---------------------------------------------------------------------------
# signal-protocol ledger (host mirror of the per-chunk counted signal)
# ---------------------------------------------------------------------------

@pytest.mark.quick
def test_ledger_signal_count_matches_pages():
    """A chunk covers its pages exactly when the signal count reaches the
    page count — the kernel signals +n for an n-page chunk, so per-chunk
    signal count == pages landed is the protocol invariant."""
    led = ChunkSignalLedger()
    led.expect(7, 0, [3, 4, 5])
    assert not led.chunk_complete(7, 0)
    assert led.covered(7) == set()             # 0/3 signals: nothing
    led.landed(7, 0, 2)
    assert led.covered(7) == set()             # 2/3: partial covers NOTHING
    assert not led.complete(7)
    led.landed(7, 0, 1)                        # third signal arrives
    assert led.chunk_complete(7, 0)
    assert led.covered(7) == {3, 4, 5}
    assert led.complete(7)
    # a signal for a chunk nobody announced is a protocol bug, loudly
    with pytest.raises(KeyError):
        led.landed(7, 9, 1)
    with pytest.raises(KeyError):
        led.landed(8, 0, 1)


@pytest.mark.quick
def test_ledger_tolerates_out_of_order_chunks():
    """Chunk completion order is NOT delivery order: coverage is the union
    over complete chunks, whatever order their signals landed in."""
    led = ChunkSignalLedger()
    led.expect(1, 0, [2, 3])
    led.expect(1, 1, [4])
    led.expect(1, 2, [5, 6])
    led.landed(1, 2, 2)                        # last chunk completes first
    assert led.covered(1) == {5, 6}
    led.landed(1, 0, 2)                        # then the first
    assert led.covered(1) == {2, 3, 5, 6}
    assert not led.complete(1)                 # chunk 1 still outstanding
    led.landed(1, 1, 1)
    assert led.complete(1)
    assert led.covered(1) == {2, 3, 4, 5, 6}
    # re-expect (preemption re-send) resets that chunk's count only
    led.expect(1, 0, [2, 3])
    assert led.covered(1) == {4, 5, 6}
    assert not led.complete(1)
    led.reset(1)
    assert led.covered(1) == set() and led.expected(1) == set()


@pytest.mark.quick
def test_channel_refuses_scratch_page():
    """Scratch pages are engine-local parking (inactive rows mutate them
    every dispatch) — migrating one plants live garbage in the peer pool.
    The channel refuses before anything is launched or ledgered."""
    def boom(*_a, **_k):
        raise AssertionError("kernel must not launch for a refused chunk")

    ch = PageMigrationChannel(boom, pmax=4, reserved=1,
                              metrics=ServingMetrics())
    with pytest.raises(PageLedgerError, match="scratch"):
        ch.send_chunk(0, 0, [0, 2], [3, 4], None, None)
    with pytest.raises(PageLedgerError, match="scratch"):
        ch.send_chunk(0, 0, [2, 3], [4, 0], None, None)
    assert ch.ledger.expected(0) == set()      # refused chunk never ledgered


# ---------------------------------------------------------------------------
# the migration kernel, in isolation
# ---------------------------------------------------------------------------

@pytest.mark.quick
def test_migrate_pages_exact_copy(role_ctx):
    """Producer-side pages land bit-exactly at the consumer-side dst ids
    (every layer), padding beyond n_pages is never dereferenced, producer
    pages are untouched, and both roles report the landed count."""
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
    pool_k, pool_v, landed = migrate_pages(
        ctx, pool_k, pool_v, src, dst, jnp.array([3], jnp.int32),
        axis="role", tag=5)
    # landed report rows are (count, echoed generation tag) per role —
    # the tag is what lets the ledger discard stale re-sent deliveries
    assert int(np.asarray(landed)[DECODE_ROLE, 0]) == 3
    assert int(np.asarray(landed)[DECODE_ROLE, 1]) == 5
    hk, hv = np.asarray(pool_k), np.asarray(pool_v)
    for s, d in [(3, 2), (5, 6), (1, 4)]:
        assert (hk[1, :, d] == 100 + s).all()
        assert (hv[1, :, d] == 200 + s).all()
    assert not hk[1, :, 7].any(), "padding entry must not migrate"
    # producer shard untouched, scratch page (id 0) included: the
    # transport is one-sided
    for p in range(Pg):
        assert (hk[0, :, p] == 100 + p).all()
        assert (hv[0, :, p] == 200 + p).all()


# ---------------------------------------------------------------------------
# end-to-end: disaggregated == colocated, bit for bit
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def disagg_golden(tiny_model):
    """Golden: the COLOCATED chunked engine over the same trace — the
    ISSUE 6 acceptance target ('bit-identical to local chunked
    prefill')."""
    cfg, params = tiny_model
    reqs = _mk_requests(cfg, 6, seed=11, mnt_lo=2, mnt_hi=7)
    eng = ServingEngine(params, cfg, num_slots=2, page_size=8, num_pages=32,
                        pages_per_seq=8, prefill_chunk=8)
    rids = [eng.submit(p, m) for p, m in reqs]
    gold = eng.run(max_steps=2000)
    assert len(gold) == len(reqs)
    return reqs, rids, gold


@pytest.mark.quick
def test_disagg_bit_identical_to_colocated(tiny_model, role_ctx,
                                           disagg_golden):
    """The two-role demo: every request's tokens (first token from the
    prefill worker's fused argmax + the decode worker's stream over
    MIGRATED pages) match the colocated chunked engine bit for bit. Also
    pins the metrics split: the decode worker processed ZERO prompt
    tokens, every request was handed off, and pages actually moved."""
    cfg, params = tiny_model
    reqs, gold_rids, gold = disagg_golden
    eng = _disagg(params, cfg, role_ctx)
    rids = [eng.submit(p, m) for p, m in reqs]
    res = eng.run(max_steps=2000)
    assert sorted(res) == sorted(gold)
    for rid, grid_ in zip(rids, gold_rids):
        assert res[rid] == gold[grid_], f"rid {rid} diverged"
    # role isolation, in token space (host-noise-proof)
    assert eng.metrics_decode.hist["step_prefill_tokens"].max == 0
    assert eng.metrics.counters["handoffs"] == len(reqs)
    assert eng.metrics_decode.counters["handoffs"] == len(reqs)
    need = sum(-(-len(p) // 8) for p, _ in reqs)
    assert eng.metrics.counters["pages_migrated"] == need
    assert eng.metrics.counters["migrate_chunks"] >= len(reqs)
    # every page freed on both sides at the end
    assert eng.alloc_p.used_pages == 0 and eng.alloc_d.used_pages == 0


def test_disagg_bit_identical_under_prefill_preemption(tiny_model, role_ctx,
                                                       disagg_golden):
    """Forced mid-prefill preemption on the PREFILL worker (the ISSUE 6
    acceptance twist): the victim resumes at its chunk cursor with its
    filled pages, never re-sends already-migrated pages, and every
    request still finishes bit-identical to the colocated golden."""
    cfg, params = tiny_model
    reqs, gold_rids, gold = disagg_golden
    eng = _disagg(params, cfg, role_ctx, num_prefill_slots=1)
    rids = [eng.submit(p, m) for p, m in reqs]
    preempted = 0
    for i in range(2000):
        if not eng.step():
            break
        if i % 2 == 0 and preempted < 4:       # hammer early prefills
            if eng.force_preempt_prefill() is not None:
                preempted += 1
    res = {r.rid: list(r.generated) for r in eng._finished}
    assert preempted >= 1, "trace was meant to force prefill preemption"
    assert eng.metrics.counters["preemptions"] >= 1
    assert sorted(res) == sorted(gold)
    for rid, grid_ in zip(rids, gold_rids):
        assert res[rid] == gold[grid_], f"rid {rid} diverged"


# ---------------------------------------------------------------------------
# signal-gated admission: loss, landmine, timeout
# ---------------------------------------------------------------------------

@pytest.mark.quick
def test_lost_signal_fails_request_not_engine(tiny_model, role_ctx,
                                              monkeypatch):
    """TDT_SERIAL lost-signal drill, ISSUE-7 contract: every signal for
    ONE request is dropped (scoped FaultPlan) and degradation is off, so
    after the retry rungs run dry THAT request fails with a typed,
    ledger-dumping reason — while the un-faulted neighbor finishes
    normally in the SAME run. The old whole-engine
    MigrationSignalTimeout raise is gone: the engine never dies for a
    transport fault."""
    monkeypatch.setenv("TDT_SERIAL", "1")
    cfg, params = tiny_model
    plan = FaultPlan(seed=3, p_drop=1.0, rids=(0,))
    eng = _disagg(params, cfg, role_ctx, fault_plan=plan,
                  signal_deadline_steps=2, max_retries=1,
                  allow_degradation=False)
    prompt = list(range(1, 13))                # 12 tokens: 2 chunks, 2 pages
    rid = eng.submit(prompt, 4)                # rid 0 — the faulted one
    rid_ok = eng.submit(list(range(20, 29)), 3)
    req = eng.sched_p.queue[0]

    res = eng.run(max_steps=400)               # must NOT raise
    assert rid not in res and rid_ok in res
    assert len(res[rid_ok]) == 3               # the neighbor was untouched
    assert [r.rid for r in eng.failed] == [rid]
    assert req.state is RequestState.FAILED
    assert isinstance(req.failure, MigrationSignalTimeout)
    msg = str(req.failure)
    assert f"request {rid}" in msg
    assert "chunk 0: 0/" in msg                # per-chunk count in the report
    assert "missing" in msg                    # ledger dump rode along
    assert req.generated == []                 # not one token decoded
    assert eng.metrics_decode.counters["failed_requests"] == 1
    assert eng.metrics_decode.counters["retries"] >= 1
    assert eng.metrics.counters["faults_injected"] >= 2
    # failure released every page on both sides
    assert eng.alloc_p.used_pages == 0 and eng.alloc_d.used_pages == 0
    eng.alloc_p.check(); eng.alloc_d.check(eng.channel.ledger)


@pytest.mark.quick
def test_unsent_chunk_landmine(tiny_model, role_ctx, monkeypatch):
    """The landmine (ISSUE 6 acceptance, ISSUE 7 failure domain): a chunk
    that is never SENT at all. The decode-side block table must never
    expose the unlanded pages (the signal gate would raise if it did),
    the retry rung must recognize there is nothing to re-send (the ledger
    has no incomplete chunk), and with degradation off the request fails
    typed, saying a chunk may never have been sent."""
    cfg, params = tiny_model
    eng = _disagg(params, cfg, role_ctx, signal_deadline_steps=4,
                  max_retries=2, allow_degradation=False)
    prompt = list(range(1, 13))
    rid = eng.submit(prompt, 4)
    req = eng.sched_p.queue[0]
    real_send = eng.channel.send_chunk

    def dropping(r, ci, src, dst, pk, pv):
        if r == rid and ci == 1:
            return pk, pv                      # chunk silently not sent
        return real_send(r, ci, src, dst, pk, pv)

    monkeypatch.setattr(eng.channel, "send_chunk", dropping)
    res = eng.run(max_steps=400)               # per-request failure, no raise
    assert res == {}
    assert req.state is RequestState.FAILED
    assert isinstance(req.failure, MigrationSignalTimeout)
    assert "never sent" in str(req.failure)
    # no retries counted: the ledger had no incomplete chunk to re-send,
    # so the ladder skipped straight past the retry rung
    assert eng.metrics_decode.counters["retries"] == 0
    assert eng.metrics_decode.counters["failed_requests"] == 1
    assert eng.alloc_p.used_pages == 0 and eng.alloc_d.used_pages == 0


# ---------------------------------------------------------------------------
# decode stall independent of prompt length
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("peer_plen", [8, 64])
def test_decode_cadence_independent_of_peer_prompt(tiny_model, role_ctx,
                                                   peer_plen):
    """The reason to disaggregate, pinned in STEP space (where CPU wall
    clocks cannot fake it): once a request is decoding, it emits exactly
    one token per engine step even while the prefill worker grinds a peer
    prompt — whether that prompt is 8 or 64 tokens. In the colocated
    engine the chunk compute sits inside the same step; here the decode
    worker's prompt-token count is identically zero."""
    cfg, params = tiny_model
    eng = _disagg(params, cfg, role_ctx, num_slots=2, num_prefill_slots=1,
                  page_size=8, num_pages=32, pages_per_seq=10,
                  prefill_chunk=8)
    target = eng.submit(list(range(1, 6)), 16)
    treq = eng.sched_p.queue[0]
    for _ in range(50):                        # drive until target decodes
        eng.step()
        if treq.state is RequestState.ACTIVE and len(treq.generated) >= 2:
            break
    assert treq.state is RequestState.ACTIVE
    before = len(treq.generated)
    eng.submit(list(range(1, peer_plen + 1)), 2)
    probe = 6                                  # peer is mid-prefill for all 6
    for _ in range(probe):
        eng.step()
    gained = len(treq.generated) - before
    assert gained == probe, (
        f"decode cadence broke: {gained} tokens in {probe} steps while "
        f"peer prompt of {peer_plen} was prefilling")
    assert eng.metrics_decode.hist["step_prefill_tokens"].max == 0
    assert eng.metrics.hist["step_prefill_tokens"].max > 0   # prefill role did
    eng.run(max_steps=500)                     # drain cleanly
    assert target in {r.rid for r in eng._finished}


# ---------------------------------------------------------------------------
# compile guard: bounded program set per role
# ---------------------------------------------------------------------------

def test_disagg_compile_guard(tiny_model, role_ctx):
    """Prefill and decode roles each compile a BOUNDED program set: one
    chunk program, one decode program, one migration program — across 8
    DISTINCT prompt lengths and every chunk size. No per-prompt-length
    recompiles anywhere (the page ids and counts ride in SMEM as runtime
    scalars)."""
    cfg, params = tiny_model
    eng = _disagg(params, cfg, role_ctx, pages_per_seq=10)
    rng = np.random.RandomState(3)
    arrivals = []
    for i, plen in enumerate(range(3, 19, 2)):   # 8 distinct prompt lengths
        prompt = [int(t) for t in rng.randint(1, cfg.vocab_size, size=plen)]
        arrivals.append((i, prompt, int(rng.randint(2, 6))))
    res = eng.run(max_steps=2000, arrivals=arrivals)
    assert len(res) == 8
    stats = eng.compile_stats
    assert stats == {"prefill_chunk_compiles": 1, "decode_compiles": 1,
                     "migrate_compiles": 1}, stats
