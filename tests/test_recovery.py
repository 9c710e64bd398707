"""Crash-consistent serving (ISSUE 9): journal/checkpoint/restore held to
the bit-identity contract on all three engines (the colocated engine here;
the sharded mesh, the digest rung and the disaggregated pair in
test_recovery_mesh.py; the crash/recover harness both share is conftest's).

The trace-determinism contract (greedy argmax decode + LIFO page
allocation + strict-FIFO scheduling) makes every request's tokens a pure
function of (params, prompt) — so crash recovery never persists KV: a
fresh engine + the journal (which embeds periodic control-plane
checkpoints) replays the WAL suffix, requeues every in-flight request at
cursor 0, and regenerates bit-identical tokens through the
already-compiled programs. The tests pin exactly that:

- **crash sweep**: inject ``InjectedCrash`` at strided steps of the
  forced-preemption trace (every step under ``-m slow``),
  recover into a fresh engine, and assert the union of pre-crash and
  post-recovery finishes is BIT-IDENTICAL to the fault-free golden — on
  colocated, sharded (n ∈ {1, 2, 4}), and disaggregated (including a
  crash with a migration in flight: the restarted decode worker
  re-admits the request through the rebuilt ledger, never fails it).
- **zero new compiles**: restore performs no device dispatches — the jit
  trace-cache sizes are unchanged across ``restore()``, and a recovered
  run still ends at exactly one decode + one chunk program.
- **digest divergence rung**: a seeded transient ``digest_skew`` on the
  sharded mesh is absorbed by quarantine + restore (``digest_recoveries
  == 1``, tokens golden); persistent skew (re-diverging with no agreed
  step in between) escalates instead of looping; no journal = the
  pre-ISSUE-9 hard raise.
- **overload terminals**: a bounded admission queue + TTL shed excess
  load with typed REJECTED terminals while every admitted request still
  finishes bit-identically.
"""

import numpy as np
import pytest

from conftest import (N_REQUESTS, RECOVERY_MAX_STEPS as MAX_STEPS,
                      crash_then_recover, journaled_steps, seeded_trace)
from triton_dist_tpu.serving import (AdmissionRejected, ControlJournal,
                                     ServingEngine, TtlExpired)
from triton_dist_tpu.serving import checkpoint as ckpt_mod
from triton_dist_tpu.serving.checkpoint import (CheckpointIntegrityError,
                                                rebuild_request,
                                                snapshot_request)
from triton_dist_tpu.serving.kv_pool import KVPagePool
from triton_dist_tpu.serving.scheduler import Request, RequestState
from triton_dist_tpu.shmem import FaultPlan
from triton_dist_tpu.shmem.faults import InjectedCrash

pytestmark = [pytest.mark.recovery, pytest.mark.serving]


def _trace(n):
    return seeded_trace(n, staggered=True)


# ------------------------------------------------------- engine factories
def _colocated(micro_model, **kw):
    cfg, params = micro_model
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 5)         # tight: forces preemption
    kw.setdefault("pages_per_seq", 4)      # the chunk grid is rows x pages
    kw.setdefault("prefill_chunk", 8)
    return ServingEngine(params, cfg, **kw)


# ------------------------------------------------------------ journal units
def test_journal_round_trip(tmp_path):
    j = ControlJournal()
    j.append("submit", 0, 0xAB, rid=0, prompt=[1, 2], max_new_tokens=3)
    j.append("admit", 1, 0xCD, rid=0, slot=2)
    j.record_checkpoint(4, 0xEF, {"live": []}, journal_seq=1)
    j.append("finish", 7, 0x11, rid=0, tokens=[5, 6, 7])
    assert len(j) == 4 and j.last_seq == 3
    assert [e["seq"] for e in j.suffix(1)] == [2, 3]
    assert j.last_checkpoint_entry()["journal_seq"] == 1
    assert j.counts() == {"submit": 1, "admit": 1, "checkpoint": 1,
                          "finish": 1}
    # bulky checkpoint state is elided from the post-mortem rendering
    tail = j.format_tail(8)
    assert "<elided>" in tail and "'live'" not in tail
    assert "digest=0x000000ab" in tail
    # jsonl save/load reconstitutes an equivalent journal
    p = tmp_path / "wal.jsonl"
    j.save(str(p))
    j2 = ControlJournal.load(str(p))
    assert j2.entries == j.entries


def test_journal_rejects_unknown_kind():
    with pytest.raises(AssertionError, match="unknown journal event"):
        ControlJournal().append("frobnicate", 0, 0)


def test_journal_path_mirror(tmp_path):
    p = tmp_path / "live.jsonl"
    j = ControlJournal(path=str(p))
    j.append("submit", 0, 1, rid=0, prompt=[1], max_new_tokens=1)
    j.close()
    assert ControlJournal.load(str(p)).entries == j.entries


def test_request_snapshot_round_trip():
    req = Request(rid=7, prompt=(1, 2, 3), max_new_tokens=4, eos_token=9)
    req.generated = [5, 6]
    req.prefill_cursor = 2
    req.preemptions = 1
    req.retries = 2
    back = rebuild_request(snapshot_request(req))
    assert back.rid == 7 and back.prompt == (1, 2, 3)
    assert back.state is RequestState.QUEUED
    assert back.prefill_cursor == 0 and back.generated == []
    assert back.preemptions == 1 and back.retries == 2


def test_pool_snapshot_audit_catches_tamper():
    pool = KVPagePool(8, 4, reserved=1)
    pool.alloc(0, 3)
    snap = pool.snapshot()
    ckpt_mod.audit_pool_snapshot(snap, pool.digest(), 8, 4, 1)  # clean
    snap["free"] = snap["free"][::-1]     # torn snapshot: free-list order
    with pytest.raises(CheckpointIntegrityError, match="torn or tampered"):
        ckpt_mod.audit_pool_snapshot(snap, pool.digest(), 8, 4, 1)


def test_prefix_snapshot_audit_catches_tamper():
    from triton_dist_tpu.serving import PrefixCache

    pool = KVPagePool(8, 4, reserved=1)
    cache = PrefixCache(pool, 4)
    pages = pool.alloc(0, 2)
    cache.insert([1, 2, 3, 4, 5, 6, 7, 8], pages)
    snap, dig = cache.snapshot(), cache.digest()
    ckpt_mod.audit_prefix_snapshot(snap, dig)               # clean
    snap[1][1][0] ^= 1                   # tamper one token of one run
    with pytest.raises(CheckpointIntegrityError, match="torn or tampered"):
        ckpt_mod.audit_prefix_snapshot(snap, dig)


def test_fault_plan_engine_tier():
    p = FaultPlan(seed=1, crash_at=(5,), digest_skew_at=(3,))
    assert p.crash(5, incarnation=0) and not p.crash(5, incarnation=1)
    assert not p.crash(4, incarnation=0)
    assert p.digest_skew(3, attempt=0) > 0
    assert p.digest_skew(3, attempt=1) == 0   # transient: attempt 0 only
    assert p.any_engine_faults
    # spec parsing round-trips the engine-tier keys
    q = FaultPlan.from_spec("seed=9,crash_at=4|7,skew=0.5")
    assert q.crash_at == (4, 7) and q.p_digest_skew == 0.5
    # probabilistic draws are seed-deterministic
    assert [q.digest_skew(s) for s in range(6)] == \
        [q.digest_skew(s) for s in range(6)]


# --------------------------------------------------- colocated crash sweep
@pytest.fixture(scope="module")
def journaled_golden(micro_model):
    """(steps, tokens, journal) of the fault-free journaled run of the
    forced-preemption trace: the golden every sweep below is held to."""
    return journaled_steps(lambda **kw: _colocated(micro_model, **kw),
                           _trace(N_REQUESTS))


def test_colocated_crash_sweep_quick(micro_model, journaled_golden):
    """Three crash points over the forced-preemption trace (every step is
    the slow-tier sweep): each crash+recover must reproduce the golden
    bit-for-bit."""
    arrivals = _trace(N_REQUESTS)
    mk = lambda **kw: _colocated(micro_model, **kw)          # noqa: E731
    total, golden, journal = journaled_golden
    assert len(golden) == N_REQUESTS
    assert journal.counts().get("preempt", 0) >= 1, "trace lost its bite"
    # three points that differ in kind: before the first checkpoint, mid-run
    # after one, and the last step (most requests already journaled finished)
    for s in (1, total // 2, total - 1):
        res = crash_then_recover(mk, arrivals, s)
        assert res is not None, f"crash at step {s} never fired"
        assert res == golden, f"crash at step {s}: not bit-identical"


def test_colocated_crash_sweep_prefix_cache(micro_model):
    """Strided crash sweep with the prefix cache ON over a template-
    sharing trace (so adoption/COW state is live at most crash points).
    The restore contract — fresh pool, EMPTY cache, KV re-earned via
    re-prefill — must keep every crash+recover bit-identical to the
    fault-free cache-on golden, which itself must equal the cache-off
    golden (the ISSUE 13 transparency contract composed with ISSUE 9)."""
    rng = np.random.RandomState(13)
    tpls = [rng.randint(1, 128, size=16).tolist() for _ in range(3)]
    arrivals = []
    for i in range(10):
        t = int(rng.randint(0, 3))
        tail = rng.randint(1, 128, size=int(rng.randint(1, 5))).tolist()
        arrivals.append((2 * i, tpls[t] + tail, int(rng.randint(2, 6))))
    mk = lambda **kw: _colocated(micro_model, prefix_cache=True,  # noqa: E731
                                 num_pages=12, **kw)
    total, golden, _ = journaled_steps(mk, arrivals)
    _, golden_off, _ = journaled_steps(
        lambda **kw: _colocated(micro_model, num_pages=12, **kw), arrivals)
    assert golden == golden_off, "prefix cache changed tokens"
    stride = max(1, total // 2)
    for s in range(1, total, stride):
        res = crash_then_recover(mk, arrivals, s)
        assert res is not None, f"crash at step {s} never fired"
        assert res == golden, f"crash at step {s}: not bit-identical"


@pytest.mark.slow
def test_colocated_crash_sweep_dense(micro_model, journaled_golden):
    arrivals = _trace(N_REQUESTS)
    mk = lambda **kw: _colocated(micro_model, **kw)          # noqa: E731
    total, golden, _ = journaled_golden
    for s in range(1, total):
        res = crash_then_recover(mk, arrivals, s)
        assert res is not None and res == golden, f"crash at step {s}"


def test_colocated_checkpoint_cadence_sweep(micro_model, journaled_golden):
    """Recovery is cadence-independent: sparse checkpoints only lengthen
    the replay suffix, never change the outcome. cadence=None = no
    checkpoints at all — the whole journal is the suffix."""
    arrivals = _trace(N_REQUESTS)
    mk = lambda **kw: _colocated(micro_model, **kw)          # noqa: E731
    total, golden, _ = journaled_golden
    crash = total // 2
    for every in (2, 16, None):
        res = crash_then_recover(mk, arrivals, crash, checkpoint_every=every)
        assert res == golden, f"checkpoint_every={every}"
    # dense cadence actually produced checkpoints
    j = ControlJournal()
    eng = mk(journal=j, checkpoint_every=2)
    eng.run(max_steps=MAX_STEPS, arrivals=arrivals)
    assert eng.metrics.counters["checkpoints"] >= total // 4
    assert j.counts().get("checkpoint", 0) == eng.metrics.counters[
        "checkpoints"]


def test_restore_compiles_nothing(micro_model, own_programs):
    """The compile guard (ISSUE 9 acceptance): restore is host-only —
    the jit trace caches are untouched by restore itself, and the whole
    recovered run still ends at exactly one decode + one chunk program."""
    arrivals = _trace(N_REQUESTS)
    mk = lambda **kw: _colocated(micro_model, **kw)          # noqa: E731
    journal = ControlJournal()
    eng = mk(journal=journal, checkpoint_every=8,
             fault_plan=FaultPlan(seed=3, crash_at=(11,)))
    with pytest.raises(InjectedCrash):
        eng.run(max_steps=MAX_STEPS, arrivals=arrivals)
    done = sum(1 for e in journal.entries if e["kind"] == "submit")
    own_programs()      # the crashed engine's programs went with its process
    eng2 = mk(journal=journal, checkpoint_every=8)
    assert eng2._step._cache_size() == 0
    assert eng2._chunk_step._cache_size() == 0
    info = ckpt_mod.restore(eng2, ckpt_mod.latest(journal), journal)
    # restore dispatched NOTHING: both trace caches still empty
    assert eng2._step._cache_size() == 0
    assert eng2._chunk_step._cache_size() == 0
    assert info["replayed"] > 0
    res = eng2.run(max_steps=MAX_STEPS, arrivals=arrivals[done:])
    golden = _colocated(micro_model).run(max_steps=MAX_STEPS,
                                        arrivals=arrivals)
    assert res == golden
    stats = eng2.compile_stats
    assert stats["decode_compiles"] == 1
    assert stats["prefill_chunk_compiles"] == 1


def test_recover_without_checkpoint_replays_whole_journal(micro_model,
                                                         journaled_golden):
    """A crash before the first checkpoint cadence still recovers: the
    journal alone (checkpoint=None path) is a complete WAL."""
    arrivals = _trace(N_REQUESTS)
    mk = lambda **kw: _colocated(micro_model, **kw)          # noqa: E731
    _, golden, _ = journaled_golden
    journal = ControlJournal()
    eng = mk(journal=journal, checkpoint_every=1000,  # never reached
             fault_plan=FaultPlan(seed=3, crash_at=(7,)))
    with pytest.raises(InjectedCrash):
        eng.run(max_steps=MAX_STEPS, arrivals=arrivals)
    assert journal.last_checkpoint_entry() is None
    done = sum(1 for e in journal.entries if e["kind"] == "submit")
    eng2 = mk(journal=journal)
    res = eng2.run(max_steps=MAX_STEPS, arrivals=arrivals[done:],
                   recover=True)
    assert res == golden


# ------------------------------------------------------- overload terminals
def test_queue_cap_rejects_typed(micro_model):
    """2x oversubscription against a bounded queue: the excess is shed
    with typed AdmissionRejected terminals, every admitted request
    finishes bit-identical to the uncapped golden, and the engine never
    raises."""
    rng = np.random.RandomState(7)
    arrivals = [(0, list(rng.randint(1, 128, size=int(rng.randint(3, 17)))),
                 int(rng.randint(2, 6))) for _ in range(20)]
    mk = lambda **kw: _colocated(micro_model, num_slots=2, num_pages=8,
                                 **kw)                       # noqa: E731
    golden = mk().run(max_steps=MAX_STEPS, arrivals=arrivals)
    journal = ControlJournal()
    eng = mk(queue_cap=4, journal=journal)
    res = eng.run(max_steps=MAX_STEPS, arrivals=arrivals)
    c = eng.metrics.counters
    assert c["rejections"] > 0 and c["rejections"] == len(eng.failed)
    assert c["requests_submitted"] == 20
    for r in eng.failed:
        assert r.state is RequestState.REJECTED
        assert isinstance(r.failure, AdmissionRejected)
        assert not isinstance(r.failure, TtlExpired)
        assert "queue full" in str(r.failure)
    assert len(res) + c["rejections"] == 20
    for rid, toks in res.items():
        assert toks == golden[rid], f"rid {rid} not bit-identical"
    assert journal.counts()["reject"] == c["rejections"]


def test_ttl_expires_typed(micro_model):
    """A slow-draining queue expires never-admitted requests past their
    TTL with typed TtlExpired terminals; admitted requests are immune
    (preemption requeues never expire) and finish bit-identically."""
    rng = np.random.RandomState(7)
    arrivals = [(0, list(rng.randint(1, 128, size=12)), 5)
                for _ in range(8)]
    mk = lambda **kw: _colocated(micro_model, num_slots=1, num_pages=8,
                                 **kw)                       # noqa: E731
    golden = mk().run(max_steps=MAX_STEPS, arrivals=arrivals)
    journal = ControlJournal()
    eng = mk(ttl_steps=6, journal=journal)
    res = eng.run(max_steps=MAX_STEPS, arrivals=arrivals)
    c = eng.metrics.counters
    assert c["expirations"] > 0 and c["rejections"] == 0
    for r in eng.failed:
        assert isinstance(r.failure, TtlExpired)
        assert "TTL" in str(r.failure)
    assert len(res) + c["expirations"] == 8
    for rid, toks in res.items():
        assert toks == golden[rid]
    assert journal.counts()["expire"] == c["expirations"]


def test_overload_survives_crash_recovery(micro_model):
    """Overload terminals are journaled state: a crash after rejections
    restores them — the recovered engine reports the same terminal set
    and still finishes every admitted request bit-identically."""
    rng = np.random.RandomState(7)
    arrivals = [(0, list(rng.randint(1, 128, size=int(rng.randint(3, 17)))),
                 int(rng.randint(2, 6))) for _ in range(20)]
    mk = lambda **kw: _colocated(micro_model, num_slots=2, num_pages=8,
                                 queue_cap=4, **kw)          # noqa: E731
    golden_eng = mk()
    golden = golden_eng.run(max_steps=MAX_STEPS, arrivals=arrivals)
    golden_failed = sorted(r.rid for r in golden_eng.failed)
    journal = ControlJournal()
    eng = mk(journal=journal, checkpoint_every=4,
             fault_plan=FaultPlan(seed=3, crash_at=(9,)))
    with pytest.raises(InjectedCrash):
        eng.run(max_steps=MAX_STEPS, arrivals=arrivals)
    done = sum(1 for e in journal.entries
               if e["kind"] in ("submit", "reject"))
    eng2 = mk(journal=journal, checkpoint_every=4)
    res = eng2.run(max_steps=MAX_STEPS, arrivals=arrivals[done:],
                   recover=True)
    assert res == golden
    assert sorted(r.rid for r in eng2.failed) == golden_failed
    for r in eng2.failed:
        assert isinstance(r.failure, AdmissionRejected)


# -------------------------------------------------------------- post-mortem
def test_postmortem_embeds_journal_tail(micro_model):
    """Engine error reports carry the forensic record: non-zero counters
    plus the last journal entries (bulky checkpoint payloads elided)."""
    journal = ControlJournal()
    eng = _colocated(micro_model, journal=journal, checkpoint_every=4)
    eng.run(max_steps=MAX_STEPS, arrivals=_trace(6))
    pm = eng._postmortem()
    assert "counters" in pm and "journal tail" in pm
    assert "finish" in pm and "tokens_generated" in pm
    assert "<elided>" in pm or "checkpoint" not in journal.counts()
    # without a journal the report says so instead of crashing
    assert "<no journal attached>" in _colocated(micro_model)._postmortem()
