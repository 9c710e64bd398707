"""Crash-consistent serving (ISSUE 9) across devices: the crash sweep on the
sharded mesh and on the disaggregated pair, and the digest-divergence rung.
The contract and the colocated sweeps are in test_recovery.py (one file was a
third of the tier-1 gate's wall time; split by mechanism); the crash/recover
harness is conftest's.
"""

import pytest

from conftest import (N_REQUESTS, RECOVERY_MAX_STEPS as MAX_STEPS,
                      crash_then_recover, journaled_steps, seeded_trace,
                      sharded_engine)
from triton_dist_tpu.serving import (ControlJournal, DisaggServingEngine,
                                     ReplicatedDecisionError)
from triton_dist_tpu.shmem import FaultPlan
from triton_dist_tpu.shmem.context import initialize_distributed

pytestmark = [pytest.mark.recovery, pytest.mark.serving]


def _trace(n):
    return seeded_trace(n, staggered=True)


@pytest.fixture(scope="module")
def role_ctx():
    return initialize_distributed(axis_names=("role",), mesh_shape=(2,))


def _disagg(micro_model, ctx, **kw):
    cfg, params = micro_model
    kw.setdefault("num_slots", 4)
    kw.setdefault("num_prefill_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 64)
    kw.setdefault("pages_per_seq", 3)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("signal_deadline_steps", 3)
    return DisaggServingEngine(params, cfg, ctx=ctx, **kw)


# ----------------------------------------------------- sharded crash sweep
@pytest.fixture(scope="module")
def n1_journaled(moe_model):
    """(steps, tokens) of the fault-free 4-request run at mesh 1x1x1: THE
    golden of every tier-1 replay across chips in this file. The schedule is
    a replicated decision, so its step count is every mesh's."""
    total, golden, _ = journaled_steps(
        lambda **kw: sharded_engine(moe_model, 1, 1, 1, **kw), _trace(4))
    assert len(golden) == 4
    return total, golden


@pytest.mark.mesh
@pytest.mark.parametrize("tp,sp,ep", [
    (1, 1, 1),
    (1, 2, 1),
    # 5-10 s an interpreter step at n=4: over a minute
    pytest.param(2, 2, 1, marks=pytest.mark.slow),
])
def test_sharded_crash_recovery(moe_model, n1_journaled, tp, sp, ep):
    """Crash+recover on the mesh (n ∈ {1, 2, 4}), mid-run: the restored
    engine reproduces the n=1 golden bit-for-bit — recovery composes with
    the cross-mesh bitwise contract instead of breaking it."""
    total, golden = n1_journaled
    mk = lambda **kw: sharded_engine(moe_model, tp, sp, ep, **kw)  # noqa: E731
    res = crash_then_recover(mk, _trace(4), total // 2)
    assert res is not None and res == golden, f"mesh {tp}x{sp}x{ep}"


@pytest.mark.slow
@pytest.mark.mesh
@pytest.mark.parametrize("tp,sp,ep,stride", [
    (1, 1, 1, 1),
    (1, 2, 1, 3),
    (2, 2, 1, 6),
])
def test_sharded_crash_sweep_dense(moe_model, tp, sp, ep, stride):
    arrivals = _trace(N_REQUESTS)
    mk = lambda **kw: sharded_engine(moe_model, tp, sp, ep, **kw)  # noqa: E731
    total, golden, _ = journaled_steps(mk, arrivals)
    for s in range(1, total, stride):
        res = crash_then_recover(mk, arrivals, s)
        assert res is not None and res == golden, f"crash at step {s}"


@pytest.mark.mesh
def test_sharded_restore_at_n4_is_host_only(moe_model, own_programs):
    """Tier 1's stand-in for the 2x2x1 crash sweep (`slow`), nothing
    dispatched: a fresh 4-rank engine restored from another's snapshot
    reaches the same control digest on every rank, having traced no
    program."""
    own_programs()
    eng = sharded_engine(moe_model, 2, 2, 1, journal=ControlJournal())
    for _, prompt, mnt in _trace(3):
        eng.submit(prompt, mnt)
    state = eng._capture_state()
    eng2 = sharded_engine(moe_model, 2, 2, 1, journal=ControlJournal())
    assert eng2.control_digest() != eng.control_digest()
    eng2._restore_state(state)
    assert eng2.n_ranks == 4
    assert eng2.control_digest() == eng.control_digest()
    eng2.check_replicated_decisions()
    assert [r.rid for r in eng2.sched.queue] == [0, 1, 2]
    assert eng2._step._cache_size() == eng2._chunk_step._cache_size() == 0


# ----------------------------------------------- digest-divergence rung
@pytest.mark.mesh
def test_digest_skew_absorbed_by_restore(moe_model, n1_journaled):
    """A transient seeded digest divergence is QUARANTINED and absorbed:
    exactly one digest_recovery, tokens still golden, nothing raised."""
    arrivals = _trace(4)
    _, golden = n1_journaled
    journal = ControlJournal()
    eng = sharded_engine(moe_model, 1, 1, 2, journal=journal,
                         checkpoint_every=2, digest_every=1,
                         fault_plan=FaultPlan(seed=5, digest_skew_at=(5,)))
    res = eng.run(max_steps=MAX_STEPS, arrivals=arrivals)
    c = eng.metrics.counters
    assert c["digest_recoveries"] == 1
    assert c["restores"] == 1
    assert c["faults_injected"] >= 1
    assert res == golden
    assert journal.counts().get("digest_divergence") == 1
    assert eng.metrics.hist["digest_recovery_s"].count == 1


@pytest.mark.mesh
def test_persistent_digest_skew_escalates(moe_model):
    """Skew that re-diverges with no agreed step since the restore is
    PERSISTENT: the rung escalates (raises) instead of looping, and the
    report embeds the counters + journal tail post-mortem."""
    journal = ControlJournal()
    eng = sharded_engine(moe_model, 1, 2, 1, journal=journal,
                         checkpoint_every=4, digest_every=1)
    eng._digest_skew[1] = 1               # persistent per-rank corruption
    with pytest.raises(ReplicatedDecisionError, match="persistent skew"):
        eng.run(max_steps=MAX_STEPS, arrivals=_trace(8))
    assert eng.metrics.counters["digest_recoveries"] == 1  # tried once
    try:
        eng2 = sharded_engine(moe_model, 1, 2, 1, journal=ControlJournal(),
                              checkpoint_every=4, digest_every=1)
        eng2._digest_skew[1] = 1
        eng2.run(max_steps=MAX_STEPS, arrivals=_trace(8))
    except ReplicatedDecisionError as e:
        assert "counters" in str(e) and "journal tail" in str(e)


@pytest.mark.mesh
def test_digest_skew_without_journal_still_raises(moe_model):
    """No journal = no restore rung: the pre-ISSUE-9 hard raise stands
    (fail loud beats silently serving forked block tables)."""
    eng = sharded_engine(moe_model, 1, 2, 1, digest_every=1)
    eng._digest_skew[1] = 1
    with pytest.raises(ReplicatedDecisionError, match="digest diverged"):
        eng.run(max_steps=MAX_STEPS, arrivals=_trace(8))
    assert eng.metrics.counters["digest_recoveries"] == 0


# ------------------------------------------------------ disagg crash sweep
@pytest.mark.disagg
def test_disagg_crash_recovery(micro_model, role_ctx):
    """Crash+recover on the disaggregated engine, including a crash with
    a migration IN FLIGHT: the restarted engine re-admits the migrated
    request through the rebuilt ledger (re-prefill + re-migrate), never
    fails it for having been half-handed-off."""
    arrivals = _trace(4)
    mk = lambda **kw: _disagg(micro_model, role_ctx, **kw)   # noqa: E731
    total, golden, ref = journaled_steps(mk, arrivals)
    # a crash point with a handoff in flight: a rid went MIGRATING at
    # step s (journal "handoff") and only finished at some step > s + 1
    finish_step = {e["rid"]: e["step"] for e in ref.entries
                   if e["kind"] == "finish"}
    midflight = [e["step"] for e in ref.entries if e["kind"] == "handoff"
                 and finish_step.get(e["rid"], 10**9) > e["step"] + 1]
    assert midflight, "no handoff was ever in flight: the trace lost its bite"
    points = sorted({midflight[0], total - 1})
    for s in points:
        res = crash_then_recover(mk, arrivals, s)
        assert res is not None and res == golden, f"crash at step {s}"


@pytest.mark.slow
@pytest.mark.disagg
def test_disagg_crash_sweep_dense(micro_model, role_ctx):
    arrivals = _trace(N_REQUESTS)
    mk = lambda **kw: _disagg(micro_model, role_ctx, **kw)   # noqa: E731
    total, golden, _ = journaled_steps(mk, arrivals)
    for s in range(1, total):
        res = crash_then_recover(mk, arrivals, s)
        assert res is not None and res == golden, f"crash at step {s}"


@pytest.mark.disagg
def test_disagg_journal_records_migration(micro_model, role_ctx):
    """The disagg journal carries the migration story: migrate attempts
    (with chunk + page counts), handoffs, and the per-event digest over
    BOTH workers' control planes."""
    journal = ControlJournal()
    eng = _disagg(micro_model, role_ctx, journal=journal, checkpoint_every=8)
    eng.run(max_steps=MAX_STEPS, arrivals=_trace(8))
    counts = journal.counts()
    assert counts["migrate"] >= counts["handoff"] >= 1
    assert counts["finish"] == 8
    m = next(e for e in journal.entries if e["kind"] == "migrate")
    assert m["pages"] >= 1 and "chunk" in m and "attempt" in m
    # pool audit: nothing leaked through the journaled run
    assert eng.alloc_p.used_pages == 0 and eng.alloc_d.used_pages == 0
    eng.alloc_p.check(eng.channel.ledger)
    eng.alloc_d.check(eng.channel.ledger)
