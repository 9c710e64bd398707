"""Chaos harness (ISSUE 7): replay ONE short disaggregated trace (the
fewest requests that make every schedule below inject a fault) under a
sweep of seeded fault schedules and hold the engine to the robustness
contract:

- **survivable schedule** (degradation allowed): every request finishes
  and every token stream is BIT-IDENTICAL to the fault-free golden run —
  whatever mix of dropped/delayed/duplicated signals and dead peers the
  plan injected, the recovery ladder (deadline → retry/backoff → local
  re-prefill) must erase it without changing a single token.
- **unsurvivable schedule** (degradation off): the injected faults fail
  exactly the requests they touch, each with a TYPED reason carrying the
  ledger dump — never a hang, never an engine crash — and every
  un-faulted request still finishes bit-identical.
- after EVERY run, faulted or not: both page pools pass the
  ``KVPagePool.check`` full-invariant audit with zero pages in use.
"""


import pytest

from conftest import seeded_trace
from triton_dist_tpu.serving import (ControlJournal, DisaggServingEngine,
                                     EngineStallError,
                                     MigrationSignalTimeout,
                                     SignalProtocolError)
from triton_dist_tpu.serving.scheduler import RequestState
from triton_dist_tpu.shmem import FaultPlan
from triton_dist_tpu.shmem.context import initialize_distributed
from triton_dist_tpu.shmem.faults import InjectedCrash

pytestmark = [pytest.mark.disagg, pytest.mark.chaos]

N_REQUESTS = 4            # fewest that make every schedule inject (asserted)
MAX_STEPS = 600           # step cap far above any legitimate run length


@pytest.fixture(scope="module")
def role_ctx():
    return initialize_distributed(axis_names=("role",), mesh_shape=(2,))


_trace = seeded_trace(N_REQUESTS, staggered=True)


def _engine(micro_model, ctx, **kw):
    cfg, params = micro_model
    kw.setdefault("num_slots", 4)
    kw.setdefault("num_prefill_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 64)
    # 16 + 5 tokens fit 3 pages, and the chunk program's grid is rows x pages
    kw.setdefault("pages_per_seq", 3)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("signal_deadline_steps", 3)
    kw.setdefault("max_retries", 3)
    return DisaggServingEngine(params, cfg, ctx=ctx, **kw)


def _audit(eng):
    """The end-of-run invariant wall (ISSUE 7 satellite): the pools'
    full self-audit, cross-checked against the live ledger, with zero
    residual ownership."""
    assert eng.alloc_p.used_pages == 0, "prefill pool leaked pages"
    assert eng.alloc_d.used_pages == 0, "decode pool leaked pages"
    eng.alloc_p.check(eng.channel.ledger)
    eng.alloc_d.check(eng.channel.ledger)


@pytest.fixture(scope="module")
def golden(micro_model, role_ctx):
    """Fault-free run of the trace — the bit-identity reference."""
    eng = _engine(micro_model, role_ctx)
    gold = eng.run(max_steps=MAX_STEPS, arrivals=_trace)
    assert len(gold) == N_REQUESTS
    _audit(eng)
    return gold


# the sweep: ≥8 seeded schedules covering the whole fault matrix. All of
# them are SURVIVABLE with degradation allowed (local re-prefill needs no
# peer), so each must reproduce the golden tokens bit for bit.
SCHEDULES = [
    ("clean", FaultPlan(seed=0)),
    ("drop_light", FaultPlan(seed=11, p_drop=0.25)),
    ("drop_heavy", FaultPlan(seed=12, p_drop=1.0)),
    ("delay", FaultPlan(seed=13, p_delay=0.9, max_delay_steps=12)),
    ("dup", FaultPlan(seed=14, p_dup=0.5)),
    ("drop_delay_mix", FaultPlan(seed=15, p_drop=0.2, p_delay=0.4,
                                 p_dup=0.1)),
    ("dead_peer_early", FaultPlan(seed=16, dead_peer_after=2)),
    ("dead_peer_late", FaultPlan(seed=17, dead_peer_after=5)),
    ("storm", FaultPlan(seed=18, p_drop=0.5, p_dup=0.3, p_delay=0.5,
                        max_delay_steps=10)),
    ("scoped_drop", FaultPlan(seed=19, p_drop=1.0, rids=(1, 3))),
]


@pytest.mark.quick
@pytest.mark.parametrize("name,plan", SCHEDULES,
                         ids=[n for n, _ in SCHEDULES])
def test_survivable_schedule_bit_identical(micro_model, role_ctx, golden,
                                           name, plan):
    """The headline sweep: under every seeded schedule, with the full
    ladder available, all requests finish with golden-identical
    tokens, nothing fails, nothing hangs, and the pools audit clean."""
    eng = _engine(micro_model, role_ctx, fault_plan=plan)
    res = eng.run(max_steps=MAX_STEPS, arrivals=_trace)
    assert eng.failed == [], (
        f"{name}: ladder should have saved every request; "
        f"failures: {[(r.rid, r.failure) for r in eng.failed]}")
    assert sorted(res) == sorted(golden), f"{name}: requests went missing"
    for rid in golden:
        assert res[rid] == golden[rid], (
            f"{name}: rid {rid} tokens diverged under faults")
    # a schedule that injected nothing proves nothing: it must FAIL here
    injected = eng.metrics.counters["faults_injected"] > 0
    assert injected == (name != "clean"), (
        f"{name}: schedule injected nothing — sweep lost its teeth")
    _audit(eng)


def test_replay_is_deterministic(micro_model, role_ctx):
    """Same seed → byte-identical recovery trajectory: not just the same
    tokens, the same retry/degradation/fault counts. The property that
    makes a chaos failure reproducible from one integer."""
    plan = FaultPlan(seed=15, p_drop=0.2, p_delay=0.4, p_dup=0.1)
    trace = _trace[:3]      # determinism needs two runs, not two LONG runs
    runs = []
    for _ in range(2):
        eng = _engine(micro_model, role_ctx, fault_plan=plan)
        res = eng.run(max_steps=MAX_STEPS, arrivals=trace)
        c, d = eng.metrics.counters, eng.metrics_decode.counters
        runs.append((res, c["faults_injected"], d["retries"],
                     d["degradations"], d["failed_requests"]))
    assert runs[0] == runs[1]


def test_dropped_signal_recovers_via_retry(micro_model, role_ctx, golden):
    """ISSUE 7 acceptance: a dropped-signal schedule that the RETRY rung
    alone absorbs — retries counted, zero degradations, tokens golden."""
    plan = FaultPlan(seed=21, p_drop=0.3)
    eng = _engine(micro_model, role_ctx, fault_plan=plan, max_retries=6)
    res = eng.run(max_steps=MAX_STEPS, arrivals=_trace)
    assert eng.metrics_decode.counters["retries"] > 0
    assert eng.metrics_decode.counters["degradations"] == 0, (
        "this seed was chosen so retry alone recovers — degradation "
        "firing means the retry rung regressed")
    assert eng.failed == []
    for rid in golden:
        assert res[rid] == golden[rid]
    _audit(eng)


def test_dead_peer_degrades_via_local_reprefill(micro_model, role_ctx,
                                                golden):
    """ISSUE 7 acceptance: a dead peer forces the DEGRADE rung — every
    request caught mid-migration re-prefills locally on the decode
    worker, survivors are bit-identical, the engine never stalls."""
    plan = FaultPlan(seed=22, dead_peer_after=3)
    eng = _engine(micro_model, role_ctx, fault_plan=plan,
                  signal_deadline_steps=2, max_retries=1)
    res = eng.run(max_steps=MAX_STEPS, arrivals=_trace)
    assert eng.metrics_decode.counters["degradations"] > 0
    assert eng.metrics_decode.hist["degraded_prefill_tokens"].count > 0
    assert eng.metrics_decode.hist["degraded_ttft_s"].count > 0
    assert eng.failed == []
    for rid in golden:
        assert res[rid] == golden[rid]
    _audit(eng)


@pytest.mark.parametrize("name,plan,faulted_rids", [
    ("drop_heavy", FaultPlan(seed=12, p_drop=1.0), None),
    ("scoped_drop", FaultPlan(seed=19, p_drop=1.0, rids=(1, 3)),
     {1, 3}),
    ("dup_scoped", FaultPlan(seed=23, p_dup=1.0, rids=(2,)), {2}),
], ids=["drop_heavy", "scoped_drop", "dup_scoped"])
def test_unsurvivable_schedule_fails_typed(micro_model, role_ctx, golden,
                                           name, plan, faulted_rids):
    """Degradation OFF: the same schedules must now fail exactly the
    requests they touch — typed reasons with the ledger dump, the engine
    still running, every untouched request bit-identical (the
    per-request failure domain, demonstrated on neighbors)."""
    eng = _engine(micro_model, role_ctx, fault_plan=plan,
                  allow_degradation=False, signal_deadline_steps=2,
                  max_retries=1)
    res = eng.run(max_steps=MAX_STEPS, arrivals=_trace)   # never raises
    failed = {r.rid for r in eng.failed}
    assert failed, f"{name}: an unsurvivable schedule must fail someone"
    if faulted_rids is not None:
        assert failed == faulted_rids, (
            f"{name}: failure domain leaked — {failed} vs {faulted_rids}")
    for req in eng.failed:
        assert req.state is RequestState.FAILED
        assert isinstance(req.failure,
                          (MigrationSignalTimeout, SignalProtocolError))
        assert "chunk" in str(req.failure), "ledger dump missing"
        assert req.rid not in res
    # everyone the plan did NOT touch is golden
    for rid in golden:
        if rid not in failed:
            assert res[rid] == golden[rid], (
                f"{name}: un-faulted rid {rid} diverged")
    assert (eng.metrics_decode.counters["failed_requests"]
            == len(eng.failed))
    _audit(eng)


def test_over_signal_is_protocol_error_not_coverage(micro_model, role_ctx):
    """The silent-poison fix (ISSUE 7 satellite): a duplicated increment
    must be DETECTED as over-signal, not widen coverage. With degradation
    off the poisoned request fails carrying SignalProtocolError."""
    plan = FaultPlan(seed=24, p_dup=1.0, rids=(0,))
    eng = _engine(micro_model, role_ctx, fault_plan=plan,
                  allow_degradation=False)
    trace = _trace[:2]
    res = eng.run(max_steps=MAX_STEPS, arrivals=trace)
    failed = {r.rid: r for r in eng.failed}
    assert set(failed) == {0}
    assert isinstance(failed[0].failure, SignalProtocolError)
    assert "over-signal" in str(failed[0].failure)
    assert sorted(res) == [1]
    _audit(eng)


@pytest.mark.recovery
def test_crash_under_signal_chaos_recovers_golden(micro_model, role_ctx,
                                                  golden):
    """ISSUE 9 satellite: the crash rung composes with the ISSUE-7
    ladder. A schedule mixing dropped signals with a mid-trace CRASH must
    still land on the golden tokens — the restarted engine replays the
    journal, re-earns every dropped signal through retry, and the two
    fault tiers never observe each other."""
    plan = FaultPlan(seed=31, p_drop=0.25, crash_at=(5,))
    journal = ControlJournal()
    eng = _engine(micro_model, role_ctx, fault_plan=plan, max_retries=6,
                  journal=journal, checkpoint_every=4)
    with pytest.raises(InjectedCrash):
        eng.run(max_steps=MAX_STEPS, arrivals=_trace)
    done = sum(1 for e in journal.entries if e["kind"] == "submit")
    # the restarted incarnation keeps the SAME plan: signal drops stay
    # live after restore (only the crash is incarnation-gated)
    eng2 = _engine(micro_model, role_ctx, fault_plan=plan, max_retries=6,
                   journal=journal, checkpoint_every=4)
    res = eng2.run(max_steps=MAX_STEPS, arrivals=_trace[done:],
                   recover=True)
    assert eng2.metrics.counters["restores"] == 1
    assert eng2.failed == []
    assert sorted(res) == sorted(golden)
    for rid in golden:
        assert res[rid] == golden[rid], f"rid {rid} diverged"
    _audit(eng2)


def test_stall_watchdog_backstops_ladder_bugs(micro_model, role_ctx,
                                              monkeypatch):
    """If the ladder itself were broken (here: its terminal verb is
    stubbed out so an expired request just waits forever), the global
    step-space watchdog must convert the livelock into EngineStallError
    with a state dump — the 'never a hang' guarantee does not depend on
    the ladder being correct."""
    plan = FaultPlan(seed=25, p_drop=1.0)
    eng = _engine(micro_model, role_ctx, fault_plan=plan,
                  signal_deadline_steps=2, max_retries=0,
                  stall_deadline_steps=40)
    monkeypatch.setattr(eng, "_degrade_or_fail", lambda *a, **k: None)
    with pytest.raises(EngineStallError, match="no progress"):
        eng.run(max_steps=MAX_STEPS, arrivals=_trace[:3])
