"""Elastic autoscaling (ISSUE 18): replica lifecycle, graceful drain,
lend-ahead, crash-mid-drain, the controller, and the churn bounds.

THE contract, four rungs:

- **lifecycle**: WARMING → ACTIVE → DRAINING → RETIRED, with KILLED an
  excursion any alive state may take; only ACTIVE admits, DRAINING still
  steps and lends, indices are append-only and never reused.
- **drain never changes tokens**: a draining replica requeues its queued
  requests to peers through the journal cursor (so a crash after the
  move never re-serves them), finishes its in-flight decodes in place,
  lends its hot prefixes ahead to their rendezvous successors, and
  retires — every trace bit-identical to ``expected_tokens``.
- **crash-mid-drain degrades to the PR 12 ladder**: kill of a DRAINING
  replica is legal; restore resumes the DRAIN (never admission), journal
  replay re-queues the live requests, and the fleet converges with the
  same tokens.
- **the controller is deterministic and resumable**: scaling decisions
  are a pure function of the windowed step-space attainment feed, every
  decision is journaled, and ``Autoscaler.resume`` rebuilds the fleet
  view (cursor, cooldown clock, decision log) from the journal alone.

Plus the closed-form rendezvous churn bound (a scale event at fleet size
N moves <= c/N of a fixed key population) and the units underneath
(``AttainmentWindow``, ``parse_budgets``).
"""

import json
import os
import subprocess
import sys
from collections import deque

import numpy as np
import pytest

from conftest import WATCHDOG_S

from triton_dist_tpu.serving import (Autoscaler, Cluster, ReplicaState,
                                     SimEngine, expected_tokens,
                                     generate_arrivals, parse_budgets,
                                     parse_slo, parse_workload)
from triton_dist_tpu.serving.journal import ControlJournal
from triton_dist_tpu.serving.metrics import AttainmentWindow
from triton_dist_tpu.shmem import FaultPlan

pytestmark = [pytest.mark.autoscale, pytest.mark.serving]

PS = 8                        # page size everywhere below


def _mk_cluster(replicas=2, tmp_path=None, slots=4, **kw):
    def factory(journal):
        return SimEngine(num_slots=slots, page_size=PS, num_pages=33,
                         pages_per_seq=8, journal=journal,
                         prefix_cache=True, prefill_chunk=PS)

    return Cluster(factory, replicas=replicas,
                   journal_dir=None if tmp_path is None else str(tmp_path),
                   **kw)


def _templates(n=4, seed=23):
    rng = np.random.RandomState(seed)
    return [tuple(int(t) for t in rng.randint(1, 997, size=3 * PS))
            for _ in range(n)]


def _drain_all(cl, asc=None, max_steps=100_000):
    """Step to quiescence with the controller (if any) still ticking —
    a restore right after an idle step is not quiescence, hence the
    debounce (same loop as cluster_sim --autoscale)."""
    idle = 0
    for _ in range(max_steps):
        if idle >= 3:
            break
        idle = 0 if cl.step() else idle + 1
        if asc is not None:
            asc.step()
    return cl.results()


def _assert_golden(cl, sent):
    res = cl.results()
    for gid, (prompt, mnt) in sent.items():
        assert res[gid] == expected_tokens(list(prompt), mnt), (
            f"gid {gid} diverged from the closed-form golden")


# ---------------------------------------------------------------------------
# units: the attainment window and the budget spec
# ---------------------------------------------------------------------------

def test_attainment_window():
    w = AttainmentWindow(4)
    assert w.count(("ttft", "chat")) == 0
    for v in (1, 2, 3, 10):
        w.observe(("ttft", "chat"), v)
    assert w.count(("ttft", "chat")) == 4
    assert w.attainment(("ttft", "chat"), 3) == 0.75
    # window semantics: a 5th sample evicts the oldest (the 1)
    w.observe(("ttft", "chat"), 20)
    assert w.count(("ttft", "chat")) == 4
    assert w.attainment(("ttft", "chat"), 3) == 0.5
    # series are independent
    w.observe(("itl", "batch"), 1)
    assert w.count(("itl", "batch")) == 1
    assert w.attainment(("itl", "batch"), 1) == 1.0


def test_parse_budgets():
    assert parse_budgets("chat:8") == {"chat": (8, None)}
    assert parse_budgets(" chat:8/2 , batch:64 ") == {
        "chat": (8, 2), "batch": (64, None)}
    with pytest.raises((AssertionError, ValueError)):
        parse_budgets("chat")


# ---------------------------------------------------------------------------
# lifecycle: states, promotion, admission gating, terminal retire
# ---------------------------------------------------------------------------

@pytest.mark.quick
def test_lifecycle_transitions(tmp_path):
    cl = _mk_cluster(replicas=2, tmp_path=tmp_path)
    assert [r.lifecycle for r in cl.replicas] == [ReplicaState.ACTIVE] * 2

    # scale-up joins WARMING: alive, not admitting, not stepped
    rep = cl.add_replica(warm_steps=2)
    assert rep.index == 2 and rep.alive and not rep.admitting
    assert cl.lifecycle_counts() == {"active": 2, "warming": 1}
    assert len(cl.admitting_replicas) == 2
    steps_before = rep.engine._steps
    cl.step()                     # warm_remaining 2 -> 1: still warming
    assert rep.lifecycle is ReplicaState.WARMING
    assert rep.engine._steps == steps_before, "WARMING must not step"
    cl.step()                     # promotion
    assert rep.lifecycle is ReplicaState.ACTIVE
    assert len(cl.admitting_replicas) == 3

    # drain: admission stops NOW, the replica still steps, then retires
    cl.begin_drain(2)
    assert rep.draining and not rep.admitting and rep.alive
    _drain_all(cl)
    assert rep.lifecycle is ReplicaState.RETIRED and not rep.alive
    assert cl.metrics.counters["retires"] == 1

    # terminal/illegal transitions are loud
    with pytest.raises(AssertionError):
        cl.begin_drain(2)         # retired replicas cannot drain
    cl.begin_drain(1)
    with pytest.raises(AssertionError):
        cl.begin_drain(0)         # never drain the last admitting replica
    # the scale history recorded every membership event in order
    kinds = [k for _, k, _ in cl.scale_history]
    assert kinds[:4] == ["scale_up", "drain_begin", "drain_done", "retire"]


# ---------------------------------------------------------------------------
# graceful drain: journal-cursor requeue, bitwise traces
# ---------------------------------------------------------------------------

@pytest.mark.quick
def test_drain_requeues_queued_bitwise(tmp_path):
    """Saturate one replica's queue, drain it: every QUEUED request moves
    to a peer under its own gid (journaled as a requeue on the source),
    in-flight slots finish in place, and every token matches the closed
    form — the drain changed the schedule, never the outputs."""
    cl = _mk_cluster(replicas=2, tmp_path=tmp_path, slots=2)
    rng = np.random.RandomState(5)
    sent = {}
    for _ in range(12):
        prompt = [int(t) for t in rng.randint(1, 997, size=6)]
        mnt = int(rng.randint(2, 5))
        sent[cl.submit(prompt, mnt)] = (tuple(prompt), mnt)
    victim = max(cl.replicas, key=lambda r: r.load).index
    moved = cl.begin_drain(victim)
    assert moved >= 1, "a saturated 2-slot replica must have had a queue"
    assert cl.metrics.counters["requeues"] == moved
    # the source journal carries one requeue event per moved request, so
    # a post-move crash replay drops them instead of re-serving them
    jpath = os.path.join(str(tmp_path), f"journal-r{victim}.jsonl")
    kinds = [json.loads(line).get("kind")
             for line in open(jpath, encoding="utf-8")]
    assert kinds.count("requeue") == moved
    res = _drain_all(cl)
    assert len(res) == len(sent) and not cl.failed_gids
    _assert_golden(cl, sent)
    assert cl.replicas[victim].lifecycle is ReplicaState.RETIRED


# ---------------------------------------------------------------------------
# crash-mid-drain: kill of DRAINING is legal, restore resumes the drain
# ---------------------------------------------------------------------------

@pytest.mark.quick
def test_crash_mid_drain_resumes_and_stays_bitwise(tmp_path):
    cl = _mk_cluster(replicas=2, tmp_path=tmp_path, slots=2)
    rng = np.random.RandomState(9)
    sent = {}
    for _ in range(10):
        prompt = [int(t) for t in rng.randint(1, 997, size=6)]
        mnt = int(rng.randint(2, 5))
        sent[cl.submit(prompt, mnt)] = (tuple(prompt), mnt)
    victim = max(cl.replicas, key=lambda r: r.load).index
    cl.begin_drain(victim)
    rep = cl.replicas[victim]
    assert rep.draining

    cl.kill(victim)               # crash MID-drain: legal
    assert rep.lifecycle is ReplicaState.KILLED
    assert rep._prekill is ReplicaState.DRAINING

    cl.restore(victim)            # comes back DRAINING, never admitting
    assert rep.draining and not rep.admitting
    res = _drain_all(cl)
    assert rep.lifecycle is ReplicaState.RETIRED
    # nothing lost, nothing doubled: the journal replay re-queued the
    # replica's live requests, the requeue events dropped the moved ones
    assert len(res) == len(sent) and not cl.failed_gids
    _assert_golden(cl, sent)


def test_autoscaler_auto_restores_crashed_drainer(tmp_path):
    """The controller's healing rung: a replica that died DRAINING is
    restored on the next tick without any policy signal — budgets never
    reach min_samples here, so the ONLY controller action is the heal."""
    cl = _mk_cluster(replicas=2, tmp_path=tmp_path, slots=2)
    asc = Autoscaler(cl, {"chat": 8}, window=8, min_samples=10**9,
                     max_replicas=4, cooldown=1)
    rng = np.random.RandomState(11)
    sent = {}
    for _ in range(8):
        prompt = [int(t) for t in rng.randint(1, 997, size=6)]
        sent[cl.submit(prompt, 3)] = (tuple(prompt), 3)
    victim = max(cl.replicas, key=lambda r: r.load).index
    cl.begin_drain(victim)
    cl.kill(victim)
    res = _drain_all(cl, asc)
    assert cl.replicas[victim].lifecycle is ReplicaState.RETIRED
    assert cl.metrics.counters["restores"] == 1
    assert len(res) == len(sent) and not cl.failed_gids
    _assert_golden(cl, sent)


# ---------------------------------------------------------------------------
# lend-ahead: push to the rendezvous successor, degrade on a dead peer,
# typed no-op on a mixed fleet
# ---------------------------------------------------------------------------

def _warm_template(cl, t, seed):
    rng = np.random.RandomState(seed)
    sent = {}
    for _ in range(3):
        prompt = list(t) + [int(x) for x in rng.randint(1, 997, size=3)]
        sent[cl.submit(prompt, 3)] = (tuple(prompt), 3)
        cl.drain()
    return sent


@pytest.mark.quick
def test_lend_ahead_lands_on_rendezvous_successor(tmp_path):
    cl = _mk_cluster(replicas=3, tmp_path=tmp_path, lend=True)
    t = _templates(1, seed=31)[0]
    sent = _warm_template(cl, t, seed=4)
    owner = cl.prefix_index.match(t)[1]
    cl.begin_drain(owner)
    _drain_all(cl)
    assert cl.replicas[owner].lifecycle is ReplicaState.RETIRED
    assert cl.metrics.counters["lend_aheads"] >= 1
    assert cl.metrics.counters["lend_ahead_pages"] >= 3
    # the index was re-pointed at the successor that adopted the pages —
    # exactly the replica the prefix's future traffic rendezvouses to
    succ = cl.prefix_index.match(t)[1]
    assert succ is not None and succ != owner
    assert succ == cl.rendezvous_owner(t)
    assert cl.replicas[succ].engine.prefix_cache.match(t), (
        "successor must hold the lent prefix warm")
    # and the next request is a warm hit there, bitwise
    prompt = list(t) + [7, 7, 7]
    gid = cl.submit(prompt, 3)
    cl.drain()
    assert cl.results()[gid] == expected_tokens(prompt, 3)
    hist = cl.replicas[succ].engine.metrics.hist
    assert (hist["ttft_cached_steps"].count
            + hist["ttft_rewarmed_steps"].count) >= 1
    _assert_golden(cl, sent)


def test_lend_ahead_dead_successor_degrades_to_cold(tmp_path):
    """A dead-peer plan kills every lend-ahead in flight: the ladder
    burns its rungs, records typed degradations, the retire is NOT
    blocked, and the successor serves the template cold — bitwise."""
    plan = FaultPlan(seed=3, dead_peer_after=0)
    cl = _mk_cluster(replicas=3, tmp_path=tmp_path, lend=True,
                     lend_plan=plan)
    t = _templates(1, seed=37)[0]
    sent = _warm_template(cl, t, seed=6)
    owner = cl.prefix_index.match(t)[1]
    degr0 = cl.metrics.counters["lend_degradations"]
    cl.begin_drain(owner)
    _drain_all(cl)
    assert cl.replicas[owner].lifecycle is ReplicaState.RETIRED, (
        "an exhausted lend-ahead ladder must never block the retire")
    assert cl.metrics.counters["lend_aheads"] == 0
    assert cl.metrics.counters["lend_degradations"] > degr0
    cl.lending._plan = FaultPlan(seed=3)       # transport heals
    prompt = list(t) + [7, 7, 7]
    gid = cl.submit(prompt, 3)
    cl.drain()
    assert cl.results()[gid] == expected_tokens(prompt, 3), (
        "cold re-prefill after a degraded lend-ahead must stay bitwise")
    _assert_golden(cl, sent)


def test_lend_ahead_mixed_fleet_is_typed_noop(tmp_path):
    cl = _mk_cluster(replicas=2, tmp_path=tmp_path, lend=True)
    t = _templates(1, seed=41)[0]
    _warm_template(cl, t, seed=8)
    owner = cl.prefix_index.match(t)[1]
    # drainee without the lend surface: the whole call is one typed no-op
    cl.replicas[owner].engine.export_prefix = None
    cl.begin_drain(owner)
    _drain_all(cl)
    assert cl.replicas[owner].lifecycle is ReplicaState.RETIRED
    assert cl.metrics.counters["lend_aheads"] == 0
    assert cl.metrics.counters["lend_ahead_noops"] == 1

    # successor without adopt: per-prefix no-ops, retire still clean
    cl2 = _mk_cluster(replicas=2, tmp_path=None, lend=True)
    _warm_template(cl2, t, seed=8)
    owner2 = cl2.prefix_index.match(t)[1]
    cl2.replicas[1 - owner2].engine.adopt_prefix = None
    cl2.begin_drain(owner2)
    _drain_all(cl2)
    assert cl2.replicas[owner2].lifecycle is ReplicaState.RETIRED
    assert cl2.metrics.counters["lend_aheads"] == 0
    assert cl2.metrics.counters["lend_ahead_noops"] >= 1


# ---------------------------------------------------------------------------
# the controller: hysteresis, cooldown, min/max clamps, journal resume
# ---------------------------------------------------------------------------

def _feed(cl, cls, ttft, n):
    for _ in range(n):
        cl._latency_feed.append((cls, ttft, None))


def test_autoscaler_up_down_cooldown_and_clamps(tmp_path):
    cl = _mk_cluster(replicas=1, tmp_path=tmp_path)
    asc = Autoscaler(cl, {"chat": 8}, window=8, min_samples=4,
                     min_replicas=1, max_replicas=2, cooldown=5,
                     warm_steps=0)
    # no samples -> no decision
    assert asc.step() is None
    # SLO misses -> ONE scale-up, then the cooldown holds the line
    _feed(cl, "chat", 50, 8)
    assert asc.step() == ("scale_up", 1)
    assert cl.replicas[1].lifecycle is ReplicaState.WARMING
    _feed(cl, "chat", 50, 8)
    for _ in range(4):
        cl.step()
        assert asc.step() is None, "cooldown must absorb the burst front"
    # still missing after cooldown, but the fleet is at max: clamped
    _feed(cl, "chat", 50, 8)
    cl.step()
    assert asc.step() is None
    assert len(cl.replicas) == 2
    # SLO comfortably met -> drain the highest-index replica... but
    # never below min_replicas
    _feed(cl, "chat", 1, 8)
    dec = None
    for _ in range(asc.cooldown + 1):
        cl.step()
        dec = dec or asc.step()
    assert dec == ("drain_begin", 1)
    _drain_all(cl, asc)
    assert cl.replicas[1].lifecycle is ReplicaState.RETIRED
    _feed(cl, "chat", 1, 8)
    for _ in range(asc.cooldown + 1):
        cl.step()
        assert asc.step() is None, "min_replicas is a floor"
    assert len(cl.admitting_replicas) == 1


def test_autoscaler_wont_drain_into_overload(tmp_path):
    """The down-side half of the dead band: attainment alone never
    drains — the survivors must also be able to SEAT the current load."""
    cl = _mk_cluster(replicas=2, tmp_path=tmp_path, slots=2)
    asc = Autoscaler(cl, {"chat": 8}, window=8, min_samples=4,
                     min_replicas=1, max_replicas=2, cooldown=1)
    rng = np.random.RandomState(13)
    for _ in range(8):     # both replicas seated + queued
        cl.submit([int(t) for t in rng.randint(1, 997, size=6)], 8)
    _feed(cl, "chat", 1, 8)
    assert asc.step() is None, (
        "perfect attainment must not drain while the load needs both "
        "replicas' slots")
    _drain_all(cl, asc)


def test_controller_journal_and_resume(tmp_path):
    jpath = Autoscaler.journal_path_for(str(tmp_path))
    cl = _mk_cluster(replicas=1, tmp_path=tmp_path)
    asc = Autoscaler(cl, {"chat": 8}, window=8, min_samples=4,
                     min_replicas=1, max_replicas=2, cooldown=3,
                     warm_steps=0, journal=jpath)
    _feed(cl, "chat", 50, 8)
    assert asc.step() == ("scale_up", 1)
    cl.step()
    _feed(cl, "chat", 1, 8)
    dec = None
    for _ in range(asc.cooldown + 1):
        cl.step()
        dec = dec or asc.step()
    assert dec == ("drain_begin", 1)
    _drain_all(cl, asc)
    assert cl.replicas[1].lifecycle is ReplicaState.RETIRED

    # the journal carries the full decision ladder in order
    kinds = [e["kind"] for e in ControlJournal.load(jpath).entries]
    assert kinds == ["scale_up", "drain_begin", "drain_done", "retire"]

    # controller crash: resume() rebuilds the fleet view from the
    # journal alone — cursor, cooldown clock, decision log — and the
    # next ticks neither re-journal old events nor re-drain retirees
    asc2 = Autoscaler.resume(cl, jpath, {"chat": 8}, window=8,
                             min_samples=4, min_replicas=1,
                             max_replicas=2, cooldown=3, warm_steps=0)
    assert asc2._hcursor == asc._hcursor
    assert [d[1:] for d in asc2.decisions] == [
        ("scale_up", 1), ("drain_begin", 1), ("drain_done", 1),
        ("retire", 1)]
    n_entries = len(ControlJournal.load(jpath).entries)
    for _ in range(3):
        cl.step()
        asc2.step()
    assert len(ControlJournal.load(jpath).entries) == n_entries, (
        "resume must not double-journal replayed history")


def test_resume_rejects_inconsistent_fleet(tmp_path):
    jpath = Autoscaler.journal_path_for(str(tmp_path))
    cl = _mk_cluster(replicas=1, tmp_path=tmp_path)
    asc = Autoscaler(cl, {"chat": 8}, window=8, min_samples=4,
                     max_replicas=2, cooldown=3, warm_steps=0,
                     journal=jpath)
    _feed(cl, "chat", 50, 8)
    asc.step()
    cl.step()
    _feed(cl, "chat", 1, 8)
    for _ in range(asc.cooldown + 1):
        cl.step()
        asc.step()
    _drain_all(cl, asc)
    # a journal that says "retired" must match the cluster it resumes
    fresh = _mk_cluster(replicas=2, tmp_path=None)
    with pytest.raises(AssertionError, match="retired"):
        Autoscaler.resume(fresh, jpath, {"chat": 8})


# ---------------------------------------------------------------------------
# churn bound: a scale event at fleet size N moves <= c/N of a fixed
# key population (closed form: only the joiner's wins / the leaver's
# keys move)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [2, 4, 8])
def test_rendezvous_churn_bound(n):
    cl = _mk_cluster(replicas=n)
    rng = np.random.RandomState(100 + n)
    keys = [tuple(int(t) for t in rng.randint(1, 32000, size=8))
            for _ in range(600)]
    before = {k: cl.rendezvous_owner(k) for k in keys}

    # scale UP: the only keys that move are those the joiner wins
    rep = cl.add_replica(warm_steps=0)
    cl.step()
    assert rep.admitting
    after_up = {k: cl.rendezvous_owner(k) for k in keys}
    moved = [k for k in keys if after_up[k] != before[k]]
    assert all(after_up[k] == rep.index for k in moved), (
        "a key that moved anywhere but the joiner breaks monotonicity")
    frac = len(moved) / len(keys)
    assert 0 < frac <= 2.0 / (n + 1), (
        f"scale-up at N={n} moved {frac:.3f} of the population — the "
        f"rendezvous bound is c/N with c=2 (ideal: {1 / (n + 1):.3f})")

    # scale DOWN: the only keys that move are the leaver's
    cl.begin_drain(rep.index)
    after_down = {k: cl.rendezvous_owner(k) for k in keys}
    for k in keys:
        if after_up[k] != rep.index:
            assert after_down[k] == after_up[k], (
                "a key not owned by the drainee must not move on drain")
    leavers = [k for k in keys if after_up[k] == rep.index]
    assert len(leavers) / len(keys) <= 2.0 / (n + 1)
    _drain_all(cl)


# ---------------------------------------------------------------------------
# end to end: scripted scale events and the policy loop on the diurnal
# workload — bitwise against the closed form AND the static-peak fleet
# ---------------------------------------------------------------------------

def _diurnal_factory(journal):
    return SimEngine(num_slots=8, page_size=PS, num_pages=129,
                     pages_per_seq=8, journal=journal, prefix_cache=True,
                     prefill_chunk=PS,
                     slo=parse_slo("chat_weight=4,batch_weight=1"))


def _run_diurnal(arrivals, n, tmp_path, elastic):
    cl = Cluster(_diurnal_factory, replicas=1 if elastic else 3,
                 journal_dir=None if tmp_path is None else str(tmp_path),
                 lend=True, spill_threshold=10)
    asc = None
    if elastic:
        asc = Autoscaler(cl, {"chat": 12, "batch": 20}, window=16,
                         min_samples=4, min_replicas=1, max_replicas=3,
                         cooldown=12, warm_steps=1)
    pend = deque(arrivals)
    reqs = {}
    i = 0
    while pend:
        while pend and pend[0][0] <= i:
            _, prompt, mnt, tenant, cls = pend.popleft()
            reqs[cl.submit(prompt, mnt, tenant=tenant,
                           cls=cls)] = (prompt, mnt)
        cl.step()
        if asc is not None:
            asc.step()
        i += 1
    res = _drain_all(cl, asc)
    assert len(res) == n and not cl.failed_gids
    for gid, toks in res.items():
        assert toks == expected_tokens(*reqs[gid])
    return cl, res


def test_diurnal_policy_loop_bitwise_vs_static_fleet(tmp_path):
    spec = parse_workload("n=400,rate=0.25,burst_every=150,burst_len=40,"
                          "burst_x=10,seed=7")
    arrivals = generate_arrivals(spec, vocab=32000, page_size=PS)
    _, res_static = _run_diurnal(arrivals, spec.n, None, elastic=False)
    cl, res_elastic = _run_diurnal(arrivals, spec.n, tmp_path,
                                   elastic=True)
    assert res_elastic == res_static, (
        "the elastic schedule changed tokens — the T3 contract is "
        "schedule-only")
    assert cl.metrics.counters["scale_ups"] >= 1
    assert cl.metrics.counters["retires"] >= 1, (
        "the diurnal swing must ride down as well as up")


def test_scripted_scale_crash_drain_bitwise(tmp_path):
    """The fully scripted ladder in ONE run: mid-stream scale-up, drain
    of a loaded replica, a forced crash mid-drain, controller-less
    manual restore — and every surviving trace bitwise."""
    cl = _mk_cluster(replicas=2, tmp_path=tmp_path, slots=2)
    rng = np.random.RandomState(17)
    sent = {}

    def pump(k):
        for _ in range(k):
            prompt = [int(t) for t in rng.randint(1, 997, size=6)]
            mnt = int(rng.randint(2, 5))
            sent[cl.submit(prompt, mnt)] = (tuple(prompt), mnt)
            cl.step()

    pump(6)
    rep = cl.add_replica(warm_steps=1)           # scale-up mid-stream
    cl.step()
    assert rep.admitting
    pump(8)
    victim = max(cl.replicas, key=lambda r: r.load).index
    cl.begin_drain(victim)
    pump(2)                                      # drain under load
    if cl.replicas[victim].draining:             # may retire in 2 steps
        cl.kill(victim)                          # crash MID-drain
        pump(3)
        cl.restore(victim)
    res = _drain_all(cl)
    assert cl.replicas[victim].lifecycle is ReplicaState.RETIRED
    assert len(res) == len(sent) and not cl.failed_gids
    _assert_golden(cl, sent)


# ---------------------------------------------------------------------------
# the CLI: cluster_sim --autoscale end to end (its own golden gate —
# exit 1 on any trace mismatch — plus the panel's acceptance rows)
# ---------------------------------------------------------------------------

def _run_cluster_sim(n, timeout=WATCHDOG_S - 30):
    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "cluster_sim.py")
    proc = subprocess.run(
        [sys.executable, script, "--autoscale", "--prefix-cache",
         "--lend", "--pages", "129", "--min-replicas", "1",
         "--max-replicas", "4", "--crash-mid-drain", "--workload",
         f"n={n},rate=0.25,burst_every=300,burst_len=60,burst_x=10,"
         "seed=7"],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    panel = next(json.loads(line) for line in proc.stderr.splitlines()
                 if line.startswith('{"autoscale"'))
    summary = json.loads(proc.stdout.splitlines()[-1])
    return panel, summary


def test_cluster_sim_autoscale_cli():
    panel, summary = _run_cluster_sim(1500)
    assert summary["verified_bit_identical"] == 1500
    assert summary["mismatched"] == 0 and summary["missing"] == 0
    assert panel["scale_ups"] >= 1 and panel["retires"] >= 1
    assert panel["replica_steps_saved_pct"] > 0
    assert panel["crash_mid_drain"] is not None, (
        "the forced crash must actually fire on this workload")
    assert panel["ttft_chat_p99_steps"] <= 12, (
        "chat p99 TTFT must hold within the budget through every "
        "scale event")


@pytest.mark.slow
def test_cluster_sim_autoscale_100k():
    """The ISSUE 18 acceptance run at full scale: 100k requests through
    scale-ups, drains and a forced crash-mid-drain, every trace verified
    bitwise by the script's own golden gate."""
    panel, summary = _run_cluster_sim(100_000, timeout=1740)
    assert summary["verified_bit_identical"] == 100_000
    assert panel["replica_steps_saved_pct"] > 0
    assert panel["ttft_chat_p99_steps"] <= 12
