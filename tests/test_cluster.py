"""Cluster serving (ISSUE 12): the composed disagg×sharded engine, the
replica wrapper, and the deterministic router.

THE contract, composed tier: ``DisaggShardedEngine`` — a disaggregated
prefill fleet feeding a ``ShardedServingEngine`` decode fleet on ONE
TP/SP/EP mesh over the unified pool contract — replays the trace whose
1x1x1 golden preempts BIT-IDENTICALLY to that golden at n=2 (the whole
trace) and n=4 (`slow`; its first four requests, as do the fault-ladder
replays: those four do not preempt), with the compile guard pinned at one executable per program
(the prefill fleet REUSES the decode engine's chunk executable) and the
decode panel's ``step_prefill_tokens`` identically 0 (fault-free).

THE contract, cluster tier: routing is a pure function of (alive set,
prompt prefix, load) — two identical runs place identically; per-replica
journals are path-namespaced so N replicas in one directory never
cross-replay (the no-bleed test kills and restores BOTH); and a routed,
preempted, killed-and-restored SimEngine workload matches the closed-
form ``expected_tokens`` golden bitwise.
"""

import json

import numpy as np
import pytest

from conftest import (N4_REQUESTS, N_REQUESTS, SHARDED_KW as ENGINE_KW,
                      seeded_trace)
from triton_dist_tpu.serving import (Cluster, ControlJournal,
                                     DisaggShardedEngine, EngineReplica,
                                     ShardedServingEngine, SimEngine,
                                     expected_tokens, serving_mesh)
from triton_dist_tpu.shmem.faults import FaultPlan, InjectedCrash

pytestmark = [pytest.mark.cluster, pytest.mark.serving]

MAX_STEPS = 100_000


def _composed(moe_model, tp, sp, ep, **kw):
    cfg, params = moe_model
    merged = {**ENGINE_KW, **kw}
    return DisaggShardedEngine(params, cfg, serving_mesh(tp, sp, ep),
                               **merged)


@pytest.fixture(scope="module")
def golden(moe_model):
    """The n=1 golden: the plain SHARDED engine at mesh 1x1x1 — the
    composition must not change a single token of it."""
    cfg, params = moe_model
    eng = ShardedServingEngine(params, cfg, serving_mesh(1, 1, 1),
                               **ENGINE_KW)
    out = eng.run(max_steps=MAX_STEPS, arrivals=seeded_trace(N_REQUESTS))
    assert eng.metrics.counters["preemptions"] >= 1, "trace lost its bite"
    return out


# ---------------------------------------------------------------------------
# the composed engine: disagg prefill × sharded decode, one mesh
# ---------------------------------------------------------------------------

@pytest.mark.mesh
@pytest.mark.parametrize("mesh", [
    (1, 2, 1),       # 5-10 s an interpreter step at n=4: over a minute
    pytest.param((1, 2, 2), marks=pytest.mark.slow)], ids=["1x2x1", "1x2x2"])
def test_composed_bit_identical_to_sharded_golden(moe_model, golden, mesh):
    """ISSUE 12 acceptance: the disagg demo with its decode role under
    shard_map on a TP/SP(/EP) mesh, per-request trace bit-identical to
    the n=1 golden at n∈{2,4} — plus the compile guard (ONE chunk
    executable SHARED by both fleets, one decode, one migration copy)
    and the decode-panel prefill-isolation invariant."""
    n = N4_REQUESTS if mesh == (1, 2, 2) else N_REQUESTS
    eng = _composed(moe_model, *mesh)
    out = eng.run(max_steps=MAX_STEPS, arrivals=seeded_trace(n))
    assert set(out) == set(range(n))
    for rid in out:
        assert out[rid] == golden[rid], (
            f"rid {rid} diverged on composed mesh {eng.mesh_desc}: "
            f"{out[rid]} != {golden[rid]}")
    assert eng.compile_stats == {"prefill_chunk_compiles": 1,
                                 "decode_compiles": 1,
                                 "migrate_compiles": 1}
    # every request went through the full remote pipeline...
    c, d = eng.metrics.counters, eng.metrics_decode.counters
    assert c["handoffs"] == n and d["handoffs"] == n
    assert c["pages_migrated"] > 0
    # ...and the decode fleet never prefilled a token (fault-free run)
    assert eng.metrics_decode.hist["step_prefill_tokens"].max in (0, None)
    assert d["degradations"] == 0 and d["failed_requests"] == 0


@pytest.mark.mesh
def test_composed_engine_at_1x2x2_is_one_pool_contract(moe_model,
                                                         own_programs):
    """Tier 1's stand-in for the 1x2x2 replay (`slow`), nothing dispatched:
    both fleets' pools share ONE sp-aware shape on the 4-device mesh, no
    program exists before the first dispatch, and neither pool will ship
    a scratch or an SP padding page."""
    from triton_dist_tpu.serving.kv_pool import PageLedgerError
    own_programs()
    eng = _composed(moe_model, 1, 2, 2)
    assert eng.mesh_desc == "1x2x2" and eng.decode.n_ranks == 4
    assert eng.compile_stats == {"prefill_chunk_compiles": 0,
                                 "decode_compiles": 0, "migrate_compiles": 0}
    p, d = eng.alloc_p, eng.decode.alloc
    assert p.sp_ranks == d.sp_ranks == 2
    assert p.device_pages == d.device_pages and p.device_pages % 2 == 0
    pages = p.alloc("r", 2)
    p.check_migratable("r", pages)
    for bad in (0, p.num_pages):
        with pytest.raises(PageLedgerError):
            p.check_migratable("r", [bad])


@pytest.mark.mesh
def test_composed_retry_rung_recovers_bit_identical(moe_model, golden):
    """Light seeded signal drops: the deadline/retry ladder re-sends the
    lost chunks and every trace still matches the golden bitwise."""
    eng = _composed(moe_model, 1, 2, 1,
                    fault_plan=FaultPlan(seed=11, p_drop=0.25),
                    signal_deadline_steps=2, max_retries=4)
    out = eng.run(max_steps=MAX_STEPS, arrivals=seeded_trace(N4_REQUESTS))
    d = eng.metrics_decode.counters
    assert d["retries"] > 0, "drop plan should have forced retries"
    assert d["failed_requests"] == 0
    assert out == {rid: golden[rid] for rid in range(N4_REQUESTS)}


@pytest.mark.mesh
def test_composed_degrade_rung_local_reprefill_bit_identical(moe_model,
                                                            golden):
    """Total signal loss on targeted rids: retries run dry, the degrade
    rung requeues the request into the DECODE fleet's own chunked
    admission (it keeps its page reservation), and the locally
    re-prefilled trace is still bit-identical — determinism makes the
    transport loss invisible in token space."""
    eng = _composed(moe_model, 1, 2, 1,
                    fault_plan=FaultPlan(seed=19, p_drop=1.0, rids=(1, 3)),
                    signal_deadline_steps=2, max_retries=1)
    out = eng.run(max_steps=MAX_STEPS, arrivals=seeded_trace(N4_REQUESTS))
    d = eng.metrics_decode.counters
    assert d["degradations"] >= 1
    assert d["failed_requests"] == 0
    assert out == {rid: golden[rid] for rid in range(N4_REQUESTS)}
    # degraded requests DID re-prefill on the decode fleet
    assert eng.metrics_decode.counters["prefill_chunks"] > 0


@pytest.mark.mesh
@pytest.mark.recovery
def test_composed_crash_recover_bit_identical(moe_model, golden, tmp_path):
    """Engine-tier crash mid-run: a FRESH composed engine restores from
    the journal (full-journal replay — restart-from-prompt through the
    whole remote pipeline) and finishes the trace bit-identically."""
    cfg, params = moe_model
    jpath = str(tmp_path / "composed.jsonl")
    journal = ControlJournal(path=jpath)
    eng = _composed(moe_model, 1, 2, 1, journal=journal,
                    checkpoint_every=2,
                    fault_plan=FaultPlan(seed=0, crash_at=(4,)))
    arrivals = seeded_trace(N4_REQUESTS)
    with pytest.raises(InjectedCrash):
        eng.run(max_steps=MAX_STEPS, arrivals=arrivals)
    done = sum(1 for e in journal.entries
               if e["kind"] in ("submit", "reject"))
    assert 0 < done
    j2 = ControlJournal.load(jpath)
    eng2 = _composed(moe_model, 1, 2, 1, journal=j2,
                     fault_plan=FaultPlan(seed=0, crash_at=(4,)))
    out = eng2.run(max_steps=MAX_STEPS, arrivals=arrivals[done:],
                   recover=True)
    assert eng2.metrics.counters["restores"] == 1
    assert out == {rid: golden[rid] for rid in range(N4_REQUESTS)}


# ---------------------------------------------------------------------------
# replica wrapper: path-namespaced journals, kill/restore
# ---------------------------------------------------------------------------

def test_replica_journals_do_not_bleed(tmp_path):
    """Two replicas, ONE directory: each journal is its own
    journal-r{i}.jsonl; killing and restoring BOTH replays each strictly
    from its own file — no request crosses over."""
    def factory(journal):
        return SimEngine(num_slots=2, page_size=8, num_pages=17,
                         pages_per_seq=4, journal=journal)

    reps = [EngineReplica(i, factory, str(tmp_path)) for i in range(2)]
    assert reps[0].journal_path != reps[1].journal_path
    prompts = {0: [], 1: []}
    for i in range(10):
        ri = i % 2
        prompt = [100 * (ri + 1) + i] * 4     # replica-tagged prompts
        reps[ri].submit(prompt, 3)
        prompts[ri].append(tuple(prompt))
    for _ in range(4):                         # some finish, some queued
        for r in reps:
            r.step()
    for r in reps:
        r.kill()
    assert reps[0].engine is None
    for r in reps:
        r.restore()
    # drain and check every request landed on the replica it was
    # submitted to — and ONLY there
    for _ in range(200):
        if not any(r.step() for r in reps):
            break
    for ri, r in enumerate(reps):
        got = {tuple(q.prompt) for q in r.engine._finished}
        assert got == set(prompts[ri]), (
            f"replica {ri} finished foreign requests: journal bleed")
        for q in r.engine._finished:
            assert q.generated == expected_tokens(q.prompt,
                                                  q.max_new_tokens)
    # the on-disk journals are disjoint too
    for ri, r in enumerate(reps):
        with open(r.journal_path) as fh:
            for line in fh:
                e = json.loads(line)
                if e.get("kind") == "submit":
                    assert tuple(e["prompt"]) in set(prompts[ri])


def test_replica_restore_without_checkpoint_replays_whole_journal(tmp_path):
    """checkpoint_every=None: kill/restore falls back to full-journal
    replay (the ISSUE 9 ckpt=None rung) and loses nothing."""
    def factory(journal):
        return SimEngine(num_slots=2, page_size=8, num_pages=17,
                         pages_per_seq=4, journal=journal)

    rep = EngineReplica(0, factory, str(tmp_path))
    for i in range(6):
        rep.submit([7 + i] * 5, 4)
    rep.step()
    rep.kill()
    stats = rep.restore()
    assert stats["checkpoint_step"] is None and stats["replayed"] >= 6
    for _ in range(200):
        if not rep.step():
            break
    assert len(rep.engine._finished) == 6
    for q in rep.engine._finished:
        assert q.generated == expected_tokens(q.prompt, q.max_new_tokens)


# ---------------------------------------------------------------------------
# the router: deterministic prefix affinity
# ---------------------------------------------------------------------------

def _mk_cluster(tmp_path=None, replicas=4):
    def factory(journal):
        return SimEngine(num_slots=4, page_size=8, num_pages=33,
                         pages_per_seq=8, journal=journal)

    return Cluster(factory, replicas=replicas,
                   journal_dir=None if tmp_path is None else str(tmp_path))


def test_router_prefix_affinity_and_determinism():
    """Same 8-token prefix => same replica (whatever the tail); the
    whole placement map is a pure function of the submission sequence —
    two identical runs place identically."""
    def run():
        cl = _mk_cluster()
        placements = []
        rng = np.random.RandomState(5)
        prefixes = [rng.randint(1, 1000, size=8).tolist()
                    for _ in range(6)]
        for i in range(60):
            pre = prefixes[i % 6]
            tail = rng.randint(1, 1000, size=3).tolist()
            cl.submit(pre + tail, 2)
            placements.append(cl._placement[i][0])
            cl.step()
        return placements, prefixes

    pl1, prefixes = run()
    pl2, _ = run()
    assert pl1 == pl2, "router must be deterministic"
    # affinity: every request sharing prefix k landed on ONE replica
    by_prefix = {}
    for i, ri in enumerate(pl1):
        by_prefix.setdefault(i % 6, set()).add(ri)
    assert all(len(v) == 1 for v in by_prefix.values()), by_prefix


def test_router_skips_dead_replicas_and_rendezvous_moves_only_their_keys():
    cl = _mk_cluster()
    rng = np.random.RandomState(6)
    prefixes = [rng.randint(1, 1000, size=8).tolist() for _ in range(12)]
    before = {k: cl.route(p).index for k, p in enumerate(prefixes)}
    dead = 2
    cl.replicas[dead].kill()
    after = {k: cl.route(p).index for k, p in enumerate(prefixes)}
    for k in before:
        if before[k] != dead:
            assert after[k] == before[k], (
                "rendezvous hashing must move ONLY the dead replica's "
                "keys")
        else:
            assert after[k] != dead


def test_router_radix_routing_deterministic_with_hits():
    """Cache-aware routing (ISSUE 13): a template workload routes by
    radix-index hit after the first submit of each template, the whole
    placement map is still a pure function of the submission sequence,
    and affinity holds — every request of a template lands on ONE
    replica."""
    def run():
        cl = _mk_cluster()
        rng = np.random.RandomState(12)
        tpls = [rng.randint(1, 1000, size=16).tolist() for _ in range(4)]
        placements = []
        for i in range(40):
            prompt = tpls[i % 4] + rng.randint(1, 1000, size=2).tolist()
            gid = cl.submit(prompt, 2)
            placements.append(cl._placement[gid][0])
            cl.step()
        cl.drain()
        return placements, dict(cl.metrics.counters)

    p1, c1 = run()
    p2, c2 = run()
    assert p1 == p2, "radix routing broke router determinism"
    assert c1["router_radix_hits"] == c2["router_radix_hits"]
    # first submit of each template misses (rendezvous), the rest hit
    assert c1["router_radix_misses"] == 4
    assert c1["router_radix_hits"] == 36
    for k in range(4):
        assert len({p1[i] for i in range(40) if i % 4 == k}) == 1


def test_router_radix_affinity_survives_kill_restore(tmp_path):
    """A routed prompt's prefix sticks to the replica that first served
    it; while that replica is dead the same prefix falls back to
    rendezvous (entries are never dropped), and the affinity returns the
    moment the replica is restored."""
    cl = _mk_cluster(tmp_path)
    rng = np.random.RandomState(11)
    pre = rng.randint(1, 1000, size=8).tolist()
    gid = cl.submit(pre + [7], 2)
    home = cl._placement[gid][0]
    assert cl.metrics.counters["router_radix_misses"] == 1
    for _ in range(3):
        g = cl.submit(pre + rng.randint(1, 1000, size=2).tolist(), 2)
        assert cl._placement[g][0] == home, "radix affinity broken"
    assert cl.metrics.counters["router_radix_hits"] == 3
    cl.drain()
    cl.kill(home)
    assert cl.route(pre + [9]).index != home
    cl.restore(home)
    assert cl.route(pre + [9]).index == home, "affinity did not return"


def test_cluster_kill_restore_traces_bit_identical(tmp_path):
    """The cluster_sim contract in miniature: a routed workload with a
    mid-run kill/restore; every trace matches the closed-form golden."""
    cl = _mk_cluster(tmp_path)
    reqs = {}
    rng = np.random.RandomState(9)
    for i in range(300):
        plen = int(rng.randint(3, 33))
        mnt = int(rng.randint(2, 9))
        prompt = rng.randint(1, 1000, size=plen).tolist()
        gid = cl.submit(prompt, mnt)
        reqs[gid] = (tuple(prompt), mnt)
        if i == 150:
            cl.kill(1)
        if i == 210:
            stats = cl.restore(1)
            assert stats["replayed"] > 0
        if i % 3 == 0:
            cl.step()
    res = cl.drain()
    assert len(res) == 300 and not cl.failed_gids
    for gid, toks in res.items():
        assert toks == expected_tokens(*reqs[gid]), gid
    assert cl.metrics.counters["restores"] == 1


def test_sim_engine_preemption_matches_closed_form():
    """Growth-driven preemption on a deliberately tight pool: evicted
    requests restart from the prompt and STILL match expected_tokens —
    the same restart-determinism contract the device engines pin."""
    eng = SimEngine(num_slots=4, page_size=4, num_pages=7,
                    pages_per_seq=6)
    rng = np.random.RandomState(3)
    arrivals = []
    for i in range(30):
        plen = int(rng.randint(3, 13))
        mnt = int(rng.randint(2, 8))
        arrivals.append((i // 3, rng.randint(1, 500, size=plen).tolist(),
                         mnt))
    out = eng.run(max_steps=100_000, arrivals=arrivals)
    assert len(out) == 30
    assert eng.metrics.counters["preemptions"] > 0, (
        "pool was sized to force eviction")
    for req in eng._finished:
        assert req.generated == expected_tokens(req.prompt,
                                                req.max_new_tokens)
