"""Multi-tenant SLO scheduling (ISSUE 14): WFQ isolation, quotas,
per-class shedding, and deadline-aware chunk sizing held to the same
bit-identity contract as everything else in the serving tier.

The tentpole claim is *isolation under bursty overload*: a batch-tier
flood must not change a single admitted chat token, chat TTFT must stay
within a fixed bound of its unflooded value, and every shed request must
carry a TYPED terminal naming its class — on the colocated engine and on
the mesh (n ∈ {1, 2, 4}). The policy plumbing itself must compose with
the ISSUE 7 fault ladder and the ISSUE 9 crash-recovery contract, so the
chaos schedules and the strided crash sweep re-run here under two-class
WFQ and must still be bit-identical to their (policied) goldens.

Layers pinned, cheapest first:

- **scheduler units** (no model, no device): WFQ weighted shares and the
  idle-class virtual-time snap-up, token-bucket throttle/refill/deficit,
  youngest-within-lowest-class victim ordering, per-class caps/TTLs,
  digest sensitivity to class regrouping and bucket levels, policy-book
  capture/restore round-trip.
- **spec parsing**: every malformed --workload / --slo field fails with
  a ValueError NAMING the field; traces are pure functions of the spec.
- **journal schema**: the checked-in headerless v1 fixture loads with
  default tenant/class backfill (pre-ISSUE-14 journals replay under the
  new engines); v2 files lead with a schema header.
- **engine integration**: batch-flood isolation (tokens + TTFT bound +
  typed per-class shed) colocated and sharded, deadline-aware chunk
  shrink with flat compile_stats, chaos schedules and the crash sweep
  under WFQ.
"""

import dataclasses

import numpy as np
import pytest

from conftest import sharded_engine
from test_chaos import SCHEDULES
from triton_dist_tpu.serving import (AdmissionRejected, ControlJournal,
                                     DisaggServingEngine, ServingEngine,
                                     TtlExpired)
from triton_dist_tpu.serving.deadline import Deadline
from triton_dist_tpu.serving.journal import SCHEMA_VERSION
from triton_dist_tpu.serving.scheduler import (ClassSpec,
                                               ContinuousBatchingScheduler,
                                               Request, SLOPolicy)
from triton_dist_tpu.serving.workload import (WorkloadSpec, generate_arrivals,
                                              parse_slo, parse_workload)
from triton_dist_tpu.shmem import FaultPlan
from triton_dist_tpu.shmem.context import initialize_distributed
from triton_dist_tpu.shmem.faults import InjectedCrash

pytestmark = [pytest.mark.slo, pytest.mark.serving, pytest.mark.quick]

MAX_STEPS = 6000


# ------------------------------------------------------ scheduler helpers
def _policy(**kw):
    return SLOPolicy.chat_batch(**kw)


def _req(rid, cls="chat", tenant=None, plen=4, mnt=4):
    return Request(rid=rid, prompt=tuple(range(1, plen + 1)),
                   max_new_tokens=mnt, tenant=tenant or f"{cls[0]}0",
                   cls=cls, shed_level=0)


def _sched(policy, num_slots=1):
    s = ContinuousBatchingScheduler(num_slots, policy=policy)
    return s


def _submit(s, req):
    s.stamp(req, req.tenant, req.cls)
    s.submit(req)
    return req


def _drain_one(s):
    """One admission + instant completion — isolates WFQ admission order
    from everything else the engine does."""
    adm = s.admissible(lambda r: True)
    if adm is None:
        return None
    slot, req = adm
    s.activate(slot, req)
    req.generated = [1] * req.max_new_tokens
    s.finish(slot)
    return req.cls


# ------------------------------------------------------------- WFQ units
def test_wfq_weighted_share():
    """weight 4:1 with equal-cost requests → 4:1 admission counts under
    sustained two-class backlog, and the order is deterministic."""
    orders = []
    for _ in range(2):
        s = _sched(_policy(chat_weight=4, batch_weight=1))
        for i in range(8):
            _submit(s, _req(i, "chat"))
            _submit(s, _req(100 + i, "batch"))
        order = [_drain_one(s) for _ in range(10)]
        orders.append(order)
        assert order.count("chat") == 8 and order.count("batch") == 2, order
    assert orders[0] == orders[1], "WFQ admission order is not deterministic"


def test_wfq_fifo_within_class():
    s = _sched(_policy(chat_weight=1, batch_weight=1), num_slots=2)
    reqs = [_submit(s, _req(i, "chat")) for i in range(4)]
    admitted = []
    for _ in range(4):
        slot, req = s.admissible(lambda r: True)
        s.activate(slot, req)
        admitted.append(req.rid)
        s.slots[slot] = None           # vacate without finishing
    assert admitted == [r.rid for r in reqs], "intra-class order not FIFO"


def test_wfq_idle_class_cannot_bank_service():
    """A class idle while the other drains must snap UP to the virtual-
    time floor on re-arrival — equal weights then ALTERNATE rather than
    letting the newcomer monopolize with its banked zero service."""
    s = _sched(_policy(chat_weight=1, batch_weight=1))
    for i in range(8):
        _submit(s, _req(100 + i, "batch"))
    for _ in range(6):                  # batch-only era: service builds
        assert _drain_one(s) == "batch"
    for i in range(4):
        _submit(s, _req(i, "chat"))
    order = [_drain_one(s) for _ in range(4)]
    assert order == ["chat", "batch", "chat", "batch"], (
        f"idle chat banked service and monopolized: {order}")


# ----------------------------------------------------------- quota units
def test_token_bucket_throttles_then_refills():
    s = _sched(_policy(quotas={"t0": (1, 2)}))
    _submit(s, _req(0, "chat", tenant="t0"))      # cost 8, burst 2
    _submit(s, _req(1, "chat", tenant="t0"))
    slot, req = s.admissible(lambda r: True)      # level 2 > 0: admits
    s.activate(slot, req)
    assert req.rid == 0 and s._bucket["t0"][0] == 2 - req.cost  # deficit
    s.slots[slot] = None
    throttled0 = s.quota_throttled
    for now in range(1, 7):                        # -6 + 6 = 0: still dry
        s.tick(now)
        assert s.admissible(lambda r: True) is None
    assert s.quota_throttled == throttled0 + 6, "throttle skips uncounted"
    s.tick(7)                                      # level 1 > 0
    slot, req = s.admissible(lambda r: True)
    assert req.rid == 1, "bucket refill never re-admitted the tenant"


def test_token_bucket_clamps_at_burst():
    s = _sched(_policy(quotas={"t0": (5, 3)}))
    s.tick(100)
    assert s._bucket["t0"] == [3, 100], "refill overshot the burst cap"


def test_unquotaed_tenant_never_throttled():
    s = _sched(_policy(quotas={"t0": (1, 1)}))
    _submit(s, _req(0, "chat", tenant="anon"))
    before = s.quota_throttled
    assert s.admissible(lambda r: True) is not None
    assert s.quota_throttled == before


def test_dry_bucket_blocks_only_its_class():
    """The isolation property the flood test leans on: a dry batch
    tenant must not head-of-line-block the chat tier."""
    s = _sched(_policy(quotas={"b0": (1, 1)}))
    _submit(s, _req(0, "batch", tenant="b0"))
    slot, req = s.admissible(lambda r: True)
    s.activate(slot, req)                          # b0 now in deficit
    s.slots[slot] = None
    _submit(s, _req(1, "batch", tenant="b0"))      # dry
    _submit(s, _req(2, "chat"))
    slot, req = s.admissible(lambda r: True)
    assert req.rid == 2, "dry batch bucket blocked the chat class"


# ------------------------------------------------- victim/shed/TTL units
def test_pick_victim_lowest_class_youngest_first():
    s = _sched(_policy(), num_slots=4)
    for slot, (rid, cls) in enumerate(
            [(0, "chat"), (1, "batch"), (2, "batch"), (3, "chat")]):
        r = _req(rid, cls)
        s.stamp(r, r.tenant, r.cls)
        s.place(slot, r)               # admitted_seq = seating order
    assert s.pick_victim() == 2                    # youngest batch
    assert s.pick_victim(exclude_slot=2) == 1      # older batch next
    s.slots[1] = s.slots[2] = None
    assert s.pick_victim() == 3, "chat order should be youngest-first"


def test_per_class_queue_cap_composes_with_global():
    s = ContinuousBatchingScheduler(
        1, queue_cap=10, policy=_policy(batch_queue_cap=2))
    for i in range(2):
        _submit(s, _req(i, "batch"))
    assert s.at_capacity_for("batch") and not s.at_capacity_for("chat")
    for i in range(8):
        _submit(s, _req(10 + i, "chat"))
    assert s.at_capacity_for("chat"), "global cap stopped composing"


def test_expire_sweeps_only_ttl_armed_never_admitted():
    s = _sched(_policy(batch_ttl_steps=3), num_slots=2)
    b = _submit(s, _req(0, "batch"))
    b.deadline = Deadline(3, 0)
    c = _submit(s, _req(1, "chat"))                # no TTL: never expires
    requeued = _submit(s, _req(2, "batch"))
    requeued.deadline = Deadline(3, 0)
    requeued.admitted_seq = 5                      # preemption requeue
    assert s.expire(2) == []
    assert s.expire(50) == [b], "TTL swept the wrong requests"
    assert b.state.value == "rejected" and b not in s.queue
    assert c in s.queue and requeued in s.queue


# ------------------------------------------------------ digest/checkpoint
def test_digest_folds_class_regrouping_and_buckets():
    def build(swap=False):
        s = _sched(_policy(quotas={"t0": (1, 4)}))
        a, b = ("batch", "chat") if swap else ("chat", "batch")
        _submit(s, _req(0, a))
        _submit(s, _req(1, b))
        return s

    assert build().digest() == build().digest()
    assert build().digest() != build(swap=True).digest(), (
        "class regrouping of the same rids must fork the digest")
    s = build()
    d0 = s.digest()
    s._bucket["t0"][0] -= 1
    assert s.digest() != d0, "bucket level is outside the digest"
    s._bucket["t0"][0] += 1
    s._service["chat"] += 1
    assert s.digest() != d0, "WFQ service counter is outside the digest"


def test_policy_books_capture_restore_round_trip():
    s = _sched(_policy(quotas={"c0": (2, 6)}))
    for i in range(4):
        _submit(s, _req(i, "chat" if i % 2 else "batch"))
    for _ in range(3):
        _drain_one(s)
    s.tick(9)
    state = s.policy_state()
    s2 = _sched(_policy(quotas={"c0": (2, 6)}))
    s2.restore_policy_state(state)
    assert s2.policy_state() == state, "policy books did not round-trip"
    # negative (deficit) levels survive the round trip too
    s._bucket["c0"][0] = -17
    s2.restore_policy_state(s.policy_state())
    assert s2._bucket["c0"][0] == -17


def test_stamp_validates_class_and_maps_default():
    s = _sched(_policy())
    r = _req(0)
    s.stamp(r, "t9", None)
    assert r.cls == "chat" and r.shed_level == 0   # policy default
    r2 = Request(rid=1, prompt=(1,), max_new_tokens=1)
    s.stamp(r2, None, "default")                   # v1-journal backfill
    assert r2.cls == "chat"
    with pytest.raises(KeyError, match="unknown class"):
        s.stamp(_req(2), None, "platinum")


# ------------------------------------------------------------ spec parsing
def test_parse_workload_round_trips_every_field():
    spec = parse_workload(
        "n=30,seed=7,chat=0.6,rate=0.8,burst_every=32,burst_len=8,"
        "burst_x=4,zipf=1.2,prefixes=4,tenants=2,plen=4:16,mnt=2:8")
    assert spec == WorkloadSpec(n=30, seed=7, chat=0.6, rate=0.8,
                                burst_every=32, burst_len=8, burst_x=4.0,
                                zipf=1.2, prefixes=4, tenants=2,
                                plen=(4, 16), mnt=(2, 8))
    assert parse_workload("") == WorkloadSpec()    # all defaults


@pytest.mark.parametrize("spec,field", [
    ("n=0", "n"),
    ("n=many", "n"),
    ("chat=1.5", "chat"),
    ("rate=0", "rate"),
    ("rate=fast", "rate"),
    ("burst_len=9,burst_every=4", "burst_len"),
    ("burst_x=0.5", "burst_x"),
    ("zipf=1.0", "zipf"),
    ("tenants=0", "tenants"),
    ("plen=9:2", "plen"),
    ("plen=4-9", "plen"),
    ("mnt=0:3", "mnt"),
    ("frobs=3", "frobs"),
    ("n", "'n'"),
])
def test_parse_workload_errors_name_the_field(spec, field):
    with pytest.raises(ValueError, match="workload spec field") as ei:
        parse_workload(spec)
    assert field in str(ei.value), (
        f"error for {spec!r} does not name {field!r}: {ei.value}")


@pytest.mark.parametrize("spec,field", [
    ("chat_weight=heavy", "chat_weight"),
    ("batch_ttl=soon", "batch_ttl"),
    ("quota=b0:1", "quota"),
    ("quota=b0:1:fat", "quota"),
    ("tier=gold", "tier"),
])
def test_parse_slo_errors_name_the_field(spec, field):
    with pytest.raises(ValueError, match="slo spec field") as ei:
        parse_slo(spec)
    assert field in str(ei.value)


def test_parse_slo_builds_chat_batch_policy():
    p = parse_slo("chat_weight=3,batch_cap=5,batch_ttl=40,quota=b0:1:4|c1:2:8")
    assert p.spec("chat").weight == 3 and p.spec("chat").level == 0
    assert p.spec("batch").queue_cap == 5
    assert p.spec("batch").ttl_steps == 40
    assert dict(p.quotas) == {"b0": (1, 4), "c1": (2, 8)}


def test_generate_arrivals_deterministic_and_well_formed():
    spec = parse_workload("n=40,seed=3,chat=0.7,rate=1.0,plen=4:12,mnt=2:6")
    a1 = generate_arrivals(spec)
    a2 = generate_arrivals(spec)
    assert a1 == a2, "same spec must replay the same trace bitwise"
    assert a1 != generate_arrivals(dataclasses.replace(spec, seed=4))
    assert len(a1) == 40
    steps = [s for s, *_ in a1]
    assert steps == sorted(steps)
    for step, prompt, mnt, tenant, cls in a1:
        assert cls in ("chat", "batch") and tenant.startswith(cls[0])
        assert 4 <= len(prompt) <= 12 and 2 <= mnt <= 6
    assert {c for *_, c in a1} == {"chat", "batch"}


def test_generate_arrivals_bursts_are_denser():
    spec = parse_workload(
        "n=400,seed=1,rate=0.5,burst_every=40,burst_len=10,burst_x=6")
    arr = generate_arrivals(spec)
    in_b = sum(1 for s, *_ in arr if (s % 40) < 10)
    out_b = len(arr) - in_b
    # 10 burst steps at 3/step vs 30 quiet steps at 0.5/step per period:
    # per-step density in-burst must dominate clearly
    assert in_b / 10 > 2 * (out_b / 30), (
        f"burst windows not denser: {in_b} in, {out_b} out")


# -------------------------------------------------------- journal schema
def test_journal_v1_fixture_loads_with_backfill():
    """The checked-in pre-ISSUE-14 journal (headerless = v1): classed
    kinds gain the default tenant/cls stamps, nothing else changes, and
    a save() round-trip re-emits it as v2 with identical entries."""
    j = ControlJournal.load("tests/fixtures/journal_v1.jsonl")
    assert j.schema == 1 and len(j) == 11
    for e in j.entries:
        if e["kind"] in ("submit", "reject", "expire"):
            assert e["tenant"] == "default" and e["cls"] == "default", e
        else:
            assert "tenant" not in e and "cls" not in e, (
                f"backfill leaked onto {e['kind']}")
    assert j.counts() == {"submit": 3, "admit": 2, "chunk": 1,
                          "reject": 1, "checkpoint": 1, "expire": 1,
                          "finish": 2}


def test_journal_v1_fixture_save_round_trip(tmp_path):
    j = ControlJournal.load("tests/fixtures/journal_v1.jsonl")
    p = tmp_path / "upgraded.jsonl"
    j.save(str(p))
    j2 = ControlJournal.load(str(p))
    assert j2.entries == j.entries
    # the rewrite leads with ITS schema header; entries stamped once,
    # backfill does not double-apply
    assert p.read_text().splitlines()[0] == '{"schema": 1}'


def test_journal_v2_header_on_fresh_files(tmp_path):
    p = tmp_path / "live.jsonl"
    j = ControlJournal(path=str(p))
    j.append("submit", 0, 1, rid=0, prompt=[1], max_new_tokens=1,
             tenant="t0", cls="chat")
    j.close()
    lines = p.read_text().splitlines()
    assert lines[0] == '{"schema": %d}' % SCHEMA_VERSION
    j2 = ControlJournal.load(str(p))
    assert j2.schema == SCHEMA_VERSION
    assert j2.entries[0]["tenant"] == "t0"         # no backfill on v2


# ------------------------------------------------------ engine fixtures
@pytest.fixture(scope="module")
def role_ctx():
    return initialize_distributed(axis_names=("role",), mesh_shape=(2,))


def _colocated(micro_model, **kw):
    cfg, params = micro_model
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 16)
    kw.setdefault("pages_per_seq", 6)
    kw.setdefault("prefill_chunk", 8)
    return ServingEngine(params, cfg, **kw)


def _disagg(micro_model, ctx, **kw):
    cfg, params = micro_model
    kw.setdefault("num_slots", 4)
    kw.setdefault("num_prefill_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 64)
    kw.setdefault("pages_per_seq", 3)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("signal_deadline_steps", 3)
    kw.setdefault("max_retries", 3)
    return DisaggServingEngine(params, cfg, ctx=ctx, **kw)


FLOOD_POLICY = dict(chat_weight=4, batch_weight=1, batch_queue_cap=6,
                    batch_ttl_steps=40)


def _chat_trace(n=12, seed=5, vocab=128):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        plen = int(rng.randint(3, 15))
        mnt = int(rng.randint(2, 6))
        out.append((2 * i, rng.randint(1, vocab, size=plen).tolist(), mnt,
                    f"c{i % 3}", "chat"))
    return out


def _batch_flood(n=24, seed=9, vocab=128, max_plen=30):
    """The burst: long batch prompts slamming the queue in the first few
    steps — far beyond what the batch queue cap admits. ``max_plen``
    keeps the flood inside the engine's pages_per_seq ceiling."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        plen = int(rng.randint(12, max_plen))
        mnt = int(rng.randint(4, 8))
        out.append((i % 6, rng.randint(1, vocab, size=plen).tolist(), mnt,
                    f"b{i % 2}", "batch"))
    return out


def _chat_map(eng):
    """prompt → tokens for finished chat requests (rids differ between
    the flooded and unflooded runs; prompts are the stable key)."""
    return {tuple(r.prompt): list(r.generated)
            for r in eng._finished if r.cls == "chat"}


def _chat_ttft(eng):
    """Step-clock TTFT per finished chat request — deterministic, unlike
    wall time."""
    return sorted(r.first_token_step - r.submit_step
                  for r in eng._finished if r.cls == "chat")


# ---------------------------------------------------- flood isolation
def test_flood_isolation_colocated(micro_model):
    """The headline: a 2x batch flood on the colocated engine sheds ONLY
    batch (typed, class-named), admits and finishes every chat request
    with tokens bit-identical to the unflooded golden, and holds chat
    TTFT within a fixed bound of the unflooded p99."""
    chat = _chat_trace(n=8)
    slo = SLOPolicy.chat_batch(**FLOOD_POLICY)
    golden = _colocated(micro_model, slo=slo)
    golden.run(max_steps=MAX_STEPS, arrivals=chat)
    gold_map, gold_ttft = _chat_map(golden), _chat_ttft(golden)
    assert len(gold_map) == len(chat)

    flooded = _colocated(micro_model, slo=slo)
    arrivals = sorted(chat + _batch_flood(n=16), key=lambda a: a[0])
    flooded.run(max_steps=MAX_STEPS, arrivals=arrivals)

    # every chat request finished, bit-identical to the unflooded golden
    assert _chat_map(flooded) == gold_map, (
        "batch flood changed admitted chat tokens")
    # all shedding is batch-tier and typed
    shed = flooded._rejected
    assert shed, "flood never shed — the overload lost its teeth"
    for r in shed:
        assert r.cls == "batch", f"chat request {r.rid} was shed"
        assert isinstance(r.failure, (AdmissionRejected, TtlExpired))
        assert "'batch'" in str(r.failure), "terminal does not name class"
    c = flooded.metrics.counters
    assert c.get("rejections{class=batch}", 0) \
        + c.get("expirations{class=batch}", 0) == len(shed)
    assert c.get("rejections{class=chat}", 0) == 0
    assert c.get("expirations{class=chat}", 0) == 0
    # chat TTFT bound (step clock): flooded p99 within a fixed budget of
    # the unflooded p99 — the WFQ isolation claim, as a number
    budget = 3 * gold_ttft[-1] + 12
    assert _chat_ttft(flooded)[-1] <= budget, (
        f"flooded chat p99 TTFT {_chat_ttft(flooded)[-1]} steps blew the "
        f"{budget}-step bound (unflooded p99 {gold_ttft[-1]})")


# a third of the colocated flood, so a third of its queue cap
SHARDED_FLOOD_POLICY = {**FLOOD_POLICY, "batch_queue_cap": 2}


@pytest.fixture(scope="module")
def unflooded_n1_chat(moe_model):
    """{rid: tokens} of the chat trace alone at mesh 1x1x1: the golden of
    every mesh's flooded run."""
    chat = _chat_trace(n=4)
    golden = sharded_engine(moe_model, 1, 1, 1, num_pages=12,
                            slo=SLOPolicy.chat_batch(**SHARDED_FLOOD_POLICY))
    golden.run(max_steps=MAX_STEPS, arrivals=chat)
    gold_map = _chat_map(golden)
    assert len(gold_map) == len(chat)
    return gold_map


@pytest.mark.mesh
@pytest.mark.parametrize("tp,sp,ep", [
    (1, 1, 1), (1, 1, 2),  # 5-10 s an interpreter step at n=4: over a minute
    pytest.param(2, 2, 1, marks=pytest.mark.slow)])
def test_flood_isolation_sharded(moe_model, unflooded_n1_chat, tp, sp, ep):
    """Same isolation contract on the mesh (n ∈ {1, 2, 4}): admitted
    chat tokens bit-identical to the n=1 unflooded golden — the policy
    books are replicated host state, so WFQ must not fork the digest."""
    chat = _chat_trace(n=4)
    slo = SLOPolicy.chat_batch(**SHARDED_FLOOD_POLICY)
    gold_map = unflooded_n1_chat

    flooded = sharded_engine(moe_model, tp, sp, ep, num_pages=12, slo=slo)
    arrivals = sorted(chat + _batch_flood(n=6, max_plen=24),
                      key=lambda a: a[0])
    flooded.run(max_steps=MAX_STEPS, arrivals=arrivals)
    assert _chat_map(flooded) == gold_map, (
        f"mesh {tp}x{sp}x{ep}: flood changed admitted chat tokens")
    assert flooded._rejected, "flood never shed: overload lost its teeth"
    for r in flooded._rejected:
        assert r.cls == "batch", f"chat shed on mesh {tp}x{sp}x{ep}"


@pytest.mark.mesh
def test_flood_sheds_only_batch_at_submit_on_the_n4_mesh(moe_model):
    """Tier 1's stand-in for the 2x2x1 flood (`slow`), nothing dispatched:
    admission control is host state replicated over the 4 ranks — the
    batch flood is shed at submit past its queue cap, typed and class-
    named, chat is untouched, and every rank still agrees on the digest."""
    eng = sharded_engine(moe_model, 2, 2, 1, num_pages=12,
                         slo=SLOPolicy.chat_batch(**FLOOD_POLICY))
    flood = _batch_flood(n=12, max_plen=24)
    for _, prompt, mnt, tenant, cls in _chat_trace(n=4) + flood:
        eng.submit(prompt, mnt, tenant=tenant, cls=cls)
    shed = eng._rejected
    assert len(shed) == len(flood) - FLOOD_POLICY["batch_queue_cap"]
    for r in shed:
        assert r.cls == "batch" and isinstance(r.failure, AdmissionRejected)
        assert "'batch'" in str(r.failure)
    assert eng.n_ranks == 4
    eng.check_replicated_decisions()


# ------------------------------------------- deadline-aware chunk sizing
def test_chunk_shrink_fires_with_flat_compile_stats(micro_model):
    """chat_stall_budget shrinks co-scheduled batch prefill chunks while
    a chat request decodes — through the SAME chunk program (runtime
    prompt_len scalar), so compile_stats stays at one decode + one chunk
    program and tokens are bit-identical to the unbudgeted run."""
    rng = np.random.RandomState(21)
    arrivals = [(0, rng.randint(1, 128, size=4).tolist(), 12, "c0", "chat")]
    for i in range(4):
        arrivals.append((1 + i, rng.randint(1, 128, size=24).tolist(), 2,
                         "b0", "batch"))

    res_by_budget = {}
    for budget in (None, 4):
        eng = _colocated(micro_model, slo=SLOPolicy.chat_batch(
            chat_stall_budget=budget))
        res = eng.run(max_steps=MAX_STEPS, arrivals=arrivals)
        res_by_budget[budget] = res
        stats = eng.compile_stats
        assert stats["decode_compiles"] == 1, stats
        assert stats["prefill_chunk_compiles"] == 1, (
            f"chunk shrink compiled a new program: {stats}")
        shrinks = eng.metrics.counters["chunk_shrinks"]
        if budget is None:
            assert shrinks == 0
        else:
            assert shrinks > 0, "stall budget never shrank a chunk"
    assert res_by_budget[None] == res_by_budget[4], (
        "chunk shrink changed tokens")


def test_unpoliced_engine_has_no_class_metrics(micro_model):
    """Pay-for-play: without a policy the metrics panel is exactly the
    pre-ISSUE-14 shape — no {class=...} keys, no quota counters moving."""
    eng = _colocated(micro_model)
    eng.run(max_steps=MAX_STEPS,
            arrivals=[(0, [3, 5, 7], 3), (1, [2, 4, 6, 8], 2)])
    assert len(eng._finished) == 2
    assert not [k for k in eng.metrics.counters if "{class=" in k]
    assert eng.metrics.counters["quota_throttled"] == 0
    assert eng.metrics.counters["chunk_shrinks"] == 0


# ------------------------------------- chaos + crash recovery under WFQ
def _two_class_trace(n=3, seed=77, vocab=128):
    """The chaos/crash trace with class stamps: same shape as the ISSUE
    7/9 suites' _trace, alternating tenants, no caps/quotas in the
    policy — shedding must stay OFF so every request reaches a terminal
    the goldens can be compared against."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        plen = int(rng.randint(3, 17))
        mnt = int(rng.randint(2, 6))
        cls = "batch" if i % 3 == 0 else "chat"
        out.append((2 * i, rng.randint(1, vocab, size=plen).tolist(), mnt,
                    f"{cls[0]}{i % 2}", cls))
    return out


@pytest.fixture(scope="module")
def chaos_wfq_golden(micro_model, role_ctx):
    slo = SLOPolicy.chat_batch()
    eng = _disagg(micro_model, role_ctx, slo=slo)
    gold = eng.run(max_steps=MAX_STEPS, arrivals=_two_class_trace())
    assert len(gold) == 3 and not eng.failed
    return gold


@pytest.mark.chaos
@pytest.mark.parametrize("name,plan", SCHEDULES,
                         ids=[n for n, _ in SCHEDULES])
def test_chaos_schedules_bit_identical_under_wfq(micro_model, role_ctx,
                                                 chaos_wfq_golden, name,
                                                 plan):
    """The ISSUE 7 fault matrix re-run with two-class WFQ live: every
    survivable schedule still finishes all requests bit-identical to the
    policied fault-free golden — the policy composes with the recovery
    ladder instead of racing it."""
    eng = _disagg(micro_model, role_ctx, slo=SLOPolicy.chat_batch(),
                  fault_plan=plan)
    res = eng.run(max_steps=MAX_STEPS, arrivals=_two_class_trace())
    assert eng.failed == [], (
        f"{name}: ladder should have saved every request under WFQ; "
        f"failures: {[(r.rid, r.failure) for r in eng.failed]}")
    assert res == chaos_wfq_golden, (
        f"{name}: tokens diverged from the policied golden")
    injected = eng.metrics.counters["faults_injected"] > 0
    assert injected == (name != "clean"), (
        f"{name}: schedule injected nothing under WFQ")


@pytest.mark.recovery
def test_crash_sweep_bit_identical_under_wfq(micro_model):
    """The ISSUE 9 strided crash sweep with WFQ + a quota bucket in
    deficit at most crash points: checkpoint/restore must carry the
    policy books (service counters, vfloor, bucket levels) or replay
    forks — the union of pre-crash and post-recovery finishes must stay
    bit-identical to the fault-free policied golden."""
    arrivals = _two_class_trace(n=8)
    slo = dict(chat_weight=4, batch_weight=1, quotas={"b0": (1, 2)})
    mk = lambda **kw: _colocated(                           # noqa: E731
        micro_model, slo=SLOPolicy.chat_batch(**slo), **kw)

    journal = ControlJournal()
    eng = mk(journal=journal, checkpoint_every=8)
    golden = eng.run(max_steps=MAX_STEPS, arrivals=arrivals)
    total = eng._steps
    assert len(golden) == 8
    assert eng.metrics.counters["quota_throttled"] > 0, (
        "quota never bit — the sweep is not exercising bucket restore")

    stride = max(1, total // 2)
    for s in range(1, total, stride):
        j = ControlJournal()
        e1 = mk(journal=j, checkpoint_every=8,
                fault_plan=FaultPlan(seed=3, crash_at=(s,)))
        try:
            e1.run(max_steps=MAX_STEPS, arrivals=arrivals)
            continue                    # finished before the crash point
        except InjectedCrash:
            pass
        done = sum(1 for e in j.entries if e["kind"] == "submit")
        e2 = mk(journal=j, checkpoint_every=8)
        res = e2.run(max_steps=MAX_STEPS, arrivals=arrivals[done:],
                     recover=True)
        assert res == golden, f"crash at step {s}: not bit-identical"
