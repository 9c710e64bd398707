"""Long-context serving (ISSUE 19): distributed flash-decode over the SP
mesh, held to the same bitwise cross-mesh contract as the base sharded
engine.

THE contract: ``long_context=True`` flips the SP attention leg from the
pool-allgather walk to ``flash_decode_dist`` — one request's KV pages
round-robined across the SP shards (``KVPagePool(layout="interleaved")``),
per-rank attention compute ∝ kv_len/n — and a forced-preemption
trace served on an n>1 interpret mesh (sp=2 in tier 1, sp=4 `slow`: the
trace's first four requests on the ``N4_PAGES`` pool, a preemption on the
interleaved pool asserted in both) is still BIT-IDENTICAL per request to
the n=1 golden. Two goldens, in fact:

- the long-context engine at mesh 1x1x1 (same code path, n=1 fold), and
- the PLAIN (``long_context=False``) engine at 1x1x1 — layout and op
  choice are balance knobs, never allowed to move a token.

Also covered here: the op-level ``flash_decode_dist`` bit-identity (with
and without ``active`` parking), the ledger-id → device-row bijection,
the ``long``/``lplen`` workload population and its RNG-stream-preserving
``long=0`` form, ``parse_slo``'s 3-class long tier, the modeled
``fd_attn_split_us`` sublinearity, and the per-class ``chunk_budget``
drip (runtime scalar — one compiled chunk program).
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import (N4_PAGES, N4_REQUESTS, N_REQUESTS,
                      assert_replay_identical, seeded_trace, sharded_engine)
from triton_dist_tpu.ops import flash_decode_dist
from triton_dist_tpu.serving import ServingEngine, serving_mesh
from triton_dist_tpu.serving.kv_pool import KVPagePool, PageLedgerError
from triton_dist_tpu.serving.scheduler import ClassSpec, SLOPolicy
from triton_dist_tpu.serving.sharded import fd_attn_split_us
from triton_dist_tpu.serving.workload import (WorkloadSpec, generate_arrivals,
                                              parse_slo, parse_workload)

pytestmark = [pytest.mark.longctx, pytest.mark.serving]

MAX_STEPS = 100_000       # engine's own stall watchdog trips far earlier


# --------------------------------------------------------- engine fixtures
def _serve(moe_model, tp, sp, ep, n=N_REQUESTS, **kw):
    kw.setdefault("long_context", True)
    eng = sharded_engine(moe_model, tp, sp, ep, **kw)
    tokens = eng.run(max_steps=MAX_STEPS, arrivals=seeded_trace(n))
    m = eng.metrics
    return {"tokens": tokens, "compiles": eng.compile_stats,
            "counters": dict(m.counters),
            "layout": eng.alloc.layout,
            "attn_count": m.hist["attn_local_us"].count,
            "attn_local_mean": m.hist["attn_local_us"].mean,
            "attn_fold_mean": m.hist["attn_fold_wait_us"].mean}


@pytest.fixture(scope="module")
def golden(moe_model):
    """The n=1 golden: the SAME long-context engine at mesh 1x1x1."""
    return _serve(moe_model, 1, 1, 1)


@pytest.fixture(scope="module")
def n2_run(moe_model):
    """sp=2: the trace's first four on the pool where they still preempt."""
    return _serve(moe_model, 1, 2, 1, n=N4_REQUESTS, num_pages=N4_PAGES)


@pytest.fixture(scope="module")
def n4_run(moe_model):
    """sp=4 with the OTHER decode horizon: K=4 multi-token dispatches —
    the trace's first four must still replay the K=1 n=1 golden exactly."""
    return _serve(moe_model, 1, 4, 1, n=N4_REQUESTS, num_pages=N4_PAGES,
                  decode_horizon=4)


# --------------------------------------------- engine cross-mesh bitwise
def test_longctx_trace_forces_preemption(golden):
    """The contract is vacuous unless preemption actually fires — and
    every request must still finish."""
    assert golden["counters"]["preemptions"] >= 1
    assert len(golden["tokens"]) == N_REQUESTS


def _assert_preempted_and_identical(run, golden):
    assert_replay_identical(run["tokens"], golden["tokens"], N4_REQUESTS)
    assert run["counters"]["preemptions"] >= 1, \
        "the interleaved pool never preempted across chips"


def test_longctx_n2_bitwise(golden, n2_run):
    _assert_preempted_and_identical(n2_run, golden)


@pytest.mark.slow          # 5-10 s an interpreter step at n=4: over a minute
def test_longctx_n4_bitwise(golden, n4_run):
    _assert_preempted_and_identical(n4_run, golden)


def test_longctx_n1_equals_replicated(moe_model, golden):
    """Layout + op choice are balance knobs: the long-context n=1 run
    must match the plain replicated engine token-for-token."""
    plain = _serve(moe_model, 1, 1, 1, long_context=False)
    assert plain["tokens"] == golden["tokens"]
    assert plain["layout"] == "blocked"


def test_longctx_one_program_per_path(n2_run):
    """ONE decode program, ONE chunk program at n>1 — the interleaved
    layout and the fold are runtime data, never a shape."""
    assert n2_run["compiles"]["decode_compiles"] == 1
    assert n2_run["compiles"]["prefill_chunk_compiles"] == 1


def test_longctx_layout_and_attn_metrics(golden, n2_run):
    """long_context flips the pool to interleaved, and the modeled
    attention split lands in the histograms: the fold-wait half is zero
    at n=1 (nothing to fold) and strictly positive at n=2."""
    assert golden["layout"] == "interleaved"
    assert n2_run["layout"] == "interleaved"
    assert n2_run["attn_count"] > 0
    assert (n2_run["attn_local_mean"] or 0.0) > 0.0
    assert (n2_run["attn_fold_mean"] or 0.0) > 0.0
    assert (golden["attn_fold_mean"] or 0.0) == 0.0


def test_longctx_engine_at_sp4_interleaves_a_padded_pool(moe_model):
    """Tier 1's stand-in for the sp=4 replay (`slow`), nothing dispatched:
    the engine a 1x4x1 mesh builds owns an interleaved pool padded to the
    SP axis, with every request's pages round-robined over the four ranks."""
    eng = sharded_engine(moe_model, 1, 4, 1, long_context=True)
    pool = eng.alloc
    assert eng.mesh_desc == "1x4x1" and pool.layout == "interleaved"
    assert pool.device_pages == 12 and pool.device_pages % 4 == 0
    assert sorted(pool.page_shard(p) for p in range(1, 9)) == \
        [0, 0, 1, 1, 2, 2, 3, 3]


# ------------------------------------------------- op-level bit-identity
def _op_inputs(seed=3, B=2, Hq=4, Hkv=2, ps=8, D=128, pages=8, S=4):
    """A mixed-ownership shape: each row's block table touches every
    rank's slice at n=4 (pages 8 / 4 ranks = 2 per rank)."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, Hq, D), jnp.float32)
    kn = jnp.asarray(rng.randn(B, Hkv, D), jnp.float32)
    vn = jnp.asarray(rng.randn(B, Hkv, D), jnp.float32)
    kp = jnp.asarray(rng.randn(pages, Hkv, ps, D), jnp.float32)
    vp = jnp.asarray(rng.randn(pages, Hkv, ps, D), jnp.float32)
    bt = jnp.asarray([[0, 2, 4, 6], [1, 3, 5, 7]], jnp.int32)[:B, :S]
    kv = jnp.asarray([20, 14], jnp.int32)[:B]       # 3 / 2 pages touched
    pos = kv - 1
    return q, kn, vn, kp, vp, bt, pos, kv


def _op_run(sp, active=None):
    ctx = serving_mesh(1, sp, 1)
    q, kn, vn, kp, vp, bt, pos, kv = _op_inputs()
    attn, kpo, vpo = flash_decode_dist(ctx, q, kn, vn, kp, vp, bt, pos, kv,
                                       axis="sp", active=active)
    return (np.asarray(attn), np.asarray(kpo), np.asarray(vpo))


def test_flash_decode_dist_op_bitwise():
    """attn AND the written-back pools are bit-identical across mesh
    sizes — the n=1 route runs the same per-page partial + fold math
    and IS the golden."""
    a1, k1, v1 = _op_run(1)
    for sp in (2, 4):
        an, kn_, vn_ = _op_run(sp)
        assert np.array_equal(a1, an), f"attn diverged at sp={sp}"
        assert np.array_equal(k1, kn_), f"k pool diverged at sp={sp}"
        assert np.array_equal(v1, vn_), f"v pool diverged at sp={sp}"


def test_flash_decode_dist_active_parking():
    """Inactive rows park their k/v_new write on the scratch page in
    BOTH routes — bitwise agreement must survive the parking path."""
    active = jnp.asarray([True, False])
    a1, k1, v1 = _op_run(1, active=active)
    a4, k4, v4 = _op_run(4, active=active)
    assert np.array_equal(a1, a4)
    assert np.array_equal(k1, k4)
    assert np.array_equal(v1, v4)


def test_flash_decode_dist_pool_divisibility_refused():
    """A pool whose page count doesn't split over the SP axis is a
    loud construction error, not a silent wrong-rank walk."""
    ctx = serving_mesh(1, 2, 1)
    q, kn, vn, kp, vp, bt, pos, kv = _op_inputs(pages=9)
    with pytest.raises(AssertionError, match="not divisible"):
        flash_decode_dist(ctx, q, kn, vn, kp, vp, bt, pos, kv, axis="sp")


# ------------------------------------------------ pool layout bijection
def test_interleaved_device_row_is_a_bijection():
    pool = KVPagePool(9, 8, sp_ranks=4, layout="interleaved")
    assert pool.device_pages == 12          # padded to a multiple of 4
    rows = [pool.device_row(p) for p in range(pool.device_pages)]
    assert sorted(rows) == list(range(pool.device_pages))
    assert pool.device_row(0) == 0          # scratch page row is FIXED
    # consecutive ids round-robin across shards
    per = pool.device_pages // pool.sp_ranks
    assert [pool.page_shard(p) for p in range(4)] == [0, 1, 2, 3]
    for p in range(pool.device_pages):
        assert pool.page_shard(p) == pool.device_row(p) // per


def test_blocked_device_row_is_identity():
    pool = KVPagePool(9, 8, sp_ranks=4)     # default layout="blocked"
    assert pool.layout == "blocked"
    for p in range(pool.device_pages):
        assert pool.device_row(p) == p


def test_device_row_range_and_layout_validation():
    pool = KVPagePool(9, 8, sp_ranks=4, layout="interleaved")
    with pytest.raises(PageLedgerError):
        pool.device_row(pool.device_pages)
    with pytest.raises(PageLedgerError):
        pool.device_row(-1)
    with pytest.raises(AssertionError, match="layout"):
        KVPagePool(9, 8, layout="diagonal")


@pytest.mark.parametrize("layout,sp_ranks", [
    ("blocked", 1), ("blocked", 4), ("interleaved", 1), ("interleaved", 2),
    ("interleaved", 4)])
def test_device_rows_is_device_row_of_every_page(layout, sp_ranks):
    """The one array operation a table row and a gather's index go through
    is the scalar map, id for id, in any order and with repeats (a row's
    fill is the scratch page over and over)."""
    pool = KVPagePool(9, 8, sp_ranks=sp_ranks, layout=layout)
    ids = list(range(pool.device_pages)) + [0, 0, 5, 3, 0]
    rows = pool.device_rows(ids)
    assert rows.dtype == np.int32 and rows.shape == (len(ids),)
    assert rows.tolist() == [pool.device_row(p) for p in ids]
    assert pool.device_rows(np.asarray(ids[::-1], np.int32)).tolist() \
        == [pool.device_row(p) for p in ids[::-1]]
    assert pool.device_rows([]).shape == (0,)


@pytest.mark.parametrize("layout", ["blocked", "interleaved"])
@pytest.mark.parametrize("bad", [-1, 12, 2 ** 31])
def test_device_rows_refuses_what_device_row_refuses(layout, bad):
    pool = KVPagePool(9, 8, sp_ranks=4, layout=layout)
    with pytest.raises(PageLedgerError) as scalar:
        pool.device_row(bad)
    with pytest.raises(PageLedgerError) as array:
        pool.device_rows([1, 2, bad, 3])
    assert str(array.value) == str(scalar.value)


# -------------------------------------------------- workload long class
def test_workload_long_population():
    spec = parse_workload("n=40,seed=3,chat=0.5,long=0.3,plen=3:10,"
                          "mnt=2:6,lplen=64:96")
    assert spec.long == 0.3 and spec.lplen == (64, 96)
    arrivals = generate_arrivals(spec)
    longs = [a for a in arrivals if a[4] == "long"]
    assert longs, "40 draws at P(long)=0.3 produced no long arrivals"
    for _step, prompt, mnt, tenant, _cls in longs:
        assert 64 <= len(prompt) <= 96      # drawn from lplen, not plen
        assert 2 <= mnt <= 4                # chat-sized decode budget
        assert tenant.startswith("l")


def test_workload_long_validation_names_the_field():
    with pytest.raises(ValueError, match="'long'"):
        parse_workload("long=1.5")
    with pytest.raises(ValueError, match="'long'"):
        parse_workload("chat=0.8,long=0.5")          # chat + long > 1
    with pytest.raises(ValueError, match="'lplen'"):
        # lplen must sit STRICTLY above plen's HI
        parse_workload("long=0.2,plen=3:10,lplen=8:20")
    with pytest.raises(ValueError, match="'lplen'"):
        parse_workload("lplen=abc")


def test_workload_long_zero_preserves_the_rng_stream():
    """The class draw partitions the SAME uniform the two-class generator
    consumed, so adding a vanishing long share moves nothing — and a
    long=0 spec replays the pre-ISSUE-19 trace bitwise."""
    base = WorkloadSpec(n=30, seed=9, chat=0.6, long=0.0)
    eps = dataclasses.replace(base, long=1e-12, lplen=(64, 96)).validate()
    assert generate_arrivals(base) == generate_arrivals(eps)


# ---------------------------------------------------- SLO long tier
def test_parse_slo_long_tier():
    pol = parse_slo("long_chunk=2,long_weight=2,long_cap=4")
    assert [c.name for c in pol.classes] == ["chat", "long", "batch"]
    assert [c.level for c in pol.classes] == [0, 1, 2]
    spec = pol.spec("long")
    assert spec.chunk_budget == 2
    assert spec.weight == 2
    assert spec.queue_cap == 4


def test_parse_slo_without_long_fields_stays_two_class():
    pol = parse_slo("chat_weight=4,batch_cap=8")
    assert [c.name for c in pol.classes] == ["chat", "batch"]
    assert SLOPolicy.chat_batch() == SLOPolicy.chat_batch(
        long_weight=None, long_chunk_budget=None)


def test_class_spec_chunk_budget_must_be_positive():
    with pytest.raises(AssertionError):
        ClassSpec("long", chunk_budget=0)


# ----------------------------------------------- modeled attention split
def test_fd_attn_split_model_is_sublinear():
    """At real page shapes (page KV bytes ≫ partial-slab row bytes) the
    modeled total shrinks as the SP mesh grows — the property the whole
    ISSUE exists for. bench.py asserts the same thing at 8k–64k tokens;
    this is the unit-sized pin."""
    page_kv, slab_row, steps = 2_097_152, 8_192, 128
    totals = {}
    for n in (1, 2, 4):
        local, fold = fd_attn_split_us(n, 1, 1, steps, page_kv, slab_row)
        if n == 1:
            assert fold == 0.0              # nothing to fold at n=1
        totals[n] = local + fold
    assert totals[4] < totals[2] < totals[1]
    # the local half is the ∝ kv_len/n piece (steps divisible by n here)
    l1, _ = fd_attn_split_us(1, 1, 1, steps, page_kv, slab_row)
    l2, _ = fd_attn_split_us(2, 1, 1, steps, page_kv, slab_row)
    assert l2 == pytest.approx(l1 / 2)


# --------------------------------------------- per-class chunk budget
def _colocated(micro_model, **kw):
    cfg, params = micro_model
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 16)
    kw.setdefault("pages_per_seq", 6)
    kw.setdefault("prefill_chunk", 8)
    return ServingEngine(params, cfg, **kw)


def test_long_chunk_budget_drips_without_recompiling(micro_model):
    """A ``chunk_budget=2`` long class drips a 24-token prompt through
    the ONE compiled chunk program two real tokens at a time — the
    shrink is a runtime scalar (compile count stays 1, ``chunk_shrinks``
    counts every clamped dispatch) and the served tokens match the
    unbudgeted engine bit-for-bit."""
    rng = np.random.RandomState(11)
    arrivals = [(0, rng.randint(1, 128, size=24).tolist(), 2,
                 "l0", "long")]
    slo = SLOPolicy.chat_batch(long_weight=1, long_chunk_budget=2)
    eng = _colocated(micro_model, slo=slo)
    tokens = eng.run(max_steps=MAX_STEPS, arrivals=list(arrivals))
    assert len(tokens) == 1
    assert eng.metrics.counters["chunk_shrinks"] >= 10   # ~12 clamped
    assert eng.compile_stats["prefill_chunk_compiles"] == 1
    base = _colocated(micro_model)
    assert base.run(max_steps=MAX_STEPS, arrivals=list(arrivals)) == tokens
    assert base.metrics.counters.get("chunk_shrinks", 0) == 0
