"""Overlapped serving (ISSUE 16): fine-grained compute/comm overlap in
the decode/chunk hot loop, held to the SAME bitwise cross-mesh contract
as tests/test_sharded_serving.py.

THE claim under test: ``overlap="ep"`` (microbatched EP dispatch riding
the segmented counted-signal a2a, expert FFN overlapping the next
microbatch's wire) and ``overlap="ep+sp"`` (plus start-local SP pool
assembly under the allgather) move the SCHEDULE only — every combine is
still a concat or fixed-order fold — so the trace is BIT-IDENTICAL to
the overlap=off n=1 golden at every mesh size, decode horizon and chunk
size. At n=1 that is the forced-preemption trace; the runs across chips
replay its first four requests on the ``N4_PAGES`` pool, where they preempt
too (asserted in every run of the matrix and in the chaos replay).
The fast tier covers n∈{1,2} at K=1, chunk 8; the slow tier fills in the
n∈{1,2,4} × K∈{1,4} × chunk∈{4,8} cross product.

Also covered: the one-decode + one-chunk compile-count guard stays
pinned with overlap on; a PR 7-style chaos schedule (seeded digest skew
through the restore rung) replays bit-identically with overlap on; the
``serving_overlap_mb`` tuned key is sigcheck-gated into the PR 15
registry (and a broken protocol — the seg_dropped_signal gallery kernel
— is REFUSED admission); the exposed/overlapped comm split lands in the
metrics.

Wire dtype pinned to fp8, never "auto" (same caveat as the sharded
suite: auto resolves per rank count, a pinned wire makes every run
quantize identically).
"""

import jax
import jax.numpy as jnp
import pytest

from conftest import (N4_PAGES, N4_REQUESTS, N_REQUESTS, SHARDED_KW,
                      assert_replay_identical, seeded_trace, sharded_engine)
from triton_dist_tpu.models.llama import LlamaConfig
from triton_dist_tpu.models.moe import MoEConfig, init_moe_params
from triton_dist_tpu.serving import ShardedServingEngine, serving_mesh
from triton_dist_tpu.serving.journal import ControlJournal
from triton_dist_tpu.shmem import FaultPlan

pytestmark = [pytest.mark.mesh, pytest.mark.serving]

WIRE = SHARDED_KW["wire_dtype"]
MAX_STEPS = 100_000

# exactly one compiled program per path, regardless of overlap mode —
# overlap must not fork the program cache
ONE_OF_EACH = {"decode_compiles": 1, "prefill_chunk_compiles": 1,
               "params_relaid_bytes": 0, "params_relaid_leaves": []}


def _serve(moe_model, tp, sp, ep, **kw):
    n = N_REQUESTS
    if tp * sp * ep > 1:
        n, kw = N4_REQUESTS, {"num_pages": N4_PAGES, **kw}
    eng = sharded_engine(moe_model, tp, sp, ep, **kw)
    tokens = eng.run(max_steps=MAX_STEPS, arrivals=seeded_trace(n))
    return {"tokens": tokens, "n": n, "compiles": eng.compile_stats,
            "engine": eng, "snap": eng.metrics.snapshot()}


@pytest.fixture(scope="module")
def golden(moe_model):
    """Lazy per-(K, chunk) overlap=off n=1 goldens — each (horizon,
    chunk) pair is its own trace, computed once and shared by the fast
    and slow matrices."""
    cache = {}

    def get(horizon, chunk):
        key = (horizon, chunk)
        if key not in cache:
            cache[key] = _serve(moe_model, 1, 1, 1, decode_horizon=horizon,
                                prefill_chunk=chunk)["tokens"]
        return cache[key]

    return get


# -- the bit-identity matrix -------------------------------------------------
# fast tier: the two cheapest corners (n=1 degenerate + the canonical n=2
# ep+sp case) keep the quick suite inside the tier-1 time budget; the slow
# tier completes the n∈{1,2,4} × K∈{1,4} × chunk∈{4,8} × mode cross
# product (n=1 runs the forced-preemption trace, n>1 its first four on the
# N4_PAGES pool).

_FAST = [
    (1, 1, 1, 1, 8, "ep+sp"),
    (1, 1, 2, 1, 8, "ep+sp"),
]
_SLOW = [
    (1, 1, 1, 4, 4, "ep"),
    (1, 1, 1, 4, 8, "ep+sp"),
    (1, 1, 2, 1, 4, "ep+sp"),
    (1, 1, 2, 4, 4, "ep"),
    (1, 1, 2, 4, 8, "ep"),
    (1, 2, 2, 1, 4, "ep"),
    (1, 2, 2, 1, 8, "ep+sp"),
    (1, 2, 2, 4, 4, "ep+sp"),
    (1, 2, 2, 4, 8, "ep+sp"),
]


def _run_matrix_case(moe_model, golden, tp, sp, ep, horizon, chunk, mode):
    run = _serve(moe_model, tp, sp, ep, decode_horizon=horizon,
                 prefill_chunk=chunk, overlap=mode)
    assert_replay_identical(run["tokens"], golden(horizon, chunk), run["n"])
    # compile guard: overlap still compiles exactly ONE decode + ONE
    # chunk program at this mesh size
    assert run["compiles"] == ONE_OF_EACH, run["compiles"]
    assert run["engine"].overlap == mode
    assert run["engine"].metrics.counters["preemptions"] >= 1
    assert run["engine"].overlap_microbatches == 2   # the tuned default


@pytest.mark.parametrize("tp,sp,ep,horizon,chunk,mode", _FAST)
def test_overlap_bit_identical(moe_model, golden, tp, sp, ep, horizon,
                               chunk, mode):
    _run_matrix_case(moe_model, golden, tp, sp, ep, horizon, chunk, mode)


@pytest.mark.slow
@pytest.mark.parametrize("tp,sp,ep,horizon,chunk,mode", _SLOW)
def test_overlap_bit_identical_full(moe_model, golden, tp, sp, ep, horizon,
                                    chunk, mode):
    _run_matrix_case(moe_model, golden, tp, sp, ep, horizon, chunk, mode)


# -- chaos replay with overlap on --------------------------------------------

def test_chaos_digest_skew_replay_with_overlap(moe_model, golden):
    """A seeded fault schedule (transient digest skew through the PR 9
    restore rung) replayed with overlap ON across two chips (the trace's
    first four requests, preempting on the N4_PAGES pool): the
    divergence is absorbed exactly once and the tokens still match the
    fault-free overlap=off n=1 golden — neither the fault nor overlap
    changes anything a request can see."""
    eng = sharded_engine(moe_model, 1, 1, 2, journal=ControlJournal(),
                         checkpoint_every=2, digest_every=1, overlap="ep+sp",
                         num_pages=N4_PAGES,
                         fault_plan=FaultPlan(seed=5, digest_skew_at=(5,)))
    toks = eng.run(max_steps=MAX_STEPS, arrivals=seeded_trace(N4_REQUESTS))
    c = eng.metrics.counters
    assert c["digest_recoveries"] == 1
    assert c["faults_injected"] >= 1
    assert c["preemptions"] >= 1
    assert_replay_identical(toks, golden(1, 8), N4_REQUESTS)


# -- tuned-key gate ----------------------------------------------------------

def test_overlap_mb_tuned_key_gated_and_consumed():
    """The microbatch depth is a sigcheck-gated registry key: a clean
    config admits (checked=True) and the engine consumes it; admission
    with a broken protocol runner — the seg_dropped_signal gallery
    kernel, the overlap wire's own hazard — is REFUSED with the
    under_signal finding attached."""
    from triton_dist_tpu.analysis.gallery import GALLERY
    from triton_dist_tpu.aot.registry import (RegistryAdmissionError,
                                              TunedConfigRegistry, TunedKey,
                                              set_default_registry)

    reg = TunedConfigRegistry()
    key = TunedKey("serving_overlap_mb", mesh_shape=(1, 1, 1),
                   dtype=str(jnp.dtype(WIRE)))
    reg.put(key, 4)                       # gate runs 4 seg-a2a rounds
    assert reg.checked(key)

    with pytest.raises(RegistryAdmissionError) as exc:
        reg.put(TunedKey("serving_overlap_mb", mesh_shape=(1, 1, 2),
                         dtype=str(jnp.dtype(WIRE))), 2,
                run=GALLERY["seg_dropped_signal"].run)
    assert "under_signal" in exc.value.finding_kinds
    assert len(reg) == 1                  # the refused config never landed
    set_default_registry(reg)
    try:
        # (num_slots // ep) % 4 == 0 holds at this shape, so the tuned
        # depth is admissible and must win over the built-in default 2
        cfg = MoEConfig(base=LlamaConfig(vocab_size=128, d_model=128,
                                         n_layers=1, n_heads=4,
                                         n_kv_heads=2, d_ff=128,
                                         max_seq_len=128,
                                         dtype=jnp.float32),
                        num_experts=4, topk=2, moe_d_ff=64)
        params = init_moe_params(jax.random.PRNGKey(0), cfg)
        eng = ShardedServingEngine(params, cfg, serving_mesh(1, 1, 1),
                                   num_slots=4, page_size=8, num_pages=9,
                                   pages_per_seq=4, prefill_chunk=8,
                                   wire_dtype=WIRE, overlap="ep")
        assert eng.overlap_microbatches == 4
    finally:
        set_default_registry(None)


def test_overlap_mb_explicit_overrides_registry(moe_model):
    eng = sharded_engine(moe_model, 1, 1, 1, overlap="ep",
                         overlap_microbatches=1)
    assert eng.overlap_microbatches == 1


def test_overlap_rejects_indivisible_microbatch(moe_model):
    with pytest.raises(AssertionError, match="microbatch"):
        sharded_engine(moe_model, 1, 1, 1, overlap="ep",
                       overlap_microbatches=3)


def test_overlap_rejects_unknown_mode(moe_model):
    with pytest.raises(AssertionError, match="overlap"):
        sharded_engine(moe_model, 1, 1, 1, overlap="sp")


# -- exposed/overlapped comm split -------------------------------------------

def test_comm_split_metrics(moe_model):
    """The modeled wire split (serving/metrics.py ISSUE 16 hists):
    overlap=off exposes everything, overlap=on hides a strictly positive
    share at n>1, and n=1 (no wire) observes zeros on both."""
    def split(tp, sp, ep, overlap):
        eng = sharded_engine(moe_model, tp, sp, ep, overlap=overlap)
        eng.run(max_steps=MAX_STEPS, arrivals=seeded_trace(1))
        s = eng.metrics.snapshot()
        return (s["exposed_comm_us"]["mean"],
                s["overlapped_comm_us"]["mean"])

    exp_off, ovl_off = split(1, 1, 2, "off")
    assert exp_off > 0 and ovl_off == 0
    exp_on, ovl_on = split(1, 1, 2, "ep")
    assert 0 < exp_on < exp_off
    assert ovl_on > 0
    exp1, ovl1 = split(1, 1, 1, "ep+sp")
    assert exp1 == 0 and ovl1 == 0
