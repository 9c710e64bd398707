"""Sharded serving (ISSUE 8): the engine's two compiled programs under
shard_map on a TP/SP/EP mesh, held to the bitwise cross-mesh contract.

THE contract (sharded.py module docstring): a forced-preemption
trace served on an n>1 interpret mesh is BIT-IDENTICAL per request to the
n=1 golden — same tokens, same preemption-survival, across decode horizons
K∈{1,4} and prefill-chunk sizes. Tier 1 replays the whole trace once across
chips (``n2_run``); every further run across chips replays its first four
requests on the ``N4_PAGES`` pool, where they preempt too: EVERY run held to
the golden asserts a preemption. The golden is the SAME
``ShardedServingEngine`` at mesh 1x1x1: hooks set, loops unrolled, fp8
wire round-tripped — so n>1 changes ONLY the rank count, never the code
path.

The wire dtype is PINNED to fp8 here rather than left on ``"auto"``:
auto resolves per rank count (``pick_wire_dtype``), so an n=1 golden under
auto could legitimately pick a different wire dtype than the n=4 run and
the comparison would test nothing. Pinning makes every run quantize
identically (docs/serving.md spells out the caveat).

Also covered: the one-program-per-path compile-count guard at n>1, the
replicated-decision digest guard (sensitivity + divergence injection),
constructor precondition refusals, and the ag_gemm TP impl's
allclose-only status.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import (N4_PAGES, N4_REQUESTS, N_REQUESTS, SHARDED_KW,
                      assert_replay_identical, seeded_trace, sharded_engine)
from triton_dist_tpu.ops.allgather_gemm import GemmConfig, tp_column_linear
from triton_dist_tpu.serving import (ReplicatedDecisionError,
                                     ShardedServingEngine, serving_mesh)
from triton_dist_tpu.serving.kv_pool import KVPagePool
from triton_dist_tpu.serving.scheduler import ContinuousBatchingScheduler

pytestmark = [pytest.mark.mesh, pytest.mark.serving]

WIRE = SHARDED_KW["wire_dtype"]
MAX_STEPS = 100_000       # engine's own stall watchdog trips far earlier


def _serve(moe_model, tp, sp, ep, n=N_REQUESTS, **kw):
    eng = sharded_engine(moe_model, tp, sp, ep, **kw)
    tokens = eng.run(max_steps=MAX_STEPS, arrivals=seeded_trace(n))
    return {"tokens": tokens, "n": n, "compiles": eng.compile_stats,
            "counters": dict(eng.metrics.counters)}


@pytest.fixture(scope="module")
def golden(moe_model):
    """The n=1 golden: the SAME sharded engine at mesh 1x1x1."""
    return _serve(moe_model, 1, 1, 1)


@pytest.fixture(scope="module")
def n2_run(moe_model):
    return _serve(moe_model, 1, 1, 2)


@pytest.fixture(scope="module")
def n4_run(moe_model):
    """n=4 with the OTHER decode horizon: SP×EP mesh, K=4 multi-token
    dispatches — the trace's first four must still replay the K=1 n=1
    golden exactly."""
    return _serve(moe_model, 1, 2, 2, n=N4_REQUESTS, num_pages=N4_PAGES,
                  decode_horizon=4)


def _assert_identical(run, golden):
    """Every request the run was given finished with the n=1 golden's
    tokens (the runs on the trace's first four are held to rids 0-3), and
    the run preempted on the way."""
    assert_replay_identical(run["tokens"], golden["tokens"], run["n"])
    assert run["counters"]["preemptions"] >= 1, "the run never preempted"


def test_golden_trace_shape(golden):
    """The golden run actually exercised what the contract claims: every
    request finished, preemption fired, chunked prefill carried every
    prompt token, and the digest guard ran every step."""
    assert len(golden["tokens"]) == N_REQUESTS
    c = golden["counters"]
    assert c["preemptions"] >= 1, "pool sizing no longer forces preemption"
    # every prompt token entered pages through the chunk program
    assert c["prefill_chunks"] > 0
    assert c["digest_checks"] > 0


@pytest.mark.quick
def test_trace_bit_identical_n2(n2_run, golden):
    _assert_identical(n2_run, golden)
    assert n2_run["counters"]["digest_checks"] > 0


@pytest.mark.slow          # 5-10 s an interpreter step at n=4: over a minute
def test_trace_bit_identical_n4_horizon4(n4_run, golden):
    _assert_identical(n4_run, golden)
    assert n4_run["compiles"] == golden["compiles"]


def test_trace_bit_identical_chunk_variant(moe_model, golden):
    """Chunk-size invariance composes with mesh invariance: n=2 with a
    DIFFERENT prefill_chunk (4, the other row-count-specialized A2A
    layer) still replays the chunk=8 golden per request."""
    run = _serve(moe_model, 1, 1, 2, n=N4_REQUESTS, num_pages=N4_PAGES,
                 prefill_chunk=4)
    _assert_identical(run, golden)


@pytest.mark.slow
def test_trace_bit_identical_full_sweep(moe_model, golden):
    """Every axis individually plus the full 8-rank mesh."""
    for tp, sp, ep, kw in [(2, 1, 1, {}), (1, 2, 1, {}),
                           (2, 2, 2, {"decode_horizon": 4})]:
        run = _serve(moe_model, tp, sp, ep, **kw)
        _assert_identical(run, golden)


def test_one_program_per_path(golden, n2_run):
    """Compile-count guard at n>1 (the GSPMD output-sharding flip this
    pins is real — see the out_shardings comment in engine.py): exactly
    ONE decode program and ONE chunk program per run, same as n=1."""
    for run in (golden, n2_run):
        assert run["compiles"]["decode_compiles"] == 1, run["compiles"]
        assert run["compiles"]["prefill_chunk_compiles"] == 1, \
            run["compiles"]


# -- replicated-decision digest guard -----------------------------------

def test_control_digest_sensitivity():
    """The digest moves on every control-plane decision class it claims
    to cover: allocation, free (order-sensitively), admission, ticketing."""
    pool = KVPagePool(8, 16, reserved=1)
    d0 = pool.digest()
    assert pool.alloc("r1", 2)
    d1 = pool.digest()
    assert d1 != d0
    pool.free_seq("r1")
    d2 = pool.digest()
    assert d2 != d1
    # deterministic: an identical decision history digests identically
    twin = KVPagePool(8, 16, reserved=1)
    assert twin.alloc("r1", 2)
    twin.free_seq("r1")
    assert twin.digest() == d2

    sched = ContinuousBatchingScheduler(4)
    s0 = sched.digest()
    from triton_dist_tpu.serving.scheduler import Request
    sched.submit(Request(rid=1, prompt=(1, 2, 3), max_new_tokens=2))
    assert sched.digest() != s0


@pytest.mark.quick
def test_digest_divergence_raises(moe_model):
    """Inject a per-rank digest skew (the test hook — a single-controller
    process cannot organically fork a replicated digest) and the guard
    must trip on the next productive step."""
    eng = sharded_engine(moe_model, 1, 1, 2)
    eng.submit([1, 2, 3, 4, 5], 4)
    assert eng.step()                      # healthy step passes the check
    eng._digest_skew[1] = 1                # rank 1 now disagrees
    with pytest.raises(ReplicatedDecisionError, match="diverged"):
        while eng.step():
            pass
    eng._digest_skew[1] = 0
    eng.check_replicated_decisions()       # healthy again


def test_digest_guard_names_the_rank_at_n4(moe_model):
    """The guard over the full 1x2x2 mesh, no step dispatched (tier 1's
    stand-in for the n=4 replay, which is `slow`): four ranks agree, then
    ONE skewed rank is named with the mesh it sits on."""
    eng = sharded_engine(moe_model, 1, 2, 2)
    assert eng.n_ranks == 4 and eng.mesh_desc == "1x2x2"
    eng.check_replicated_decisions()
    eng._digest_skew[3] = 1
    with pytest.raises(ReplicatedDecisionError, match=r"ranks \[3\].*1x2x2"):
        eng.check_replicated_decisions()
    assert eng.metrics.counters["digest_checks"] == 2


def test_digest_every_disables(moe_model):
    eng = sharded_engine(moe_model, 1, 1, 2, digest_every=0)
    eng._digest_skew[1] = 1                # would trip if checks ran
    eng.submit([1, 2, 3], 2)
    eng.run(max_steps=MAX_STEPS)
    assert eng.metrics.counters["digest_checks"] == 0


# -- constructor precondition refusals ----------------------------------

def test_requires_prefill_chunk(moe_model):
    cfg, params = moe_model
    with pytest.raises(ValueError, match="prefill_chunk"):
        ShardedServingEngine(params, cfg, serving_mesh(1, 1, 2),
                             prefill_chunk=None, wire_dtype=WIRE)


def test_requires_ep_divisibility(moe_model):
    cfg, params = moe_model
    with pytest.raises(AssertionError, match="split evenly"):
        ShardedServingEngine(params, cfg, serving_mesh(1, 1, 2),
                             num_slots=3, prefill_chunk=8, wire_dtype=WIRE)
    with pytest.raises(AssertionError, match="split evenly"):
        ShardedServingEngine(params, cfg, serving_mesh(1, 1, 2),
                             num_slots=4, prefill_chunk=7, wire_dtype=WIRE)


def test_requires_mesh_axes(moe_model):
    cfg, params = moe_model
    from triton_dist_tpu.shmem.context import initialize_distributed
    ctx = initialize_distributed(axis_names=("role",), mesh_shape=(2,))
    with pytest.raises(AssertionError, match="missing axis"):
        ShardedServingEngine(params, cfg, ctx, prefill_chunk=8,
                             wire_dtype=WIRE)


# -- TP impl status ------------------------------------------------------

@pytest.mark.quick
def test_tp_column_linear_xla_bitwise_ag_gemm_allclose():
    """impl="xla" is bitwise-equal to the unsplit matmul (the exactness
    fact the trace contract leans on); impl="ag_gemm" — the Pallas
    overlap kernel — is allclose only, which is exactly why the engine
    defaults to xla for the bit-pinned path.

    Single-axis mesh: the Pallas DMA lowering refuses LOGICAL device ids
    on meshes with more than one named axis, so the ag_gemm impl is
    (for now) only reachable on an effectively-1-axis serving mesh
    (docs/serving.md notes this alongside its allclose-only status)."""
    from triton_dist_tpu.shmem.context import initialize_distributed
    ctx = initialize_distributed(axis_names=("tp",), mesh_shape=(2,))
    rng = np.random.RandomState(3)
    h = jnp.asarray(rng.randn(16, 128), jnp.float32)
    w = jnp.asarray(rng.randn(128, 256), jnp.float32)
    ref = h @ w
    out_xla = jax.jit(lambda h, w: tp_column_linear(
        ctx, h, w, axis="tp", impl="xla"))(h, w)
    assert jnp.array_equal(out_xla, ref)
    out_ag = jax.jit(lambda h, w: tp_column_linear(
        ctx, h, w, axis="tp", impl="ag_gemm",
        cfg=GemmConfig(block_m=8, block_n=128)))(h, w)
    np.testing.assert_allclose(np.asarray(out_ag), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
