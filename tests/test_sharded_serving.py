"""Sharded serving (ISSUE 8): the engine's two compiled programs under
shard_map on a TP/SP/EP mesh, held to the bitwise cross-mesh contract.

THE contract (sharded.py module docstring): a 50-request forced-preemption
trace served on an n>1 interpret mesh is BIT-IDENTICAL per request to the
n=1 golden — same tokens, same preemption-survival, across decode horizons
K∈{1,4} and prefill-chunk sizes. The golden is the SAME
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

Every test runs under the per-test SIGALRM watchdog (same pattern as
tests/test_chaos.py): a mesh-collective hang must kill the test loudly,
not stall the suite.
"""

import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TEST_WORLD  # noqa: F401
from triton_dist_tpu.models.llama import LlamaConfig
from triton_dist_tpu.models.moe import MoEConfig, init_moe_params
from triton_dist_tpu.ops.allgather_gemm import GemmConfig, tp_column_linear
from triton_dist_tpu.serving import (ReplicatedDecisionError,
                                     ShardedServingEngine, serving_mesh)
from triton_dist_tpu.serving.kv_pool import KVPagePool
from triton_dist_tpu.serving.scheduler import ContinuousBatchingScheduler

pytestmark = [pytest.mark.mesh, pytest.mark.serving]

WATCHDOG_S = 240          # per-test wall cap — generous, CPU CI is slow
N_REQUESTS = 50
MAX_STEPS = 100_000       # engine's own stall watchdog trips far earlier
WIRE = jnp.float8_e4m3fn  # pinned (NOT "auto") — see module docstring


@pytest.fixture(autouse=True)
def mesh_watchdog():
    """Hard per-test wall-clock watchdog (test_chaos.py pattern): SIGALRM,
    not a thread, so even a wedged collective inside jax is interrupted."""
    def boom(signum, frame):
        raise TimeoutError(
            f"mesh watchdog: test exceeded {WATCHDOG_S}s wall — "
            "a mesh collective (or the engine) is hanging")

    old = signal.signal(signal.SIGALRM, boom)
    signal.alarm(WATCHDOG_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="module")
def moe_model():
    """Micro MoE: smallest shape that exercises every sharded path
    (d_model=128 is the A2A wire-lane floor; 2 KV heads so GQA grouping
    is real; 4 experts / topk 2 so EP dispatch actually routes)."""
    cfg = MoEConfig(base=LlamaConfig(vocab_size=128, d_model=128,
                                     n_layers=1, n_heads=4, n_kv_heads=2,
                                     d_ff=128, max_seq_len=128,
                                     dtype=jnp.float32),
                    num_experts=4, topk=2, moe_d_ff=64)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _trace():
    """50 requests, bursty arrivals (two per step) against a 9-page pool —
    growth-driven preemption is forced, not incidental. Deterministic."""
    rng = np.random.RandomState(77)
    out = []
    for i in range(N_REQUESTS):
        plen = int(rng.randint(3, 17))
        mnt = int(rng.randint(2, 6))
        prompt = rng.randint(1, 128, size=plen).tolist()
        out.append((i // 2, prompt, mnt))
    return out


def _engine(moe_model, tp, sp, ep, **kw):
    cfg, params = moe_model
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 9)          # tight: forces preemption
    kw.setdefault("pages_per_seq", 4)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("wire_dtype", WIRE)
    return ShardedServingEngine(params, cfg, serving_mesh(tp, sp, ep), **kw)


def _serve(moe_model, tp, sp, ep, **kw):
    eng = _engine(moe_model, tp, sp, ep, **kw)
    tokens = eng.run(max_steps=MAX_STEPS, arrivals=_trace())
    return {"tokens": tokens, "compiles": eng.compile_stats,
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
    dispatches — trace must still replay the K=1 n=1 golden exactly."""
    return _serve(moe_model, 1, 2, 2, decode_horizon=4)


def _assert_identical(run, golden):
    assert run["tokens"].keys() == golden["tokens"].keys()
    bad = [r for r in golden["tokens"]
           if run["tokens"][r] != golden["tokens"][r]]
    assert not bad, f"token streams diverged from n=1 golden: rids {bad}"


def test_golden_trace_shape(golden):
    """The golden run actually exercised what the contract claims: every
    request finished, preemption fired, chunked prefill carried every
    prompt token, and the digest guard ran every step."""
    assert len(golden["tokens"]) == N_REQUESTS
    c = golden["counters"]
    assert c["preemptions"] >= 1, "pool sizing no longer forces preemption"
    # every prompt token entered pages through the chunk program — no
    # bucketed inline-prefill program ever compiled
    assert c["prefill_chunks"] > 0
    assert golden["compiles"]["prefill_programs"] == 0
    assert c["digest_checks"] > 0


@pytest.mark.quick
def test_trace_bit_identical_n2(n2_run, golden):
    _assert_identical(n2_run, golden)
    assert n2_run["counters"]["digest_checks"] > 0


def test_trace_bit_identical_n4_horizon4(n4_run, golden):
    _assert_identical(n4_run, golden)


def test_trace_bit_identical_chunk_variant(moe_model, golden):
    """Chunk-size invariance composes with mesh invariance: n=2 with a
    DIFFERENT prefill_chunk (4, the other row-count-specialized A2A
    layer) still replays the chunk=8 golden per request."""
    run = _serve(moe_model, 1, 1, 2, prefill_chunk=4)
    _assert_identical(run, golden)


@pytest.mark.slow
def test_trace_bit_identical_full_sweep(moe_model, golden):
    """Every axis individually plus the full 8-rank mesh."""
    for tp, sp, ep, kw in [(2, 1, 1, {}), (1, 2, 1, {}),
                           (2, 2, 2, {"decode_horizon": 4})]:
        run = _serve(moe_model, tp, sp, ep, **kw)
        _assert_identical(run, golden)


def test_one_program_per_path(golden, n2_run, n4_run):
    """Compile-count guard at n>1 (the GSPMD output-sharding flip this
    pins is real — see the out_shardings comment in engine.py): exactly
    ONE decode program and ONE chunk program per run, same as n=1."""
    for run in (golden, n2_run, n4_run):
        assert run["compiles"]["decode_compiles"] == 1, run["compiles"]
        assert run["compiles"]["prefill_chunk_compiles"] == 1, \
            run["compiles"]
        assert run["compiles"]["prefill_programs"] == 0, run["compiles"]


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
    eng = _engine(moe_model, 1, 1, 2)
    eng.submit([1, 2, 3, 4, 5], 4)
    assert eng.step()                      # healthy step passes the check
    eng._digest_skew[1] = 1                # rank 1 now disagrees
    with pytest.raises(ReplicatedDecisionError, match="diverged"):
        while eng.step():
            pass
    eng._digest_skew[1] = 0
    eng.check_replicated_decisions()       # healthy again


def test_digest_every_disables(moe_model):
    eng = _engine(moe_model, 1, 1, 2, digest_every=0)
    eng._digest_skew[1] = 1                # would trip if checks ran
    eng.submit([1, 2, 3], 2)
    eng.run(max_steps=MAX_STEPS)
    assert eng.metrics.counters["digest_checks"] == 0


# -- constructor precondition refusals ----------------------------------

def test_requires_prefill_chunk(moe_model):
    cfg, params = moe_model
    with pytest.raises(AssertionError, match="prefill_chunk"):
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
