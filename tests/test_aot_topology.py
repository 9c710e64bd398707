"""n>1 Mosaic-lowering gate: AOT-compile every overlap kernel against an
abstract 8-device v5e TPU topology — no silicon required.

Interpret-mode tests (the rest of tests/) validate protocol semantics but
not Mosaic lowering, so kernels can hit n>1-only lowering bugs that nothing
catches before a pod run. jax's compile-only topology
client (``jax.experimental.topologies`` over the local libtpu) closes the
gap: ``jit(fn).lower(shaped_args).compile()`` runs the full XLA+Mosaic
pipeline for a v5e-8 mesh and fails loudly on lowering bugs.

Parity: the reference's AOT kernel list compile coverage
(scripts/aot_kernels.txt via tools/compile_aot.py, SURVEY §5.9) — there the
AOT build compiles every shipped kernel signature ahead of time; here the
same sweep doubles as the multi-chip lowering gate.

Bisection note: ``dispatch_2d``/``combine_2d``/fp8 compile clean here at
(2,4) AND at a (1,1) mesh with the local libtpu — a hang of those graphs on
silicon is therefore an execution problem, not a Mosaic compile bug
(scripts/bisect_a2a_onchip.py is the staged runbook).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from conftest import REPO_ROOT  # noqa: F401  (conftest forces the CPU mesh)
from triton_dist_tpu.ops.gemm import GemmConfig
from triton_dist_tpu.shmem.context import ShmemContext

N8 = 8


@pytest.fixture(scope="module", autouse=True)
def _force_compiled_env():
    """Force the compiled Mosaic path (the ops would otherwise pick
    interpret mode off the CPU default backend) and quiet libtpu's host
    introspection; persistent compile cache amortizes reruns."""
    saved = {k: os.environ.get(k) for k in
             ("TDT_FORCE_COMPILED", "TPU_ACCELERATOR_TYPE",
              "TPU_WORKER_HOSTNAMES", "TPU_SKIP_MDS_QUERY")}
    os.environ["TDT_FORCE_COMPILED"] = "1"
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-8")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    # Off-GCE there is no metadata server; libtpu's probe retries for
    # ~7 minutes before giving up (measured 433s of fixture setup).
    # Everything the MDS would provide is already pinned above.
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")
    saved_cache_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", "/tmp/tdt_topo_cache")
    yield
    jax.config.update("jax_compilation_cache_dir", saved_cache_dir)
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc("v5e:2x4", "tpu")
    except Exception as e:
        # a process killed mid-libtpu-init leaves a stale lockfile that
        # would otherwise silently SKIP the whole n>1 lowering gate; only
        # remove it if no live process holds the lock (non-blocking flock)
        if "libtpu_lockfile" in str(e) and _remove_stale_libtpu_lock():
            try:
                return topologies.get_topology_desc("v5e:2x4", "tpu")
            except Exception as e2:  # pragma: no cover
                pytest.skip(f"local libtpu topology unavailable: {e2}")
        pytest.skip(f"local libtpu topology unavailable: {e}")


def _remove_stale_libtpu_lock(path: str = "/tmp/libtpu_lockfile") -> bool:
    import errno
    import fcntl
    try:
        fd = os.open(path, os.O_RDWR)
    except OSError:
        return False
    try:
        fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
    except OSError as err:
        os.close(fd)
        if err.errno in (errno.EACCES, errno.EAGAIN):
            return False  # a live process holds it — do not yank
        return False
    os.close(fd)
    try:
        os.remove(path)
    except OSError:
        return False
    return True


@pytest.fixture(scope="module")
def ctx1d(topo):
    from jax.experimental import topologies
    return ShmemContext(mesh=topologies.make_mesh(topo, (N8,), ("x",)))


@pytest.fixture(scope="module")
def ctx2d(topo):
    from jax.experimental import topologies
    return ShmemContext(mesh=topologies.make_mesh(topo, (2, 4), ("o", "i")))


def sds(ctx, shape, spec, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(ctx.mesh, spec))


def compile_ok(fn, *args):
    exe = jax.jit(fn).lower(*args).compile()
    assert exe is not None


# -- collectives -------------------------------------------------------------

@pytest.mark.parametrize("method", ["push", "ring"])
def test_all_gather_lowers_8dev(ctx1d, method):
    from triton_dist_tpu.ops import all_gather
    x = sds(ctx1d, (N8 * 8, 128), P("x"))
    compile_ok(lambda v: all_gather(ctx1d, v, axis="x", method=method), x)


def test_push2d_all_gather_lowers_8dev(ctx2d):
    from triton_dist_tpu.ops import all_gather
    x = sds(ctx2d, (N8 * 8, 128), P(("o", "i")))
    compile_ok(lambda v: all_gather(ctx2d, v, method="push_2d"), x)


def test_reduce_scatter_lowers_8dev(ctx1d):
    from triton_dist_tpu.ops import reduce_scatter
    x = sds(ctx1d, (N8 * 8, 128), P("x"))
    compile_ok(lambda v: reduce_scatter(ctx1d, v, axis="x"), x)


# -- overlap ops -------------------------------------------------------------

def test_ag_gemm_lowers_8dev(ctx1d):
    from triton_dist_tpu.ops.allgather_gemm import ag_gemm
    M = K = 512
    N = 128 * N8
    a = sds(ctx1d, (M, K), P("x"))
    b = sds(ctx1d, (K, N), P(None, "x"))
    compile_ok(lambda u, v: ag_gemm(ctx1d, u, v, axis="x",
                                    cfg=GemmConfig(M // N8, 128)), a, b)


def test_ag_gemm_2tier_lowers_8dev(ctx2d):
    from triton_dist_tpu.ops.allgather_gemm import ag_gemm
    axes = ("o", "i")
    M, K, N = 512, 128, N8 * 128
    a = sds(ctx2d, (M, K), P(axes))
    b = sds(ctx2d, (K, N), P(None, axes))
    compile_ok(lambda u, v: ag_gemm(ctx2d, u, v, axis=axes,
                                    cfg=GemmConfig(M // N8, 128)), a, b)


def test_gemm_rs_lowers_8dev(ctx1d):
    from triton_dist_tpu.ops.gemm_reduce_scatter import gemm_rs
    M, K, N = N8 * 32, N8 * 128, 128
    a = sds(ctx1d, (M, K), P(None, "x"))
    b = sds(ctx1d, (K, N), P("x", None))
    compile_ok(lambda u, v: gemm_rs(ctx1d, u, v, axis="x",
                                    cfg=GemmConfig(32, 128)), a, b)


def test_gemm_rs_2tier_lowers_8dev(ctx2d):
    from triton_dist_tpu.ops.gemm_reduce_scatter import gemm_rs
    axes = ("o", "i")
    M, K, N = N8 * 32, N8 * 128, 128
    a = sds(ctx2d, (M, K), P(None, axes))
    b = sds(ctx2d, (K, N), P(axes, None))
    compile_ok(lambda u, v: gemm_rs(ctx2d, u, v, axis=axes,
                                    cfg=GemmConfig(32, 128)), a, b)


def test_reduce_scatter_multitier_lowers_8dev(ctx2d):
    from triton_dist_tpu.ops import reduce_scatter
    x = sds(ctx2d, (N8 * N8 * 2, 128), P(("o", "i")))
    compile_ok(lambda v: reduce_scatter(ctx2d, v, method="ring_2d"), x)


# -- EP all-to-all -----------------------------------------------------------

def test_a2a_dispatch_combine_lowers_8dev(ctx1d):
    from triton_dist_tpu.ops.all_to_all import (combine,
                                                create_all_to_all_context,
                                                dispatch)
    T, H, topk = N8 * 4, 128, 2
    a2a = create_all_to_all_context(ctx1d, max_tokens=T // N8, hidden=H,
                                    topk=topk, num_experts=2 * N8, axis="x")
    t = sds(ctx1d, (T, H), P("x"), jnp.bfloat16)
    i = sds(ctx1d, (T, topk), P("x"), jnp.int32)
    w = sds(ctx1d, (T, topk), P("x"))

    def roundtrip(tt, ii, ww):
        recv, _, layout = dispatch(a2a, tt, ii)
        return combine(a2a, recv, layout, ww)

    compile_ok(roundtrip, t, i, w)


def test_a2a_fused_dequant_lowers_8dev(ctx1d):
    """capacity=128 → the IN-KERNEL per-arrival dequant (emit_pipeline with
    the lane→sublane scale broadcast) must lower at n=8."""
    from triton_dist_tpu.ops.all_to_all import (combine,
                                                create_all_to_all_context,
                                                dispatch)
    T, H, topk = N8 * 4, 128, 2
    a2a = create_all_to_all_context(ctx1d, max_tokens=T // N8, hidden=H,
                                    topk=topk, num_experts=2 * N8, axis="x",
                                    capacity=128,
                                    wire_dtype=jnp.float8_e4m3fn)
    assert a2a.capacity == 128
    t = sds(ctx1d, (T, H), P("x"), jnp.bfloat16)
    i = sds(ctx1d, (T, topk), P("x"), jnp.int32)
    w = sds(ctx1d, (T, topk), P("x"))

    def roundtrip(tt, ii, ww):
        recv, _, layout = dispatch(a2a, tt, ii)
        return combine(a2a, recv, layout, ww)

    compile_ok(roundtrip, t, i, w)


@pytest.mark.parametrize("wire", [None, jnp.float8_e4m3fn])
def test_a2a_2tier_lowers_8dev(ctx2d, wire):
    """The round-2 on-chip hang suspect: 2-tier dispatch+combine, bf16 and
    quantized wire."""
    from triton_dist_tpu.ops.all_to_all import (combine_2d,
                                                create_all_to_all_context_2d,
                                                dispatch_2d)
    T, H, topk, E = 8, 128, 2, 16
    a2a = create_all_to_all_context_2d(ctx2d, max_tokens=T, hidden=H,
                                       topk=topk, num_experts=E,
                                       dtype=jnp.bfloat16, wire_dtype=wire)
    spec = P(("o", "i"))
    t = sds(ctx2d, (N8 * T, H), spec, jnp.bfloat16)
    i = sds(ctx2d, (N8 * T, topk), spec, jnp.int32)
    w = sds(ctx2d, (N8 * T, topk), spec)

    def roundtrip(tt, ii, ww):
        recv, _, layouts = dispatch_2d(a2a, tt, ii)
        return combine_2d(a2a, recv, layouts, ww)

    compile_ok(roundtrip, t, i, w)


def test_a2a_2tier_dcn_outer_lowers_8dev(ctx2d, monkeypatch):
    """2-slice virtual topology (VERDICT r4 #6): the OUTER tier forced
    onto DCN compiles the XLA all_to_all variant while the inner tier
    keeps the Pallas kernel — the real multi-slice deployment shape."""
    from triton_dist_tpu.ops.all_to_all import (combine_2d,
                                                create_all_to_all_context_2d,
                                                dispatch_2d)
    monkeypatch.setenv("TDT_DCN_AXES", "o")
    T, H, topk, E = 8, 128, 2, 16
    a2a = create_all_to_all_context_2d(ctx2d, max_tokens=T, hidden=H,
                                       topk=topk, num_experts=E,
                                       dtype=jnp.bfloat16)
    spec = P(("o", "i"))
    t = sds(ctx2d, (N8 * T, H), spec, jnp.bfloat16)
    i = sds(ctx2d, (N8 * T, topk), spec, jnp.int32)
    w = sds(ctx2d, (N8 * T, topk), spec)

    def roundtrip(tt, ii, ww):
        recv, _, layouts = dispatch_2d(a2a, tt, ii)
        return combine_2d(a2a, recv, layouts, ww)

    compile_ok(roundtrip, t, i, w)


def test_ag_gemm_2tier_dcn_outer_lowers_8dev(ctx2d, monkeypatch):
    """2-tier AG-GEMM with the outer tier on DCN: XLA gather outer +
    Pallas overlap inner compiles on the abstract topology."""
    from triton_dist_tpu.ops.allgather_gemm import ag_gemm
    monkeypatch.setenv("TDT_DCN_AXES", "o")
    axes = ("o", "i")
    M, K, N = 512, 128, N8 * 128
    a = sds(ctx2d, (M, K), P(axes))
    b = sds(ctx2d, (K, N), P(None, axes))
    compile_ok(lambda u, v: ag_gemm(ctx2d, u, v, axis=axes,
                                    cfg=GemmConfig(M // N8, 128)), a, b)


def test_moe_2tier_lowers_8dev(ctx2d):
    """Hierarchical MoE overlap ops (AG+GroupGEMM and GroupGEMM+RS over an
    axis tuple) — the inter-node analog paths."""
    from triton_dist_tpu.ops.moe import ag_moe_group_gemm, moe_reduce_rs
    axes = ("o", "i")
    E, H, N, T = 4, 128, N8 * 128, N8 * 32
    t = sds(ctx2d, (T, H), P(axes))
    i = sds(ctx2d, (T,), P(axes), jnp.int32)
    w = sds(ctx2d, (E, H, N), P(None, None, axes))
    compile_ok(lambda tt, ii, ww: ag_moe_group_gemm(ctx2d, tt, ii, ww,
                                                    axis=axes, block_m=32),
               t, i, w)

    K, N2, Tr, topk = N8 * 128, 128, N8 * 8, 2
    t2 = sds(ctx2d, (Tr * topk, K), P(None, axes))
    i2 = sds(ctx2d, (Tr * topk,), P(), jnp.int32)
    tw = sds(ctx2d, (Tr, topk), P())
    w2 = sds(ctx2d, (E, K, N2), P(None, axes, None))
    compile_ok(lambda a, b, c, d: moe_reduce_rs(ctx2d, a, b, c, d,
                                                axis=axes, block_m=16),
               t2, i2, tw, w2)


def test_ring_attention_dp_composed_lowers_8dev(ctx2d):
    """Ring attention with an independent ring per dp row (batch_axis
    composition) on a (2, 4) mesh."""
    from triton_dist_tpu.ops.ring_attention import ring_attention
    B, H, D, s_loc = 2, 2, 128, 128
    S = 4 * s_loc
    spec = P("o", None, "i")
    q = sds(ctx2d, (B, H, S, D), spec)
    k = sds(ctx2d, (B, H, S, D), spec)
    v = sds(ctx2d, (B, H, S, D), spec)
    compile_ok(lambda a, b, c: ring_attention(ctx2d, a, b, c, axis="i",
                                              batch_axis="o", causal=True,
                                              block_q=128, block_k=128),
               q, k, v)


# -- three-tier hierarchy ----------------------------------------------------

@pytest.fixture(scope="module")
def ctx3d(topo):
    from jax.experimental import topologies
    return ShmemContext(mesh=topologies.make_mesh(topo, (2, 2, 2),
                                                  ("a", "b", "c")))


def test_three_tier_lowers_8dev(ctx3d):
    """3-axis hierarchical AG + AG-GEMM (reference push_3d family parity,
    low_latency_allgather.py:345-530) must lower at (2,2,2)."""
    from triton_dist_tpu.ops import all_gather
    from triton_dist_tpu.ops.allgather_gemm import ag_gemm
    axes = ("a", "b", "c")
    x = sds(ctx3d, (N8 * 8, 128), P(axes))
    compile_ok(lambda v: all_gather(ctx3d, v, method="push_2d"), x)
    M, K, N = 512, 128, N8 * 128
    a = sds(ctx3d, (M, K), P(axes))
    b = sds(ctx3d, (K, N), P(None, axes))
    compile_ok(lambda u, v: ag_gemm(ctx3d, u, v, axis=axes,
                                    cfg=GemmConfig(M // N8, 128)), a, b)


# -- MoE overlap -------------------------------------------------------------

def test_ag_moe_group_gemm_lowers_8dev(ctx1d):
    from triton_dist_tpu.ops.moe import ag_moe_group_gemm
    E, H, N, T = 4, 128, N8 * 128, N8 * 32
    t = sds(ctx1d, (T, H), P("x"))
    i = sds(ctx1d, (T,), P("x"), jnp.int32)
    w = sds(ctx1d, (E, H, N), P(None, None, "x"))
    compile_ok(lambda tt, ii, ww: ag_moe_group_gemm(ctx1d, tt, ii, ww,
                                                    block_m=32), t, i, w)


def test_moe_reduce_rs_lowers_8dev(ctx1d):
    from triton_dist_tpu.ops.moe import moe_reduce_rs
    E, K, N, T, topk = 4, N8 * 128, 128, N8 * 8, 2
    t = sds(ctx1d, (T * topk, K), P(None, "x"))
    i = sds(ctx1d, (T * topk,), P(), jnp.int32)
    tw = sds(ctx1d, (T, topk), P())
    w = sds(ctx1d, (E, K, N), P(None, "x", None))
    compile_ok(lambda tt, ii, tww, ww: moe_reduce_rs(ctx1d, tt, ii, tww, ww,
                                                     block_m=16),
               t, i, tw, w)


@pytest.mark.parametrize("op", ["ag_moe_group_gemm", "moe_reduce_rs"])
def test_fused_moe_lowers_with_a_runs_strips_8dev(ctx1d, op):
    """The fused overlap kernels at a serving-size contraction (4,096 deep:
    1 MB an x strip), where the run walk inside ``emit_grouped_gemm`` takes
    all it may (``fit_run_strips``: 8 strips beside the two weight tiles):
    Mosaic's scoped VMEM holds it next to what the kernels themselves keep."""
    from triton_dist_tpu.ops import moe
    from triton_dist_tpu.ops.group_gemm import fit_run_strips
    E, H, T, bf = 8, 4096, N8 * 256, jnp.bfloat16
    if op == "ag_moe_group_gemm":
        bn = moe._default_bn(H, 512, bf)
        compile_ok(lambda tt, ii, ww: moe.ag_moe_group_gemm(ctx1d, tt, ii, ww),
                   sds(ctx1d, (T, H), P("x"), bf),
                   sds(ctx1d, (T,), P("x"), jnp.int32),
                   sds(ctx1d, (E, H, N8 * 512), P(None, None, "x"), bf))
    else:
        bn = moe._default_bn(H, 2048, bf)
        compile_ok(lambda tt, ii, tw, ww: moe.moe_reduce_rs(ctx1d, tt, ii, tw,
                                                            ww),
                   sds(ctx1d, (T * 4, N8 * H), P(None, "x"), bf),
                   sds(ctx1d, (T * 4,), P(), jnp.int32),
                   sds(ctx1d, (T, 4), P()),
                   sds(ctx1d, (E, N8 * H, 2048), P(None, "x", None), bf))
    assert fit_run_strips(H, 128, bn, 2, 2) == (8, 4)


# -- ring attention (training CP) --------------------------------------------

def _qkv_sds(ctx, n, B=1, Hq=2, Hkv=2, s_loc=128, D=128):
    spec = P(None, None, "x")
    S = n * s_loc
    return (sds(ctx, (B, Hq, S, D), spec), sds(ctx, (B, Hkv, S, D), spec),
            sds(ctx, (B, Hkv, S, D), spec))


def test_ring_attention_fwd_lowers_8dev(ctx1d):
    from triton_dist_tpu.ops.ring_attention import ring_attention
    q, k, v = _qkv_sds(ctx1d, N8)
    compile_ok(lambda a, b, c: ring_attention(ctx1d, a, b, c, axis="x",
                                              causal=True, block_q=128,
                                              block_k=128), q, k, v)


def test_ring_attention_bwd_lowers_8dev(ctx1d):
    from triton_dist_tpu.ops.ring_attention import ring_attention
    q, k, v = _qkv_sds(ctx1d, N8)

    def loss(a, b, c):
        return ring_attention(ctx1d, a, b, c, axis="x", causal=True,
                              block_q=128, block_k=128).astype(
            jnp.float32).sum()

    compile_ok(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)


def test_ring_attention_unaligned_tiles_raise(ctx1d):
    """The compiled-backend tile guard must fire with a clear error for
    shapes whose derived tiles are lane-unaligned — BEFORE Mosaic's opaque
    memref_slice rejection, and through every public entry."""
    from triton_dist_tpu.ops.ring_attention import (ring_attention,
                                                    ring_attention_bwd,
                                                    ring_attention_fwd)
    # zigzag chunks of 64 rows (s_loc=128)
    q, k, v = _qkv_sds(ctx1d, N8, s_loc=128)
    for entry in (ring_attention, ring_attention_fwd):
        with pytest.raises(ValueError, match="128-multiple"):
            jax.jit(lambda a, b, c, e=entry: e(
                ctx1d, a, b, c, axis="x", layout="zigzag")).lower(q, k, v)
    with pytest.raises(ValueError, match="128-multiple"):
        o = sds(ctx1d, q.shape, P(None, None, "x"))
        lse = sds(ctx1d, q.shape[:2] + (q.shape[2],), P(None, None, "x"))
        jax.jit(lambda a, b, c, oo, ll, dd: ring_attention_bwd(
            ctx1d, a, b, c, oo, ll, dd, axis="x", causal=True,
            sm_scale=None, layout="zigzag")).lower(q, k, v, o, lse, q)
    # contiguous with a sub-128 derived tile (block_q=64)
    with pytest.raises(ValueError, match="128-multiple"):
        jax.jit(lambda a, b, c: ring_attention(
            ctx1d, a, b, c, axis="x", block_q=64)).lower(q, k, v)


def test_ring_attention_zigzag_bwd_lowers_8dev(ctx1d):
    """The load-balanced causal layout (fwd+bwd) — its two-chunk tile
    offsets exercise different slicing than contiguous. s_loc=256 so each
    zigzag chunk is 128 rows (the compiled-backend floor the op enforces;
    s_loc=128 → 64-row chunks is rejected with a clear error)."""
    from triton_dist_tpu.ops.ring_attention import ring_attention
    q, k, v = _qkv_sds(ctx1d, N8, s_loc=256)

    def loss(a, b, c):
        return ring_attention(ctx1d, a, b, c, axis="x", causal=True,
                              block_q=128, block_k=128,
                              layout="zigzag").astype(jnp.float32).sum()

    compile_ok(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)


# -- full serving composition ------------------------------------------------

def test_moe_decode_step_lowers_8dev(ctx1d):
    """The DeepSeek-style serving step (SP flash-decode attention + EP A2A
    MoE FFN, models.moe.moe_decode_step_sp) — the widest single graph in
    the framework — must lower at n=8 in one piece."""
    from triton_dist_tpu.layers.ep_a2a_layer import EPAll2AllLayer
    from triton_dist_tpu.models.llama import LlamaConfig
    from triton_dist_tpu.models.moe import (MoEConfig, init_moe_params,
                                            moe_decode_step_sp)
    base = LlamaConfig(vocab_size=256, d_model=1024, n_layers=2, n_heads=8,
                       n_kv_heads=2, d_ff=256, max_seq_len=N8 * 128)
    cfg = MoEConfig(base=base, num_experts=2 * N8, topk=2, moe_d_ff=128)
    B, S, L = N8, base.max_seq_len, base.n_layers
    layer = EPAll2AllLayer.create(ctx1d, max_tokens=B // N8,
                                  hidden=base.d_model, topk=cfg.topk,
                                  num_experts=cfg.num_experts, axis="x",
                                  dtype=base.dtype)
    params = jax.eval_shape(lambda k: init_moe_params(k, cfg),
                            jax.random.key(0))  # shapes only, no init work
    params = jax.tree.map(
        lambda s: sds(ctx1d, s.shape, P(), s.dtype), params)
    Hkv, D = base.n_kv_heads, base.head_dim
    kv = sds(ctx1d, (L, B, Hkv, S, D), P(None, None, None, "x", None),
             base.dtype)
    cache = {"k": kv, "v": kv}
    token = sds(ctx1d, (B,), P(), jnp.int32)
    pos = sds(ctx1d, (), P(), jnp.int32)

    compile_ok(lambda p, t, po, c: moe_decode_step_sp(
        ctx1d, layer, p, t, po, cfg, c, sp_axis="x"), params, token, pos,
        cache)


# -- distributed decode ------------------------------------------------------

def test_fused_sp_decode_lowers_8dev(ctx1d):
    from triton_dist_tpu.ops.flash_decode import sp_gqa_flash_decode
    B, Hq, Hkv, D, s_local = 1, 4, 2, 128, 128
    S = N8 * s_local
    q = sds(ctx1d, (B, Hq, D), P())
    k = sds(ctx1d, (B, Hkv, S, D), P(None, None, "x"))
    v = sds(ctx1d, (B, Hkv, S, D), P(None, None, "x"))
    kv = sds(ctx1d, (B,), P(), jnp.int32)
    compile_ok(lambda *a: sp_gqa_flash_decode(ctx1d, *a, ag_method="fused"),
               q, k, v, kv)


@pytest.fixture(scope="module")
def ctx_single(topo):
    """1-device mesh carved from the same topology: the n=1 causal
    contiguous path (flat valid-tile walk over SMEM tile maps) only
    activates at axis size 1."""
    from jax.experimental import topologies
    mesh1 = jax.sharding.Mesh(topologies.make_mesh(
        topo, (N8,), ("x",)).devices[:1], ("x",))
    return ShmemContext(mesh=mesh1)


def test_ring_attention_flat_walk_lowers_1dev(ctx_single):
    """n=1 causal flat walk: Mosaic must accept the SMEM tile-map inputs
    and the dynamic qi_ref[t]/kvi_ref[t] index maps in the 1-D pipeline
    (interpret mode does not model either constraint)."""
    from triton_dist_tpu.ops.ring_attention import ring_attention
    B, Hq, Hkv, S, D = 1, 4, 2, 1024, 128
    q = sds(ctx_single, (B, Hq, S, D), P(None, None, "x"), jnp.bfloat16)
    kv = sds(ctx_single, (B, Hkv, S, D), P(None, None, "x"), jnp.bfloat16)
    compile_ok(lambda a, b, c: ring_attention(
        ctx_single, a, b, c, axis="x", causal=True,
        block_q=256, block_k=256), q, kv, kv)


# -- the KV pool stays in one layout and in place (ISSUE 25) -----------------

POOL_L, POOL_P, POOL_PAGE, POOL_PPS = 2, 209, 128, 13
POOL_HKV, POOL_D = 8, 128                   # Mistral-7B: 8 KV heads of 128
# the pool's row-major 2-D view, as an HLO shape
POOL_VIEW = f"[{POOL_L * POOL_P * POOL_HKV * POOL_PAGE},{POOL_D}]"


def _kernel_grids(jaxpr, found):
    """The grid of each Pallas kernel of a traced program, by its ``name=``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = tuple(eqn.params["grid_mapping"].grid)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_grids(sub, found)
    return found


def _moved_like(text, big):
    """The instructions of an optimised program whose result is shaped like
    one of ``big`` (a pool leaf, a layer of it) and that are a ``copy`` or a
    slice (``dynamic-update-slice`` apart: a carried leaf written in place
    has the leaf's shape by definition)."""
    import re
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.+?) ([\w\-]+)\(", line)
        if not m:
            continue
        name, result, opcode = m.groups()
        kind = name if opcode == "fusion" else opcode
        if any(s in result for s in big) and re.search(
                r"copy|slice", kind) and not re.search(r"update.slice", kind):
            moved.append(line.strip()[:160])
    return moved


def _yielding(text, shapes, kind):
    """The instructions of an optimised program whose opcode (a fusion: its
    name) matches ``kind`` and whose result is shaped like one of
    ``shapes``."""
    import re
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.+?) ([\w\-]+)\(", line)
        if m and re.search(kind, m[1] if m[3] == "fusion" else m[3]) \
                and any(s in m[2] for s in shapes):
            found.append(line.strip()[:160])
    return found


def _engine_programs(topo, cfg, init_params, pages, B, C, W, K=4):
    """The engine's two model functions for ``cfg`` and their abstract
    arguments on one described v5e, as the engine hands them over:
    ``(step, chunk, params, step_rest, chunk_rest)``. ``pages`` pages of 128
    rows, ``B`` slots, a chunk of ``C`` rows, a table ``W`` wide, K token-
    steps a dispatch."""
    from jax.sharding import SingleDeviceSharding
    from triton_dist_tpu.models.llama import (decode_multistep_paged,
                                              prefill_chunk_paged)
    chip = SingleDeviceSharding(topo.devices[0])
    on = lambda t: jax.tree_util.tree_map(            # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), t)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)  # noqa: E731
    params = on(jax.eval_shape(lambda k: init_params(k, cfg),
                               jax.random.PRNGKey(0)))
    pool = on(jax.eval_shape(lambda: cfg.paged.init_pool(cfg, pages, 128)))

    def step(p, t, pos, pg, bt, lim):
        return decode_multistep_paged(p, t, pos, cfg, pg, bt, lim,
                                      horizon=K, eos_id=None)

    def chunk(p, t, s, n, pg, bt):
        return prefill_chunk_paged(p, t, s, n, cfg, pg, bt)

    return (step, chunk, params, (i32(B), i32(B), pool, i32(B, W), i32(B)),
            (i32(C), i32(), i32(), pool, i32(W)))


def _plain_jits(step, chunk, params, step_rest, chunk_rest):
    """The two programs as plain ``jax.jit``s (pool donated, every parameter
    in the layout it comes in), bound to their arguments: name ->
    (jitted, arguments)."""
    return {"decode": (jax.jit(step, donate_argnums=(3,)),
                       (params, *step_rest)),
            "chunk": (jax.jit(chunk, donate_argnums=(4,)),
                      (params, *chunk_rest))}


def _mistral_cfg(n_layers=POOL_L):
    import dataclasses
    from triton_dist_tpu.models.llama import LlamaConfig
    cfg = dataclasses.replace(LlamaConfig.mistral_7b(), n_layers=n_layers)
    assert (cfg.n_kv_heads, cfg.head_dim) == (POOL_HKV, POOL_D)
    return cfg


@pytest.fixture(scope="module")
def paged_programs(topo):
    """The engine's two programs at Mistral-7B widths, 2 layers, at the
    benchmark cell's sizes (209 pages of 128, 16 slots, K = 4, chunk 256),
    lowered for one described v5e the way ``benchmark/tools/fit.py`` lowers
    them (pool donated). Name -> (optimised HLO text, memory analysis, the
    grid of each Pallas kernel of the traced program by its ``name=``)."""
    from triton_dist_tpu.models.llama import init_params
    traced = {name: fn.trace(*args) for name, (fn, args) in _plain_jits(
        *_engine_programs(topo, _mistral_cfg(), init_params, POOL_P, 16, 256,
                          POOL_PPS)).items()}
    out = {}
    for name, tr in traced.items():
        exe = tr.lower().compile()
        out[name] = (exe.as_text(), exe.memory_analysis(),
                     _kernel_grids(tr.jaxpr.jaxpr, {}))
    return out


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_pool_is_not_copied_sliced_or_relaid(paged_programs, program):
    """No ``copy``, ``dynamic-slice`` or ``dynamic-update-slice`` (alone, as
    a fusion's root or asynchronous) yields an array of the pool's or of one
    layer's pool's shape, in any layout, and the program's temporaries stay
    under half the pool: the pool is carried, written and read where it lies.
    The window scatter ``K.at[layer, page, :, slot]`` on the carried stack
    fails this: it makes the compiler hold the pool slot-major of head and
    re-lay ALL of it out for the kernel inside the layer loop."""
    import re
    text, mem, _ = paged_programs[program]
    layer_pool = f"{POOL_P},{POOL_HKV},{POOL_PAGE},{POOL_D}]"
    pool_shapes = [f"[{POOL_L},{layer_pool}", f"[1,{layer_pool}",
                   f"[{layer_pool}"]
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.+?) ([\w\-]+)\(", line)
        if not m:
            continue
        name, result, opcode = m.groups()
        kind = name if opcode == "fusion" else opcode
        # "slice" takes in dynamic-slice, dynamic-update-slice and the
        # asynchronous slice-start; "copy" the asynchronous copy-start
        if any(s in result for s in pool_shapes) and re.search(
                r"copy|slice", kind):
            moved.append(line.strip()[:160])
    assert not moved, "\n".join(moved)
    # nor is the row-major 2-D view both writes go through ever copied (a
    # ``dynamic-update-slice`` of its shape is the chunk's write in place)
    copied = _yielding(text, [POOL_VIEW], "copy")
    assert not copied, "\n".join(copied)
    pool_bytes = 2 * POOL_L * POOL_P * POOL_HKV * POOL_PAGE * POOL_D * 2
    assert mem.temp_size_in_bytes < pool_bytes / 2, (
        mem.temp_size_in_bytes, pool_bytes)
    assert mem.alias_size_in_bytes >= pool_bytes, "the pool is not donated"


def test_chunk_rows_share_one_walk_decode_rows_do_not(paged_programs):
    """The only Pallas kernel of the compiled chunk program is the one named
    ``gqa_prefill_paged`` (once: the layer loop is a scan), so no attention
    over a chunk runs as rows of decode; the decode program holds its
    decode-rows kernel and none of that name (ISSUE 27)."""
    import re
    kernels = {name: re.findall(
        r"(%[\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
        for name, (text, _, _) in paged_programs.items()}
    assert len(kernels["chunk"]) == 1 and re.fullmatch(
        r"%gqa_prefill_paged[.\d]*", kernels["chunk"][0]), kernels["chunk"]
    # (by instruction name: the module's table of source frames may name
    # any function this process has traced)
    assert kernels["decode"] and not any(
        "gqa_prefill_paged" in k for k in kernels["decode"]), kernels["decode"]


def test_decode_rows_walk_live_pages_only(paged_programs):
    """The only Pallas kernel of the compiled decode program is the one named
    ``gqa_decode_paged`` (the name the trace and ``gqa_attn_ms`` find it by),
    and its grid is ONE step for the 16 slots: the walk over the live pages
    is a loop inside the kernel, not 16 x 13 = 208 grid steps a layer call of
    which most do nothing (ISSUE 29). Mosaic takes its hand-made page DMAs
    out of the stacked pool, and the pool left in HBM brings no pool-shaped
    copy: the guard above holds."""
    import re
    text, _, grids = paged_programs["decode"]
    kernels = re.findall(
        r"(%[\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)
    assert len(kernels) == 1 and re.fullmatch(
        r"%gqa_decode_paged[.\d]*", kernels[0]), kernels
    assert grids == {"gqa_decode_paged": (1,)}, grids


# -- the latent family's programs at published widths (ISSUE 26) -------------

# pages: a layer of the pool (184 MB) must not fit the chip's 128 MB of VMEM,
# or XLA prefetches it there whole, which the cell's 2,241 pages never allow
LAT_P, LAT_HELD = 1121, 12


def _latent_cfg():
    import dataclasses
    from triton_dist_tpu.models import mla
    return dataclasses.replace(mla.LatentMoEConfig(), n_layers=3,
                               vocab_size=20480, n_experts_held=LAT_HELD)


@pytest.fixture(scope="module")
def latent_programs(topo):
    """The engine's two programs for ``models.mla`` at Kimi-K2 widths (3
    layers: the dense one and two sparse ones holding 12 of 384 experts), at
    the benchmark cell's batch sizes (32 slots, K = 4, chunk 512, 70 pages a
    sequence), lowered for one described v5e, pool donated. Name -> (optimised
    HLO text, memory analysis, configuration, the grid of each Pallas kernel
    by its ``name=``)."""
    cfg = _latent_cfg()
    from triton_dist_tpu.models import mla
    traced = {name: fn.trace(*args) for name, (fn, args) in _plain_jits(
        *_engine_programs(topo, cfg, mla.init_params, LAT_P, 32, 512,
                          70)).items()}
    out = {}
    for name, tr in traced.items():
        exe = tr.lower().compile()
        out[name] = (exe.as_text(), exe.memory_analysis(), cfg,
                     _kernel_grids(tr.jaxpr.jaxpr, {}))
    return out


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_latent_pool_and_expert_tables_stay_in_place(latent_programs,
                                                     program):
    """Mosaic takes the latent kernel and the grouped GEMMs at the published
    widths, the trace will find them by name, and nothing shaped like the
    latent pool, a layer of it, or an expert table (one layer's or the
    stack's) comes out of a ``copy`` or a slice: the pool is carried and the
    tables are read in place through the flattened [layers x held] view."""
    import re
    text, mem, cfg, _ = latent_programs[program]
    for kernel in ("mla_decode_paged", "grouped_gemm_gated", "grouped_gemm"):
        assert re.search(rf"%{kernel}[.\d]* = [^\n]*custom-call", text), kernel
    W, D, F = cfg.cache_width, cfg.d_model, cfg.moe_d_ff
    big = [f"{LAT_P},128,{W}]", f"{LAT_HELD},{D},{F}]", f"{LAT_HELD},{F},{D}]",
           f"{2 * LAT_HELD},{D},{F}]", f"{2 * LAT_HELD},{F},{D}]"]
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.+?) ([\w\-]+)\(", line)
        if not m:
            continue
        name, result, opcode = m.groups()
        kind = name if opcode == "fusion" else opcode
        if any(s in result for s in big) and re.search(r"copy|slice", kind):
            moved.append(line.strip()[:160])
    assert not moved, "\n".join(moved)
    pool_bytes = cfg.n_layers * LAT_P * 128 * W * 2
    assert mem.alias_size_in_bytes >= pool_bytes, "the pool is not donated"
    assert mem.temp_size_in_bytes < 1.0e9, mem.temp_size_in_bytes


def test_latent_walks_are_loops_over_live_pages(latent_programs):
    """Both programs' latent kernel is the in-kernel loop over live pages
    (ISSUE 31 the decode rows, ISSUE 34 a chunk's): its grid is the row blocks
    alone, 2 x 16 of the 32 slots and 32 x 16 of the chunk's 512 rows, where
    the (row block, page step) grid made 32 x 10 steps a layer call of either;
    Mosaic takes its hand-made page DMAs out of the stacked pool (left in
    HBM: the guard above finds no pool-shaped copy), the decode rows' ring of
    three [7 x 128, 640] operands (8.9 MB of scoped VMEM by the compiler's
    own count) and a row block's [1024, 640] queries, [1024, 4 x 128] float32
    scores and ring of two [4 x 128, 640] operands (12.1 MB by that count)
    inside the default 16 MB: neither call asks for a limit of its own, or the
    fixture's compile would have refused it."""
    import re
    grids = {name: prog[3]["mla_decode_paged"]
             for name, prog in latent_programs.items()}
    assert grids == {"decode": (2,), "chunk": (32,)}, grids
    for name, prog in latent_programs.items():
        asked = re.findall(r"%mla_decode_paged[.\d]* = [^\n]*custom-call[^\n]*"
                           r"\"scoped_memory_configs\":\[([^\]]*)\]", prog[0])
        assert asked and not any(asked), (name, asked)


def test_kv_chunk_walks_are_loops_over_live_pages(topo):
    """The K/V chunk walk is the in-kernel loop over a row block's live pages
    (ISSUE 41): whatever the call (dense, a window over a ring, a sink beside
    keys wider than values), ``gqa_prefill_paged``'s grid is the row blocks
    ALONE, where the (row block, page) grid made 4 x 13, 64 x 34 and 64 x
    200, 16 x 3 and 16 x 108 steps a layer call. The dense call (4 heads a KV
    head, 64 rows a block) stays inside Mosaic's default 16 MB of scoped VMEM
    and asks for no limit; both window families (16, and 8 / 16, heads a KV
    head, 32 rows a block) ask for ``CHUNK_VMEM_LIMIT`` in both kinds of
    layer. (That Mosaic takes each call at its published widths is what the
    programs' compiles in the fixtures around this test show.)"""
    from triton_dist_tpu.models import llama
    from triton_dist_tpu.models import window_moe as wm

    def walks(jaxpr, found):
        """name -> (grid, scoped-VMEM limit asked for) of the chunk walks."""
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call" and eqn.params[
                    "name"].startswith("gqa_prefill_paged"):
                asked = eqn.params["compiler_params"].get("mosaic_tpu")
                found[eqn.params["name"]] = (
                    tuple(eqn.params["grid_mapping"].grid),
                    getattr(asked, "vmem_limit_bytes", None))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walks(sub, found)
        return found

    def chunk_walks(cfg, init, pages, B, C, W):
        _, chunk, params, _, rest = _engine_programs(topo, cfg, init, pages,
                                                     B, C, W)
        return walks(jax.jit(chunk).trace(params, *rest).jaxpr.jaxpr, {})

    limit = wm.CHUNK_VMEM_LIMIT
    assert chunk_walks(_mistral_cfg(), llama.init_params, POOL_P, 16, 256,
                       POOL_PPS) == {"gqa_prefill_paged": ((4,), None)}
    assert chunk_walks(_window_cfg(), wm.init_params, 1201, 24, 2048,
                       201) == {"gqa_prefill_paged_window": ((64,), limit),
                                "gqa_prefill_paged": ((64,), limit)}
    assert chunk_walks(_sink_window_cfg(), wm.init_params, SINK_P, SINK_SLOTS,
                       512, SINK_PPS + 1) == {
        "gqa_prefill_paged_window_sink": ((16,), limit),
        "gqa_prefill_paged": ((16,), limit)}


# -- the mixer-beside-attention family's programs at published widths (ISSUE 32)

HYB_P, HYB_SLOTS = 1282, 64


def _hybrid_cfg(n_layers):
    import dataclasses
    from triton_dist_tpu.models import hybrid_ssm as hm
    return hm.bind(dataclasses.replace(hm.HybridSSMConfig(),
                                       n_layers=n_layers), HYB_SLOTS, 512)


@pytest.fixture(scope="module")
def hybrid_programs(topo):
    """The engine's two programs for ``models.hybrid_ssm`` at Falcon-H1-34B
    widths (the cell's 6 layers, every width and the whole vocabulary as
    published), at the benchmark cell's sizes (64 slots, K = 4, chunk 512, 20 pages a
    sequence and the slot's column), lowered for one described v5e, pool
    donated. Name -> (optimised HLO text, memory analysis, configuration)."""
    from triton_dist_tpu.models import hybrid_ssm as hm
    cfg = _hybrid_cfg(6)
    progs = {name: fn.lower(*args) for name, (fn, args) in _plain_jits(
        *_engine_programs(topo, cfg, hm.init_params, HYB_P, HYB_SLOTS, 512,
                          21)).items()}
    out = {}
    for name, low in progs.items():
        exe = low.compile()
        out[name] = (exe.as_text(), exe.memory_analysis(), cfg)
    return out


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_hybrid_state_and_pages_stay_in_place(hybrid_programs, program):
    """Mosaic takes ``ssm_decode_update`` (its in-kernel transposes, its
    hand-made DMAs out of and back into the aliased state leaf) and the paged
    GQA kernels at a query group of FIVE at the published widths; the trace
    will find them by name; and nothing shaped like the state leaf, a layer
    of it, or the K/V pool comes out of a ``copy`` or a slice: the leaves are
    carried, written and read where they lie (one slot's state, [1, 1, 32,
    256, 128], is what a chunk reads and writes; the conv leaf, 12 MB, is
    small enough that XLA prefetches thirds of it into VMEM: not held here)."""
    import re
    text, mem, cfg = hybrid_programs[program]
    kernels = {"decode": ("ssm_decode_update", "gqa_decode_paged"),
               "chunk": ("gqa_prefill_paged",)}[program]
    for kernel in kernels:
        assert re.search(rf"%{kernel}[.\d]* = [^\n]*custom-call", text), kernel
    assert (program == "chunk") == (
        re.search(r"%ssm_decode_update[.\d]* = ", text) is None)
    S = HYB_SLOTS + 1
    state = f"{S},{cfg.ssm_heads},{cfg.ssm_state},{cfg.ssm_head_dim}]"
    kv = f"{HYB_P},{cfg.n_kv_heads},128,{cfg.head_dim}]"
    big = [f"[{cfg.n_layers},{s}" for s in (state, kv)] \
        + [f"[1,{state}", f"[{state}", f"[1,{kv}", f"[{kv}"]
    # (a dynamic-update-slice of a carried leaf is written in place and
    # has the leaf's shape by definition: the chunk's one-slot write;
    # a copy of the state would show in the temporaries below)
    moved = _moved_like(text, big)
    assert not moved, "\n".join(moved)
    pool_bytes = cfg.n_layers * (
        S * (cfg.ssm_heads * cfg.ssm_state * cfg.ssm_head_dim * 4
             + 3 * cfg.d_xbc * 2)
        + 2 * HYB_P * cfg.n_kv_heads * 128 * cfg.head_dim * 2)
    assert mem.alias_size_in_bytes >= pool_bytes, "the pool is not donated"
    # the state leaf is 1.64 GB: no copy of it fits under this
    assert mem.temp_size_in_bytes < 1.0e9, mem.temp_size_in_bytes


# -- the sink-window family at MiMo-V2-Flash widths (ISSUE 37) ---------------------

SINK_SLOTS, SINK_P, SINK_PPS = 24, 2593, 108


def _sink_window_cfg(periods=1):
    """MiMo-V2-Flash as published (every width; window 128; 8 / 4 KV heads;
    keys of 192 in 256 lanes, values of 128), the leading dense layer and
    ``periods`` periods, 32 of 256 experts, 1/8 of the vocabulary."""
    from triton_dist_tpu.models import window_moe as wm
    return wm.bind(wm.WindowMoEConfig(
        vocab_size=19072, d_model=4096, n_layers=1 + 6 * periods, n_heads=64,
        n_kv_heads=8, full_kv_heads=4, head_dim=192, v_head_dim=128,
        k_pool_width=256, window=128,
        layer_kinds=("window",) * 4 + ("full", "window"), rope_dims=64,
        rope_theta=1e4, full_rope_theta=5e6, sinks=True, value_scale=0.707,
        n_dense_layers=1, d_ff=16384, moe_d_ff=2048, n_routed_experts=256,
        n_experts_held=32, topk=8, n_shared_experts=0, selection_bias=True,
        sequential=True, max_seq_len=SINK_PPS * 128), SINK_SLOTS, 512)


def _sink_window_lowered(topo, cfg):
    from triton_dist_tpu.models import window_moe as wm
    return {name: fn.lower(*args) for name, (fn, args) in _plain_jits(
        *_engine_programs(topo, cfg, wm.init_params, SINK_P, SINK_SLOTS, 512,
                          SINK_PPS + 1)).items()}


@pytest.fixture(scope="module")
def sink_window_programs(topo):
    """The engine's two programs at the benchmark cell's sizes (24 slots, K =
    4, chunk 512, 108 pages a sequence and the ring's column), lowered for
    one described v5e, pool donated. Name -> (optimised HLO text, memory
    analysis, configuration)."""
    cfg = _sink_window_cfg()
    out = {}
    for name, low in _sink_window_lowered(topo, cfg).items():
        exe = low.compile()
        out[name] = (exe.as_text(), exe.memory_analysis(), cfg)
    return out


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_sink_window_pools_stay_in_place(sink_window_programs, program):
    """Mosaic takes the paged GQA kernels at keys of 256 lanes beside values
    of 128, groups of 8 and 16, a sink and a one-page window; the trace will
    find each variant by its own name; and nothing shaped like a pool leaf or
    a layer of one comes out of a ``copy`` or a slice (a 192-wide minor dim
    invites a re-layout: the pool's keys are held in 256 lanes). As the plain
    ``jax.jit`` it is HERE, the decode program re-lays out ``wq`` / ``wk`` /
    ``wv`` once a dispatch (0.8 GB of its temporaries); the engine's does not
    (ISSUE 38: the guard at the end of this file)."""
    import re
    text, mem, cfg = sink_window_programs[program]
    kernels = {"decode": ("gqa_decode_paged_window_sink", "gqa_decode_paged"),
               "chunk": ("gqa_prefill_paged_window_sink",
                         "gqa_prefill_paged")}[program]
    for kernel in kernels:
        assert re.search(rf"%{kernel}[.\d]* = [^\n]*custom-call", text), kernel
    ring = 1 + SINK_SLOTS * cfg.ring_pages(128)
    leaves = [f"{SINK_P},4,128,256]", f"{SINK_P},4,128,128]",
              f"{ring},8,128,256]", f"{ring},8,128,128]"]
    big = [f"[{n},{s}" for s in leaves for n in (1, 2, 5)] \
        + [f"[{s}" for s in leaves]
    moved = _moved_like(text, big)
    assert not moved, "\n".join(moved)
    pool_bytes = 2 * (2 * SINK_P * 4 + 5 * ring * 8) * 128 * (256 + 128)
    assert mem.alias_size_in_bytes >= pool_bytes, "the pool is not donated"
    assert mem.temp_size_in_bytes < 1.0e9, mem.temp_size_in_bytes


@pytest.mark.parametrize("family", ["dense", "sink_window"])
def test_chunk_rows_land_a_page_at_a_time(request, family):
    """The compiled CHUNK program scatters no row into a pool's 2-D view: its
    rows are one sequence's run and go page by page
    (``paged_kv_write(shared_table=True)``: a ``dynamic-update-slice`` of the
    view inside the visits' loop, in place, and no ``copy`` of the view). The
    DECODE program's rows belong to different sequences and keep their row
    scatter, one a pool leaf a layer of the scanned body (ISSUE 47)."""
    if family == "dense":
        progs = request.getfixturevalue("paged_programs")
        views, per_body = [POOL_VIEW], 2
    else:
        progs = request.getfixturevalue("sink_window_programs")
        cfg = progs["chunk"][2]
        ring = 1 + SINK_SLOTS * cfg.ring_pages(128)
        views = [f"[{n * pages * heads * 128},{width}]"
                 for n, pages, heads in ((2, SINK_P, 4), (5, ring, 8))
                 for width in (256, 128)]
        # the dense layer (full) and a period of five window layers and one
        # full one, keys and values each
        per_body = 2 * 7
    text = progs["chunk"][0]
    stray = _yielding(text, views, "scatter|copy")
    assert not stray, "\n".join(stray)
    updates = _yielding(text, views, "^dynamic-update-slice$")
    assert len(updates) == per_body, "\n".join(updates)
    assert len(_yielding(progs["decode"][0], views, "scatter")) == per_body


def test_sink_window_segments_are_one_scanned_body_each(topo):
    """A leading dense layer and TWO periods of six layers whose kinds differ
    in shape lower to one ``while`` a segment (13 layers, not 13 bodies): the
    chunk program has two, the decode program two inside its horizon's one.
    Each of a body's layers holds one more in the chunk program, the visits of
    its K/V write (``paged_kv_write(shared_table=True)``): 1 + 6, whatever
    the periods."""
    low = _sink_window_lowered(topo, _sink_window_cfg(periods=2))
    whiles = {name: lo.as_text().count("stablehlo.while")
              for name, lo in low.items()}
    assert whiles == {"chunk": 2 + 7, "decode": 3}, whiles


# -- the linear-attention family at Qwen3-Next widths (ISSUE 39) --------------------

LIN_SLOTS, LIN_P, LIN_PPS = 128, 1282, 40


def _linear_attn_cfg(periods=1):
    """Qwen3-Next-80B-A3B as published (every width: 16 / 32 linear heads of
    128, conv 4; 16 / 2 heads of 256, rotary 64; router 512, top-10, experts
    of 512), ``periods`` periods of (linear x 3, full), 32 of 512 experts, 1/8
    of the vocabulary, bound to the cell's 128 slots and 2,048-row chunk."""
    from triton_dist_tpu.models import linear_attn_moe as lm
    return lm.bind(lm.LinearAttnMoEConfig(
        vocab_size=19072, n_layers=4 * periods, n_experts_held=32,
        max_seq_len=LIN_PPS * 128), LIN_SLOTS, 2048)


def _linear_attn_lowered(topo, cfg):
    from triton_dist_tpu.models import linear_attn_moe as lm
    return {name: fn.lower(*args) for name, (fn, args) in _plain_jits(
        *_engine_programs(topo, cfg, lm.init_params, LIN_P, LIN_SLOTS, 2048,
                          LIN_PPS + 1)).items()}


@pytest.fixture(scope="module")
def linear_attn_programs(topo):
    """The engine's two programs at the benchmark cell's sizes (128 slots, K
    = 4, chunk 2,048, 40 pages a sequence and the slot's column; the K/V pool
    cut to 1,282 pages), one period, lowered for one described v5e, pool
    donated. ``programs(name)`` -> (optimised HLO text, memory analysis,
    configuration), each compiled at its first reading: a program of this
    family is a minute of the compiler, and the suite's watchdog counts a
    fixture's wall beside five busy workers."""
    import functools
    cfg = _linear_attn_cfg()
    lowered = _linear_attn_lowered(topo, cfg)

    @functools.cache
    def programs(name):
        exe = lowered[name].compile()
        return exe.as_text(), exe.memory_analysis(), cfg
    return programs


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_linear_attn_states_and_pages_stay_in_place(linear_attn_programs,
                                                    program):
    """Mosaic takes ``gdn_decode_update`` (its in-kernel transposes, its
    hand-made DMAs out of and back into the aliased state leaf) and the paged
    GQA kernels at heads of 256 in groups of 8 at the published widths; the
    trace will find them by name; and nothing shaped like the state leaf, a
    layer of it, the conv leaf or the K/V pool comes out of a ``copy`` or a
    slice: each kind of layer's leaves (3 layers of states, 1 of pages) are
    carried, written and read where they lie."""
    import re
    text, mem, cfg = linear_attn_programs(program)
    kernels = {"decode": ("gdn_decode_update", "gqa_decode_paged",
                          "grouped_gemm_gated"),
               "chunk": ("gqa_prefill_paged", "grouped_gemm_gated")}[program]
    for kernel in kernels:
        assert re.search(rf"%{kernel}[.\d]* = [^\n]*custom-call", text), kernel
    assert (program == "chunk") == (
        re.search(r"%gdn_decode_update[.\d]* = ", text) is None)
    S = LIN_SLOTS + 1
    state = f"{S},32,128,128]"
    kv = f"{LIN_P},2,128,256]"
    big = [f"[3,{state}", f"[1,{state}", f"[{state}", f"[1,{kv}", f"[{kv}",
           f"[{3 * S},24576]"]
    moved = _moved_like(text, big)
    assert not moved, "\n".join(moved)
    pool_bytes = 3 * S * (32 * 128 * 128 * 4 + 3 * 8192 * 2) \
        + 2 * LIN_P * 2 * 128 * 256 * 2
    assert mem.alias_size_in_bytes >= pool_bytes, "the pool is not donated"
    # the state leaf is 0.81 GB a period: no copy of it fits under this
    assert mem.temp_size_in_bytes < 0.7e9, mem.temp_size_in_bytes


SC_SLOTS, SC_P, SC_PPS = 96, 1153, 72


def test_short_conv_rows_and_side_by_side_pages_stay_in_place(topo):
    """LFM2-24B-A2B as published (every width: heads of 64, 32 over 8; conv
    of 3 taps over 2,048; 64 experts of 1,536, top-4; dense 11,776), the two
    dense layers and ONE period of (full, conv, conv, conv), bound to the
    cell's 96 slots and 2,048-row chunk, the K/V pool cut to 1,153 pages, both
    programs lowered for one described v5e, pool donated. Mosaic takes both
    paged GQA kernels on ONE pool of ``[K | V]`` rows (heads of 64 as one
    128-lane row, a page one copy); the trace will find them by the dense
    kernels' names; nothing shaped like the pool, a layer of it or the conv
    leaf comes out of a ``copy`` or a slice."""
    import re
    from triton_dist_tpu.models import short_conv_moe as sc
    cfg = sc.bind(sc.ShortConvMoEConfig(n_layers=6,
                                        max_seq_len=SC_PPS * 128),
                  SC_SLOTS, 2048)
    S = SC_SLOTS + 1
    kv = f"{SC_P},8,128,128]"
    big = [f"[1,{kv}", f"[{kv}", f"[{5 * S},4096]"]
    pool_bytes = SC_P * 8 * 128 * 128 * 2 + 5 * S * 4096 * 2
    assert sc.kv_bytes_per_token(cfg) == 2048       # as published
    for program, (fn, args) in _plain_jits(*_engine_programs(
            topo, cfg, sc.init_params, SC_P, SC_SLOTS, 2048,
            SC_PPS + 1)).items():
        exe = fn.lower(*args).compile()
        text, mem = exe.as_text(), exe.memory_analysis()
        walk = {"decode": "gqa_decode_paged", "chunk": "gqa_prefill_paged"}
        for kernel in (walk[program], "grouped_gemm_gated", "grouped_gemm"):
            assert re.search(rf"%{kernel}[.\d]* = [^\n]*custom-call", text), \
                (program, kernel)
        moved = _moved_like(text, big)
        assert not moved, "\n".join(moved)
        assert mem.alias_size_in_bytes >= pool_bytes, "the pool is not donated"
        # a sparse layer's tables are 1.2 GB: no copy of one fits under this
        assert mem.temp_size_in_bytes < 0.5e9, (program,
                                                mem.temp_size_in_bytes)


def test_looped_walks_are_one_body_over_planes_that_stay_in_place(topo):
    """Ouro-2.6B as published (every width: 16 heads for 16 KV heads of 128,
    FFN 5,632, vocabulary 49,152, untied; all 4 walks), depth cut to 4 layers
    for the compile alone, at the cell's 8 slots, 256-row chunk, 42 pages of
    128 and a table 5 wide, both programs lowered for one described v5e, pool
    donated. Mosaic takes both paged GQA kernels at a GROUP OF ONE (a
    [16, 1, 128] query operand a row); the trace will find them by the dense
    kernels' names; each is ONE call in the traced program whatever the
    planes (the scan over layers inside ONE loop over the walks: 3 nested
    loops with the horizon's; 2 in the chunk program, and inside them the
    visits of the layer's K/V write); nothing shaped like the 16-plane pool
    or a plane of it comes out of a ``copy`` or a slice."""
    import re
    from triton_dist_tpu.models import looped as lp
    cfg = lp.LoopedConfig(n_layers=4, max_seq_len=5 * 128)
    assert lp.kv_bytes_per_token(cfg) == 16 * 8192
    pages = 42
    kv = f"{pages},16,128,128]"
    big = [f"[16,{kv}", f"[1,{kv}", f"[{kv}"]
    for program, (fn, args) in _plain_jits(*_engine_programs(
            topo, cfg, lp.init_params, pages, 8, 256, 5)).items():
        traced = fn.trace(*args)
        walk = {"decode": "gqa_decode_paged", "chunk": "gqa_prefill_paged"}
        assert set(_kernel_grids(traced.jaxpr.jaxpr, {})) == {walk[program]}
        assert str(traced.jaxpr).count("pallas_call") == 1
        lowered = traced.lower()
        assert lowered.as_text().count("stablehlo.while") == {
            "decode": 3, "chunk": 2 + 1}[program]
        exe = lowered.compile()
        text, mem = exe.as_text(), exe.memory_analysis()
        assert re.search(rf"%{walk[program]}[.\d]* = [^\n]*custom-call", text)
        moved = _moved_like(text, big)
        assert not moved, "\n".join(moved)
        pool_bytes = 2 * 16 * pages * 16 * 128 * 128 * 2
        assert mem.alias_size_in_bytes >= pool_bytes, "the pool is not donated"
        # a walk's four layers are 0.41 GB: no copy of a stack fits under this
        assert mem.temp_size_in_bytes < 0.3e9, (program,
                                                mem.temp_size_in_bytes)


def test_linear_attn_periods_are_one_scanned_body(topo):
    """Periods of four layers of two kinds lower to ONE ``while`` over the
    periods (8 or 12 layers, not 8 or 12 bodies): a third period adds no
    loop to either program (the body's own: a scan over blocks of 64 tokens
    a linear layer of the chunk program and the visits of the full layer's
    K/V write, the compacted share's loop a layer, the horizon's in the decode
    program)."""
    whiles = [{name: lo.as_text().count("stablehlo.while") for name, lo in
               _linear_attn_lowered(topo, _linear_attn_cfg(periods)).items()}
              for periods in (2, 3)]
    assert whiles[0] == whiles[1], whiles
    assert whiles[0]["chunk"] == 1 + 3 + 1 + 4 and whiles[0]["decode"] == 2 + 4


# -- the parameters are held in the layouts the decode program reads (ISSUE 38) -

def _window_cfg():
    """command-a-plus as published (every width; window 4,096; three window
    layers and a full one: one period), 16 of 128 experts, 1/8 of the
    vocabulary, bound to the cell's 24 slots and 2,048-row chunk."""
    from triton_dist_tpu.models import window_moe as wm
    return wm.bind(wm.WindowMoEConfig(
        vocab_size=32768, d_model=4096, n_layers=4, n_heads=128, n_kv_heads=8,
        head_dim=128, window=4096,
        layer_kinds=("window",) * 3 + ("full",), moe_d_ff=4096,
        n_routed_experts=128, n_experts_held=16, topk=8, n_shared_experts=4,
        rope_theta=5e4, norm_eps=1e-5, max_seq_len=200 * 128), 24, 2048)


def _family_programs(topo, family):
    """One of the six one-chip families at PUBLISHED widths and its cell's
    slots, chunk and table width, depth cut to one period (Mistral: the
    cell's 20 layers, which a scanned layer loop compiles as fast as two, so
    that neither ``wq``'s stack nor ``wk``'s fits the chip's 128 MiB of
    on-chip memory, where a copy is no temporary), pools cut as the guards
    above cut them."""
    from triton_dist_tpu.models import hybrid_ssm as hm
    from triton_dist_tpu.models import linear_attn_moe as lm
    from triton_dist_tpu.models import llama, mla
    from triton_dist_tpu.models import window_moe as wm
    cfg, init, pages, B, C, W = {
        "dense": lambda: (_mistral_cfg(20), llama.init_params, POOL_P, 16,
                          256, POOL_PPS),
        "latent": lambda: (_latent_cfg(), mla.init_params, LAT_P, 32, 512,
                           70),
        "window": lambda: (_window_cfg(), wm.init_params, 1201, 24, 2048,
                           201),
        "hybrid": lambda: (_hybrid_cfg(2), hm.init_params, HYB_P, HYB_SLOTS,
                           512, 21),
        "sink_window": lambda: (_sink_window_cfg(), wm.init_params, SINK_P,
                                SINK_SLOTS, 512, SINK_PPS + 1),
        "linear_attn": lambda: (_linear_attn_cfg(), lm.init_params, LIN_P,
                                LIN_SLOTS, 2048, LIN_PPS + 1)}[family]()
    return _engine_programs(topo, cfg, init, pages, B, C, W)


@pytest.fixture(scope="module", params=["dense", "latent", "window", "hybrid",
                                        "sink_window", "linear_attn"])
def held_family(topo, request):
    """A family's programs THROUGH ``serving.layouts.held_layout_programs``
    (what the engine calls off the CPU): ``(params, chunk_rest, decode,
    chunk_jit, formats)``. The decode program is compiled here and the chunk
    program in the test: at the linear-attention family's widths each is
    most of a minute, and the suite's watchdog counts either's wall."""
    from triton_dist_tpu.serving import layouts
    step, chunk, params, step_rest, chunk_rest = _family_programs(
        topo, request.param)
    return (params, chunk_rest,
            *layouts.held_layout_programs(step, chunk, params, step_rest))


def test_no_program_relays_out_a_weight_at_its_entry(held_family):
    """Neither the decode nor the chunk program copies, transposes or
    re-tiles a parameter of a weight leaf's shape in its ENTRY computation:
    the weights are committed once to what the decode program reads, and the
    chunk program is compiled against that. No copy of the leaves held
    re-laid would fit the decode program's temporaries."""
    from triton_dist_tpu.serving import layouts
    params, chunk_rest, decode, chunk_jit, formats = held_family
    held = layouts.relaid(params, formats)
    assert held, "the compiler asked for every leaf as it comes"
    assert layouts.entry_copies(decode.as_text(), params) == []
    chunk_exe = chunk_jit.lower(params, *chunk_rest).compile()
    assert layouts.entry_copies(chunk_exe.as_text(), params) == []
    assert decode.memory_analysis().temp_size_in_bytes < sum(
        h["bytes"] for h in held)


def test_the_plain_decode_program_copies_what_the_held_one_does_not(topo):
    """The guard above can fail: the same decode program as a plain
    ``jax.jit`` (Mistral-7B widths, 20 layers) re-lays ``wq`` and ``wk`` out
    at its entry, every dispatch (ROADMAP A10, as it stood), and the held
    program's temporaries are smaller by the bytes that one copied."""
    from triton_dist_tpu.serving import layouts
    step, chunk, params, step_rest, _ = _family_programs(topo, "dense")
    decode, _, formats = layouts.held_layout_programs(step, chunk, params,
                                                      step_rest)
    plain = jax.jit(step, donate_argnums=(3,)).lower(
        params, *step_rest).compile()
    copies = layouts.entry_copies(plain.as_text(), params)
    assert len(copies) == 2 and all(" copy(" in c for c in copies), copies
    freed = (plain.memory_analysis().temp_size_in_bytes
             - decode.memory_analysis().temp_size_in_bytes)
    copied = sum(h["bytes"] for h in layouts.relaid(params, formats))
    assert 0.99 * copied <= freed <= 1.01 * copied, (freed, copied)


def test_relaid_bytes_are_the_leaves_not_held_as_they_come(topo):
    """What the engine reports as ``params_relaid_bytes`` / ``_leaves``
    (``layouts.relaid`` of the formats it committed to): at Mistral-7B's
    widths the decode program asks for ``wq`` and ``wk`` with the contracted
    dim minor, every other leaf as the device holds it by default."""
    from triton_dist_tpu.serving import layouts
    step, chunk, params, step_rest, _ = _family_programs(topo, "dense")
    _, _, formats = layouts.held_layout_programs(step, chunk, params,
                                                 step_rest)
    held = {h["leaf"]: h for h in layouts.relaid(params, formats)}
    assert sorted(held) == ["['blocks']['wk']", "['blocks']['wq']"], held
    assert {(h["from"], h["to"]) for h in held.values()} == {
        ("{2,1,0}", "{1,2,0}")}
    blocks = params["blocks"]
    assert sum(h["bytes"] for h in held.values()) == 2 * (
        blocks["wq"].size + blocks["wk"].size) > 0
    differ = [jax.tree_util.keystr(path) for (path, leaf), fmt in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree_util.tree_leaves(formats))
        if fmt.layout != layouts.default_layout(fmt, leaf)]
    assert differ == sorted(held)
