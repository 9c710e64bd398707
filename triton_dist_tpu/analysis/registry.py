"""The op registry sigcheck iterates: every public name in
``triton_dist_tpu.ops`` is either protocol-checked here (a ``run``
callable that drives the op end to end on a :class:`~.capture.FakeContext`
at tiny, assert-satisfying shapes) or carries a documented skip reason
(pure host math, config dataclasses, eager stateful wrappers whose kernel
path is checked through their functional twin).

tests/test_sigcheck.py asserts this registry and the ``ops`` export
surface stay in lockstep: adding an export without registering it (or
registering a ghost) fails the quick tier.

Shapes follow the ops' own validators: lane-multiple (128) contraction
shards where the compiled path insists (``gemm_rs``, ``moe_reduce_rs``,
``ll_ag_merge``), sublane-multiple page sizes, rank-divisible row counts.
They are chosen per rank count inside ``run`` (the capture instantiates
n ∈ {2, 3, 4}).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .api import DEFAULT_MESHES
from .capture import FakeContext

MESH_2D: Tuple[Dict[str, int], ...] = ({"x": 2, "y": 2},)
MESH_LOCAL: Tuple[Dict[str, int], ...] = ({"x": 1},)
MESH_PAIR: Tuple[Dict[str, int], ...] = ({"role": 2},)
# lend_pages' role-gated protocol must balance at ANY axis size (ranks
# outside the {lender, borrower} pair only hit the entry barrier) — the
# ISSUE 17 satellite pins n ∈ {2, 3, 4}
MESH_LEND: Tuple[Dict[str, int], ...] = ({"role": 2}, {"role": 3},
                                         {"role": 4})
MESH_1D_AND_2D = DEFAULT_MESHES + MESH_2D

f32 = jnp.float32
i32 = jnp.int32


@dataclasses.dataclass
class RegistryEntry:
    name: str
    run: Optional[Callable[[FakeContext], Any]] = None
    meshes: Sequence[Dict[str, int]] = DEFAULT_MESHES
    skip: Optional[str] = None


def _local(fn: Callable[[], Any]) -> Callable[[FakeContext], Any]:
    """Wrap a single-device op (no ctx argument) as a registry run: replay
    it as the body of a 1-rank shard_map so its pallas_calls record."""

    def run(ctx: FakeContext):
        ctx.shard_map(lambda: (fn(), jnp.zeros(()))[1],
                      in_specs=(), out_specs=None)()

    return run


# -- collectives -------------------------------------------------------------

def _run_barrier_all_op(ctx):
    from ..ops import barrier_all_op
    barrier_all_op(ctx)()


def _run_all_gather(ctx):
    from ..ops import all_gather
    n = ctx.num_ranks
    x = jnp.zeros((4 * n, 128), f32)
    if len(ctx.axis_names) > 1:
        for method in ("push_2d", "ring_2d"):
            all_gather(ctx, x, axis=None, method=method)
    else:
        for method in ("push", "ring"):
            all_gather(ctx, x, axis="x", method=method)


def _run_all_gather_ll(ctx):
    from ..ops import all_gather_ll, create_ag_ll_workspace
    n = ctx.num_ranks
    ws = create_ag_ll_workspace(ctx, 4, (128,), f32)
    phase = jnp.zeros((1,), i32)
    all_gather_ll(ctx, jnp.zeros((4 * n, 128), f32), ws, phase)


def _run_broadcast(ctx):
    from ..ops import broadcast
    n = ctx.num_ranks
    broadcast(ctx, jnp.zeros((n, 8, 128), f32), axis="x", root=n - 1)


def _run_reduce_scatter(ctx):
    from ..ops import reduce_scatter
    n = ctx.num_ranks
    x = jnp.zeros((4 * n * n, 128), f32)
    if len(ctx.axis_names) > 1:
        reduce_scatter(ctx, x, axis=None, method="ring_2d")
    else:
        reduce_scatter(ctx, x, axis="x", method="ring")


def _run_all_to_all_push(ctx):
    from ..ops import all_to_all_push
    n = ctx.num_ranks
    all_to_all_push(ctx, jnp.zeros((n * n, 8, 128), f32), axis="x")


def _run_all_to_all_push_seg(ctx):
    from ..ops import all_to_all_push_seg
    n = ctx.num_ranks
    # 16 f32 rows split into two 8-row sublane-aligned segments — a real
    # two-segment counted-signal schedule, not the degenerate "full" path
    all_to_all_push_seg(ctx, jnp.zeros((n * n, 16, 128), f32), axis="x",
                        segments=2)


# -- GEMM overlaps -----------------------------------------------------------

def _gemm_cfg():
    from ..ops.gemm import GemmConfig
    return GemmConfig(block_m=8, block_n=128)


def _run_ag_gemm(ctx):
    from ..ops import ag_gemm
    n = ctx.num_ranks
    a = jnp.zeros((8 * n, 128), f32)
    b = jnp.zeros((128, 128 * n), f32)
    ag_gemm(ctx, a, b, axis="x", cfg=_gemm_cfg())


def _run_ag_gemm_ws(ctx):
    from ..ops import ag_gemm_ws, create_ag_gemm_workspace
    n = ctx.num_ranks
    a = jnp.zeros((8 * n, 128), f32)
    b = jnp.zeros((128, 128 * n), f32)
    ws = create_ag_gemm_workspace(ctx, m_local=8, k=128, dtype=f32)
    ag_gemm_ws(ctx, a, b, ws, axis="x", cfg=_gemm_cfg())


def _run_ag_gemm_diff(ctx):
    from ..ops import ag_gemm_diff
    n = ctx.num_ranks
    ag_gemm_diff(ctx, "x", _gemm_cfg(), jnp.zeros((8 * n, 128), f32),
                 jnp.zeros((128, 128 * n), f32))


def _run_tp_column_linear(ctx):
    from ..ops import tp_column_linear
    n = ctx.num_ranks
    w = jnp.zeros((128, 128 * n), f32)
    tp_column_linear(ctx, jnp.zeros((8, 128), f32), w, axis="x", impl="xla")
    tp_column_linear(ctx, jnp.zeros((8 * n, 128), f32), w, axis="x",
                     impl="ag_gemm", cfg=_gemm_cfg())


def _run_gemm_rs(ctx):
    from ..ops import gemm_rs
    n = ctx.num_ranks
    a = jnp.zeros((4 * n, 128 * n), f32)
    b = jnp.zeros((128 * n, 128), f32)
    gemm_rs(ctx, a, b, axis="x")


def _run_gemm_rs_ws(ctx):
    from ..ops import gemm_rs_ws, create_gemm_rs_workspace
    n = ctx.num_ranks
    a = jnp.zeros((4 * n, 128 * n), f32)
    b = jnp.zeros((128 * n, 128), f32)
    ws, stage = create_gemm_rs_workspace(ctx, m_seg=4, n_cols=128,
                                         out_dtype=f32)
    gemm_rs_ws(ctx, a, b, ws, stage, axis="x")


def _run_gemm_rs_diff(ctx):
    from ..ops import gemm_rs_diff
    n = ctx.num_ranks
    gemm_rs_diff(ctx, "x", None, jnp.zeros((4 * n, 128 * n), f32),
                 jnp.zeros((128 * n, 128), f32))


# -- ring attention ----------------------------------------------------------

def _ra_shapes(n, s_local=128):
    # zigzag layout splits each rank's chunk in half, and the compiled-path
    # validator wants 128-multiple row tiles — so zigzag runs need 256
    B, Hq, Hkv, D = 1, 2, 2, 128
    q = jnp.zeros((B, Hq, n * s_local, D), f32)
    kv = jnp.zeros((B, Hkv, n * s_local, D), f32)
    return q, kv


def _run_ring_attention(ctx):
    from ..ops import ring_attention
    q, kv = _ra_shapes(ctx.num_ranks)
    ring_attention(ctx, q, kv, kv, axis="x", block_q=128, block_k=128)


def _run_ring_attention_fwd(ctx):
    from ..ops import ring_attention_fwd
    for layout, s_local in (("contiguous", 128), ("zigzag", 256)):
        q, kv = _ra_shapes(ctx.num_ranks, s_local)
        ring_attention_fwd(ctx, q, kv, kv, axis="x", block_q=128, block_k=128,
                           layout=layout)


def _run_ring_attention_bwd(ctx):
    from ..ops import ring_attention_bwd, ring_attention_fwd
    q, kv = _ra_shapes(ctx.num_ranks)
    o, lse = ring_attention_fwd(ctx, q, kv, kv, axis="x",
                                block_q=128, block_k=128)
    ring_attention_bwd(ctx, q, kv, kv, o, lse, o, axis="x", causal=True,
                       sm_scale=None, block_q=128, block_k=128)


# -- serving: page migration -------------------------------------------------

def _run_migrate_pages(ctx):
    from ..ops import migrate_pages
    n_roles = ctx.num_ranks
    L, num_pages, Hkv, page_size, D, pmax = 2, 9, 2, 8, 32, 4
    pool = jnp.zeros((n_roles, L, num_pages, Hkv, page_size, D), f32)
    migrate_pages(ctx, pool, pool,
                  jnp.array([1, 2, 0, 0], i32), jnp.array([3, 4, 0, 0], i32),
                  jnp.array([2], i32), axis="role")


def _run_lend_pages(ctx):
    from ..ops import lend_pages
    n_roles = ctx.num_ranks
    L, num_pages, Hkv, page_size, D = 2, 9, 2, 8, 32
    pool = jnp.zeros((n_roles, L, num_pages, Hkv, page_size, D), f32)
    # lender 0 → borrower (last rank): at n > 2 the middle ranks are
    # pure bystanders — the capture proves their signal books still
    # balance (entry barrier only)
    lend_pages(ctx, pool, pool,
               jnp.array([1, 2, 0, 0], i32), jnp.array([3, 4, 0, 0], i32),
               jnp.array([2], i32), axis="role",
               lender=0, borrower=n_roles - 1)


# -- EP all-to-all -----------------------------------------------------------

def _run_ep_dispatch_combine(ctx):
    from ..ops import create_all_to_all_context, dispatch, combine
    n = ctx.num_ranks
    T, H, topk = 4, 128, 2
    a2a = create_all_to_all_context(ctx, max_tokens=T, hidden=H, topk=topk,
                                    num_experts=2 * n, dtype=f32)
    tokens = jnp.zeros((n * T, H), f32)
    topk_ids = jnp.zeros((n * T, topk), i32)
    _, _, layout = dispatch(a2a, tokens, topk_ids)
    processed = jnp.zeros((n * n, a2a.capacity, H), f32)
    combine(a2a, processed, layout, jnp.ones((n * T, topk), f32))


def _run_ep_dispatch_combine_2d(ctx):
    from ..ops import (create_all_to_all_context_2d, dispatch_2d, combine_2d)
    n = ctx.num_ranks
    T, H, topk = 4, 128, 2
    a2a = create_all_to_all_context_2d(ctx, max_tokens=T, hidden=H,
                                       topk=topk, num_experts=n, dtype=f32)
    tokens = jnp.zeros((n * T, H), f32)
    topk_ids = jnp.zeros((n * T, topk), i32)
    recv, _, layouts = dispatch_2d(a2a, tokens, topk_ids)
    combine_2d(a2a, jnp.zeros(recv.shape, f32), layouts,
               jnp.ones((n * T, topk), f32))


# -- flash decode ------------------------------------------------------------

def _fd_gqa_decode_partial():
    from ..ops import gqa_decode_partial
    q = jnp.zeros((1, 4, 128), f32)
    kv = jnp.zeros((1, 2, 128, 128), f32)
    gqa_decode_partial(q, kv, kv, jnp.array([64], i32), block_s=128)


def _fd_gqa_decode_paged():
    from ..ops import gqa_decode_paged
    # two live rows around two idle ones: every page DMA the kernel's loop
    # starts (3 + 2 pages, K and V) is waited, and an idle row starts none
    q = jnp.zeros((4, 4, 128), f32)
    pages = jnp.zeros((8, 2, 8, 128), f32)
    gqa_decode_paged(q, pages, pages, jnp.zeros((4, 4), i32),
                     jnp.array([20, 0, 0, 9], i32))


def _fd_gqa_prefill_paged():
    from ..ops import gqa_prefill_paged
    q = jnp.zeros((8, 4, 128), f32)
    pages = jnp.zeros((8, 2, 8, 128), f32)
    gqa_prefill_paged(q, pages, pages, jnp.zeros((4,), i32),
                      jnp.arange(13, 21, dtype=i32), rows_per_block=4)


def _fd_paged_kv_write():
    from ..ops import paged_kv_write
    pages = jnp.zeros((8, 2, 8, 128), f32)
    new = jnp.zeros((1, 2, 128), f32)
    paged_kv_write(pages, pages, new, new, jnp.zeros((1, 4), i32),
                   jnp.array([3], i32))


def _fd_paged_rows_write():
    from ..ops import paged_rows_write
    paged_rows_write(jnp.zeros((8, 8, 128), f32), jnp.zeros((1, 128), f32),
                     jnp.zeros((1, 4), i32), jnp.array([3], i32))


def _fd_mla_decode_paged():
    from ..ops import mla_decode_paged
    # the decode rows' loop, two live rows around two idle ones over a table
    # longer than a group: every page DMA it starts (a short group's 3 pages;
    # a full group then a short one, 9) is waited, and an idle row starts none
    mla_decode_paged(jnp.zeros((4, 2, 256), f32),
                     jnp.zeros((1, 12, 8, 256), f32), jnp.zeros((4, 10), i32),
                     jnp.array([20, 0, 0, 72], i32), layer=0, latent_dim=128,
                     sm_scale=0.1)


def _fd_ssm_decode_update():
    from ..ops import ssm_decode_update
    # two live rows around an idle one, two head blocks a row: every block
    # fetched out of the aliased state leaf is waited and written back
    # (4 fetches, 4 stores), and the idle row starts none
    ssm_decode_update(jnp.zeros((2, 4, 4, 8, 128), f32), 1,
                      jnp.array([1, 2, 3], i32),
                      jnp.array([True, False, True]),
                      jnp.zeros((3, 4, 128), f32), jnp.zeros((3, 4), f32),
                      jnp.zeros((3, 2, 8), f32), jnp.zeros((3, 2, 8), f32))


def _fd_gdn_decode_update():
    from ..ops import gdn_decode_update
    # two live rows around an idle one, two head blocks a row (32 value heads
    # in blocks of 16): every block fetched out of the aliased state leaf is
    # waited and written back (4 fetches, 4 stores), and the idle row starts
    # none
    gdn_decode_update(jnp.zeros((2, 4, 32, 8, 128), f32), 1,
                      jnp.array([1, 2, 3], i32),
                      jnp.array([True, False, True]),
                      jnp.zeros((3, 16, 8), f32), jnp.zeros((3, 16, 8), f32),
                      jnp.zeros((3, 32, 128), f32), jnp.zeros((3, 32), f32),
                      jnp.zeros((3, 32), f32))


def _fd_decode_combine():
    from ..ops import decode_combine
    decode_combine(jnp.zeros((2, 1, 4, 128), f32),
                   jnp.zeros((2, 1, 4, 128), f32))


def _run_ll_ag_merge(ctx):
    from ..ops import ll_ag_merge
    n = ctx.num_ranks
    packed = jnp.zeros((n, 1, 4, 128 + 128), f32)
    ll_ag_merge(ctx, packed, 128, f32, "x")


def _run_sp_gqa_flash_decode(ctx):
    from ..ops import sp_gqa_flash_decode
    n = ctx.num_ranks
    q = jnp.zeros((1, 4, 128), f32)
    kv = jnp.zeros((1, 2, n * 128, 128), f32)
    sp_gqa_flash_decode(ctx, q, kv, kv, jnp.array([100], i32), axis="x",
                        block_s=128)


def _run_sp_paged_attend_write(ctx):
    from ..ops import sp_paged_attend_write
    n = ctx.num_ranks
    q = jnp.zeros((1, 4, 128), f32)
    pages = jnp.zeros((4 * n, 2, 8, 128), f32)
    new = jnp.zeros((1, 2, 128), f32)
    sp_paged_attend_write(ctx, q, new, new, pages, pages,
                          jnp.zeros((1, 4), i32), jnp.array([3], i32),
                          jnp.array([4], i32), axis="x")


def _run_pool_ag_start_local(ctx):
    from ..ops import pool_ag_start_local
    n = ctx.num_ranks
    pages = jnp.zeros((4 * n, 2, 8, 128), f32)
    pool_ag_start_local(ctx, pages, pages, axis="x")


def _run_flash_decode_dist(ctx):
    from ..ops import flash_decode_dist
    n = ctx.num_ranks
    q = jnp.zeros((1, 4, 128), f32)
    pages = jnp.zeros((4 * n, 2, 8, 128), f32)
    new = jnp.zeros((1, 2, 128), f32)
    flash_decode_dist(ctx, q, new, new, pages, pages,
                      jnp.zeros((1, 4), i32), jnp.array([3], i32),
                      jnp.array([4], i32), axis="x")


# -- grouped GEMM / MoE ------------------------------------------------------

def _gg_grouped_gemm():
    from ..ops import grouped_gemm
    tokens = jnp.zeros((16, 64), f32)
    w = jnp.zeros((2, 64, 128), f32)
    grouped_gemm(tokens, w, jnp.zeros((2,), i32), block_m=8)


def _gg_grouped_gemm_gated():
    from ..ops import grouped_gemm_gated
    tokens = jnp.zeros((16, 64), f32)
    w = jnp.zeros((2, 64, 128), f32)
    grouped_gemm_gated(tokens, w, w, jnp.zeros((2,), i32), block_m=8)


def _gg_apply_grouped():
    from ..ops import apply_grouped, grouped_gemm
    tokens = jnp.zeros((16, 64), f32)
    w = jnp.zeros((2, 64, 128), f32)
    apply_grouped(tokens, jnp.zeros((16,), i32), 2,
                  lambda x, be, nb: grouped_gemm(x, w, be, block_m=8,
                                                 n_blocks_used=nb),
                  block_m=8)


def _gg_moe_ffn_local():
    from ..ops import moe_ffn_local
    tokens = jnp.zeros((16, 64), f32)
    moe_ffn_local(tokens, jnp.zeros((16,), i32),
                  jnp.zeros((2, 64, 128), f32), jnp.zeros((2, 128, 64), f32),
                  block_m=8)


def _run_ag_moe_group_gemm(ctx):
    from ..ops import ag_moe_group_gemm
    n = ctx.num_ranks
    tokens = jnp.zeros((8 * n, 64), f32)
    ids = jnp.zeros((8 * n,), i32)
    weights = jnp.zeros((2, 64, 16 * n), f32)
    ag_moe_group_gemm(ctx, tokens, ids, weights, axis="x", block_m=8,
                      block_n=16)


def _run_moe_reduce_rs(ctx):
    from ..ops import moe_reduce_rs
    n = ctx.num_ranks
    T, topk = 4 * n, 2
    tokens = jnp.zeros((T * topk, 128 * n), f32)
    ids = jnp.zeros((T * topk,), i32)
    moe_reduce_rs(ctx, tokens, ids, jnp.ones((T, topk), f32),
                  jnp.zeros((2, 128 * n, 16), f32), axis="x", block_m=8)


# -- the registry ------------------------------------------------------------

_SKIP_PURE = "pure host-side math, no DMA/semaphore protocol"
_SKIP_CLASS = "config/context dataclass, not an op"

_ENTRIES = [
    # common
    RegistryEntry("collective_id_for",
                  skip="deterministic name→collective_id hash; " + _SKIP_PURE),
    RegistryEntry("barrier_all_op", _run_barrier_all_op,
                  meshes=MESH_1D_AND_2D),
    # gemm tiling
    RegistryEntry("GemmConfig", skip=_SKIP_CLASS),
    RegistryEntry("best_gemm_config",
                  skip="tile-size heuristic; " + _SKIP_PURE),
    # allgather
    RegistryEntry("all_gather", _run_all_gather, meshes=MESH_1D_AND_2D),
    RegistryEntry("all_gather_ll", _run_all_gather_ll),
    RegistryEntry("create_ag_ll_workspace", _run_all_gather_ll),
    RegistryEntry("AgLLContext",
                  skip="eager stateful wrapper; kernel path checked via "
                       "all_gather_ll"),
    RegistryEntry("broadcast", _run_broadcast),
    # reduce_scatter
    RegistryEntry("reduce_scatter", _run_reduce_scatter,
                  meshes=MESH_1D_AND_2D),
    # AG-GEMM
    RegistryEntry("ag_gemm", _run_ag_gemm),
    RegistryEntry("ag_gemm_ws", _run_ag_gemm_ws),
    RegistryEntry("create_ag_gemm_workspace", _run_ag_gemm_ws),
    RegistryEntry("create_ag_gemm_context",
                  skip="eager stateful wrapper; kernel path checked via "
                       "ag_gemm_ws"),
    RegistryEntry("AgGemmContext",
                  skip="eager stateful wrapper; kernel path checked via "
                       "ag_gemm_ws"),
    RegistryEntry("tp_column_linear", _run_tp_column_linear),
    RegistryEntry("ag_gemm_diff", _run_ag_gemm_diff),
    # GEMM-RS
    RegistryEntry("gemm_rs", _run_gemm_rs),
    RegistryEntry("gemm_rs_ws", _run_gemm_rs_ws),
    RegistryEntry("create_gemm_rs_workspace", _run_gemm_rs_ws),
    RegistryEntry("create_gemm_rs_context",
                  skip="eager stateful wrapper; kernel path checked via "
                       "gemm_rs_ws"),
    RegistryEntry("GemmRsContext",
                  skip="eager stateful wrapper; kernel path checked via "
                       "gemm_rs_ws"),
    RegistryEntry("gemm_rs_diff", _run_gemm_rs_diff),
    # ring attention
    RegistryEntry("ring_attention", _run_ring_attention),
    RegistryEntry("ring_attention_fwd", _run_ring_attention_fwd),
    RegistryEntry("ring_attention_bwd", _run_ring_attention_bwd),
    RegistryEntry("zigzag_indices", skip=_SKIP_PURE),
    # page migration (pairwise producer/consumer role protocol)
    RegistryEntry("migrate_pages", _run_migrate_pages, meshes=MESH_PAIR),
    RegistryEntry("paged_transport",
                  skip="shared transport host wrapper; protocol checked "
                       "via migrate_pages and lend_pages"),
    # cluster page lending (ISSUE 17): same counted-signal protocol,
    # role-gated — must balance with bystander ranks on the axis
    RegistryEntry("lend_pages", _run_lend_pages, meshes=MESH_LEND),
    # EP all-to-all
    RegistryEntry("all_to_all_push", _run_all_to_all_push),
    # segmented counted-signal wire (ISSUE 16 overlap schedule)
    RegistryEntry("all_to_all_push_seg", _run_all_to_all_push_seg),
    RegistryEntry("create_all_to_all_context", _run_ep_dispatch_combine),
    RegistryEntry("dispatch", _run_ep_dispatch_combine),
    RegistryEntry("combine", _run_ep_dispatch_combine),
    RegistryEntry("route_tokens", _run_ep_dispatch_combine),
    RegistryEntry("create_all_to_all_context_2d", _run_ep_dispatch_combine_2d,
                  meshes=MESH_2D),
    RegistryEntry("dispatch_2d", _run_ep_dispatch_combine_2d,
                  meshes=MESH_2D),
    RegistryEntry("combine_2d", _run_ep_dispatch_combine_2d, meshes=MESH_2D),
    RegistryEntry("route_tokens_2d", _run_ep_dispatch_combine_2d,
                  meshes=MESH_2D),
    RegistryEntry("EpAllToAllContext", skip=_SKIP_CLASS),
    RegistryEntry("Ep2dAllToAllContext", skip=_SKIP_CLASS),
    RegistryEntry("a2a_wire_bytes", skip=_SKIP_PURE),
    RegistryEntry("pick_wire_dtype", skip=_SKIP_PURE),
    RegistryEntry("expected_capacity", skip=_SKIP_PURE),
    # flash decode
    RegistryEntry("gqa_decode_partial", _local(_fd_gqa_decode_partial),
                  meshes=MESH_LOCAL),
    RegistryEntry("gqa_decode_paged", _local(_fd_gqa_decode_paged),
                  meshes=MESH_LOCAL),
    RegistryEntry("gqa_prefill_paged", _local(_fd_gqa_prefill_paged),
                  meshes=MESH_LOCAL),
    RegistryEntry("paged_kv_write", _local(_fd_paged_kv_write),
                  meshes=MESH_LOCAL),
    RegistryEntry("paged_rows_write", _local(_fd_paged_rows_write),
                  meshes=MESH_LOCAL),
    RegistryEntry("mla_decode_paged", _local(_fd_mla_decode_paged),
                  meshes=MESH_LOCAL),
    RegistryEntry("decode_combine", _local(_fd_decode_combine),
                  meshes=MESH_LOCAL),
    # the recurrent state beside the pages (ISSUE 32)
    RegistryEntry("ssm_decode_update", _local(_fd_ssm_decode_update),
                  meshes=MESH_LOCAL),
    RegistryEntry("ssd_chunk_scan",
                  skip="plain jnp scan in blocks; " + _SKIP_PURE),
    # the matrix state of the linear-attention layers (ISSUE 39)
    RegistryEntry("gdn_decode_update", _local(_fd_gdn_decode_update),
                  meshes=MESH_LOCAL),
    RegistryEntry("gdn_chunk_scan",
                  skip="plain jnp scan in blocks; " + _SKIP_PURE),
    RegistryEntry("ll_ag_merge", _run_ll_ag_merge),
    RegistryEntry("sp_gqa_flash_decode", _run_sp_gqa_flash_decode),
    RegistryEntry("sp_paged_attend_write", _run_sp_paged_attend_write),
    # start-local signal-gated pool allgather (ISSUE 16 SP overlap)
    RegistryEntry("pool_ag_start_local", _run_pool_ag_start_local),
    # distributed flash-decode: per-page partial slab exchange + fixed-
    # order page fold (ISSUE 19 long-context serving)
    RegistryEntry("flash_decode_dist", _run_flash_decode_dist),
    # grouped GEMM
    RegistryEntry("grouped_gemm", _local(_gg_grouped_gemm),
                  meshes=MESH_LOCAL),
    RegistryEntry("grouped_gemm_gated", _local(_gg_grouped_gemm_gated),
                  meshes=MESH_LOCAL),
    RegistryEntry("apply_grouped", _local(_gg_apply_grouped),
                  meshes=MESH_LOCAL),
    RegistryEntry("moe_ffn_local", _local(_gg_moe_ffn_local),
                  meshes=MESH_LOCAL),
    RegistryEntry("PackedGatedWeights", skip=_SKIP_CLASS),
    RegistryEntry("pack_gated_weights",
                  skip="pure weight relayout; " + _SKIP_PURE),
    RegistryEntry("align_tokens_by_expert",
                  skip=_SKIP_PURE + "; exercised inside apply_grouped"),
    RegistryEntry("used_block_count",
                  skip=_SKIP_PURE + "; exercised inside apply_grouped"),
    RegistryEntry("emit_grouped_gemm",
                  skip="kernel-body emitter; protocol checked via "
                       "grouped_gemm/grouped_gemm_gated"),
    # MoE overlaps
    RegistryEntry("ag_moe_group_gemm", _run_ag_moe_group_gemm),
    RegistryEntry("moe_reduce_rs", _run_moe_reduce_rs),
    # autotuned wrappers: same kernels behind a config search — the signal
    # protocol is config-independent and checked via the wrapped op
    RegistryEntry("ag_gemm_autotuned",
                  skip="autotune wrapper; protocol checked via ag_gemm"),
    RegistryEntry("gemm_rs_autotuned",
                  skip="autotune wrapper; protocol checked via gemm_rs"),
    RegistryEntry("ag_moe_group_gemm_autotuned",
                  skip="autotune wrapper; protocol checked via "
                       "ag_moe_group_gemm"),
    RegistryEntry("moe_reduce_rs_autotuned",
                  skip="autotune wrapper; protocol checked via moe_reduce_rs"),
    RegistryEntry("grouped_gemm_autotuned",
                  skip="autotune wrapper; protocol checked via grouped_gemm"),
    RegistryEntry("moe_ffn_gated_autotuned",
                  skip="autotune wrapper; protocol checked via "
                       "grouped_gemm_gated"),
    RegistryEntry("ring_attention_autotuned",
                  skip="autotune wrapper; protocol checked via "
                       "ring_attention"),
]

REGISTRY: Dict[str, RegistryEntry] = {e.name: e for e in _ENTRIES}


def surface_names() -> set:
    """Non-module public names exported by ``triton_dist_tpu.ops`` — the set
    the registry must cover exactly."""
    import types
    from .. import ops
    return {name for name in dir(ops)
            if not name.startswith("_")
            and not isinstance(getattr(ops, name), types.ModuleType)}
