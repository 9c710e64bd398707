"""Grouped (expert) GEMM + MoE token alignment (analog of reference
``sort_topk_ids_align_block_size`` allgather_group_gemm.py:54-139, the
grouped-GEMM consumer kernels :229-316, and csrc's
``moe_ag_scatter_align_block_size`` moe_utils.cu:61-356).

TPU-native design: tokens are sorted by expert and padded so every
``block_m`` row-block belongs to exactly one expert; a scalar-prefetch array
maps each block to its expert, letting the BlockSpec index_map stream the
right expert's weight tile — the Pallas/TPU shape of "grouped GEMM" (cf.
megablox). Sorting/alignment is pure jnp (argsort + one-hot cumsum), not a
hand-written CUDA kernel: it runs on the VPU inside the same jit.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.utils import default_interpret


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PackedGatedWeights:
    """The [E, H, 2F] interleaved gate‖up layout from ``pack_gated_weights``
    together with the ``block_n`` it was packed with. The interleave is
    invisible in the array's shape, so a bare array cannot be validated by
    the consumer — carrying the pack width in the type is what closes that
    contract: ``grouped_gemm_gated(packed=True)`` and
    ``moe_mlp_ep_overlap`` reject a width mismatch instead of silently
    computing garbage. ``block_n`` is pytree aux data (static under jit)."""

    w: jax.Array
    block_n: int

    def tree_flatten(self):
        return (self.w,), self.block_n

    @classmethod
    def tree_unflatten(cls, block_n, children):
        return cls(children[0], block_n)

    @property
    def shape(self):
        return self.w.shape

    @property
    def dtype(self):
        return self.w.dtype


def align_tokens_by_expert(ids: jax.Array, num_experts: int, block_m: int,
                           with_used_count: bool = False):
    """Sort token indices by expert and pad each expert's run to a multiple
    of ``block_m`` (analog of sort_topk_ids_align_block_size,
    allgather_group_gemm.py:54-139 — there a CPU/CUDA helper, here jnp).

    ids: [T] expert id per row (-1 = invalid/padding row).
    Returns (gather_idx [P], row_valid [P], block_expert [P//block_m]) with
    the *packed* static bound ``P = round_up(T, bm) + E*bm`` (each expert
    wastes < one block of padding; per-expert offsets are runtime values —
    ``block_expert`` is a scalar-prefetch array, so dynamic packing is
    free). Gathered row j participates in expert ``block_expert[j//bm]``'s
    GEMM iff ``row_valid[j]``; blocks past the used range carry no valid
    rows.

    ``with_used_count=True`` appends the runtime used-block bound (see
    ``used_block_count``) as a 4th element, computed from the counts this
    layout already materializes — callers that need both avoid a second
    one-hot pass over ``ids``.

    Host routing tables (numpy ``ids``) take the native C++ path
    (``csrc.moe_align_block_size`` — the analog of the reference's
    registered host op, csrc moe_utils.cu:61-356 via registry.cc:32-44):
    no device round-trip, no one-hot materialization. Traced/device ids
    use the jnp twin below; the two are cross-tested in test_tools.py.
    """
    import numpy as np
    if isinstance(ids, np.ndarray) and not isinstance(ids, jax.Array):
        from triton_dist_tpu import csrc
        res = csrc.native_or_none("moe_align_block_size", ids, num_experts,
                                  block_m)
        if res is not None:
            g, v, b = res
            if not with_used_count:
                return g, v, b
            # out-of-range ids (>= E) are invalid rows in both twins'
            # layouts — they must not count toward the block bound
            in_range = ids[(ids >= 0) & (ids < num_experts)]
            counts = np.bincount(in_range.astype(np.int64),
                                 minlength=num_experts)
            n_used = max(1, int(np.sum(-(-counts // block_m))))
            return g, v, b, np.int32(n_used)
    T = ids.shape[0]
    E = num_experts
    bm = block_m
    P = ((T + bm - 1) // bm) * bm + E * bm
    n_blocks = P // bm
    ids_safe = jnp.where(ids >= 0, ids, E)
    oh = jax.nn.one_hot(ids_safe, E + 1, dtype=jnp.int32)
    rank_in_e = (jnp.cumsum(oh, axis=0) - oh)[jnp.arange(T), ids_safe]
    counts = jnp.sum(oh[:, :E], axis=0)                       # [E]
    blocks_e = (counts + bm - 1) // bm                        # [E]
    block_start = jnp.cumsum(blocks_e) - blocks_e             # [E] (blocks)
    row_start = block_start * bm                              # [E] (rows)
    dest_row = jnp.where(ids >= 0,
                         jnp.take(row_start, jnp.clip(ids_safe, 0, E - 1))
                         + rank_in_e,
                         P)  # invalid rows -> dropped
    gather_idx = jnp.zeros((P,), jnp.int32).at[dest_row].set(
        jnp.arange(T, dtype=jnp.int32), mode="drop")
    row_valid = jnp.zeros((P,), jnp.bool_).at[dest_row].set(True, mode="drop")
    # expert of block i: number of experts whose block range ends at or
    # before i (unused tail blocks get expert E-1; their rows are invalid)
    blk = jnp.arange(n_blocks, dtype=jnp.int32)
    block_expert = jnp.sum(
        (block_start + blocks_e)[None, :] <= blk[:, None], axis=1
    ).astype(jnp.int32)
    block_expert = jnp.clip(block_expert, 0, E - 1)
    if with_used_count:
        n_used = jnp.maximum(1, jnp.sum(blocks_e)).astype(jnp.int32)
        return gather_idx, row_valid, block_expert, n_used
    return gather_idx, row_valid, block_expert


def used_block_count(ids: jax.Array, num_experts: int, block_m: int):
    """Runtime number of ``block_m`` row-blocks that carry any valid rows
    under ``align_tokens_by_expert``'s layout: ``sum_e ceil(count_e / bm)``,
    clamped to ≥1 so downstream dynamic grids are never empty. All blocks at
    or past this index hold only invalid rows — a grouped GEMM bounded by
    it skips up to ``E`` blocks of pure padding (the analog of the
    reference's ``num_tokens_post_padded`` early-exit,
    allgather_group_gemm.py:278-285).

    Standalone form for callers that have no use for the alignment arrays;
    when you need both, pass ``with_used_count=True`` to
    ``align_tokens_by_expert`` instead of paying this one-hot pass twice."""
    E, bm = num_experts, block_m
    ids_safe = jnp.where(ids >= 0, ids, E)
    oh = jax.nn.one_hot(ids_safe, E + 1, dtype=jnp.int32)
    counts = jnp.sum(oh[:, :E], axis=0)
    return jnp.maximum(1, jnp.sum((counts + bm - 1) // bm)).astype(jnp.int32)


# Mosaic's scoped-VMEM stack is 16 MB; the pipelines' double-buffered
# operand tiles get 12 of it (the budget GemmConfig is calibrated to).
_VMEM_TILE_BUDGET = 12 * 1024 * 1024


def fit_block_k(K: int, block_m: int, block_n: int, itemsize: int,
                n_weights: int = 1) -> int | None:
    """The contraction split a grouped GEMM needs at this shape: None when
    the full-K strips fit scoped VMEM (x strip + ``n_weights`` weight
    tiles, double-buffered), else the largest lane-multiple divisor of
    ``K`` whose tiles plus the f32 accumulators do. Full-K is the measured
    best where it fits (K=7168 at (128, 128)); a wide FFN's down
    projection (K=14336 at (128, 512): 37 MB of tiles) does not."""
    per_k = 2 * (block_m + n_weights * block_n) * itemsize
    if K * per_k <= _VMEM_TILE_BUDGET:
        return None
    acc = n_weights * block_m * block_n * 4
    for bk in range(K // 128 * 128, 0, -128):
        if K % bk == 0 and bk * per_k + acc <= _VMEM_TILE_BUDGET:
            return bk
    raise ValueError(
        f"no lane-multiple K-split of K={K} fits scoped VMEM at "
        f"block_m={block_m}, block_n={block_n}")


def _gemm_block(t_blk, w_blk, sc_row, out_dtype):
    """THE grouped-GEMM accumulator body, shared by the bounded and
    unbounded paths: f32 MXU accumulate, optional per-row dequant scale
    fold (``sc_row`` [block_m] f32 or None), cast to ``out_dtype``."""
    acc = jnp.dot(t_blk[...], w_blk[0], preferred_element_type=jnp.float32)
    if sc_row is not None:
        acc = acc * sc_row[:, None]
    return acc.astype(out_dtype)


def _gated_math(g, u, sc_row, out_dtype, activation):
    """THE gated epilogue, shared by every gated path (unbounded, bounded,
    packed, K-split): optional per-row dequant scale folded into BOTH f32
    accumulators (scaling commutes with each matmul, and
    ``act(s·g)·(s·u)`` IS the dequantized math), activation in f32, one
    cast out."""
    if sc_row is not None:
        g = g * sc_row[:, None]
        u = u * sc_row[:, None]
    return (activation(g) * u).astype(out_dtype)


def _gated_block(t_blk, wg_blk, wu_blk, sc_row, out_dtype, activation):
    """Fused gate+up accumulator body: BOTH expert projections of one row
    block against the SAME resident x-tile, activation applied in f32
    before anything leaves VMEM — ``act(x@wg) * (x@wu)`` never stages the
    two [bm, bn] halves in HBM (vs the reference's separate gate/up GEMM
    launches + elementwise pass)."""
    g = jnp.dot(t_blk[...], wg_blk[0], preferred_element_type=jnp.float32)
    u = jnp.dot(t_blk[...], wu_blk[0], preferred_element_type=jnp.float32)
    return _gated_math(g, u, sc_row, out_dtype, activation)


# An expert's run: consecutive row blocks of ONE expert (the aligned layout
# puts them side by side). Cut at this many blocks: past it the run goes on as
# a new run, whose tiles are fetched again.
_RUN_BLOCKS = 4


def fit_run_strips(K: int, block_m: int, block_n: int, x_itemsize: int,
                   w_itemsize: int, n_weights: int = 1) -> tuple[int, int]:
    """(x strips ``_emit_run_walk`` holds in VMEM at once at this shape, the
    blocks it cuts a run at). The strips are what ``_VMEM_TILE_BUDGET`` leaves
    beside the ``n_weights`` double-buffered full-K weight tiles, from 2 (the
    old walk's two strips: wherever ``fit_block_k`` says full K fits, these
    do) to ``2 * _RUN_BLOCKS`` (a whole run resident AND the next one's strips
    under way); a run leaves one strip free for the next run's first. Like
    ``fit_block_k`` this reckons the OPERAND tiles against the budget: the two
    output tiles and the scale rows (a few hundred KB) ride in the 4 MB the
    budget leaves of the scoped stack, and a caller's kernel holds nothing
    else in VMEM (the fused overlap kernels' workspaces are HBM)."""
    left = _VMEM_TILE_BUDGET - 2 * n_weights * K * block_n * w_itemsize
    strips = max(2, min(2 * _RUN_BLOCKS,
                        left // (block_m * K * x_itemsize)))
    return strips, min(_RUN_BLOCKS, strips - 1)


def _emit_run_walk(t_ref, w_refs, sc_ref, o_ref, be_ref, base_blk, m_steps,
                   block_m: int, block_n: int, tile):
    """THE full-K walk of the bounded grouped GEMMs, over HBM refs: for the
    row blocks ``i < m_steps``, ``o[i] = tile(x strip i, the (1, K, block_n)
    tiles of expert be_ref[base_blk + i] in each of w_refs, scale row i)``,
    column tile by column tile.

    The unit is an expert's RUN: up to ``_RUN_BLOCKS`` consecutive row blocks
    that name one expert. A run's x strips are resident in VMEM, each of the
    expert's weight tiles crosses HBM -> VMEM ONCE and meets every strip of
    the run before the next tile replaces it, so a table is read once an
    expert (and cut), not once a row block; where every expert has one block
    this is the walk ``emit_pipeline`` made over ``(row block, column tile)``,
    step for step. Each output block is the same ``jnp.dot`` of the same
    tiles as there: the result is that walk's bit for bit.

    One loop over the runs, all copies the kernel's own: the weight tiles of
    step ``s`` (a run's column tile) ride slot ``s % 2`` and step ``s + 1``'s
    are started before ``s`` computes, across runs too; strip ``i`` rides slot
    ``i % strips`` of a ring, the next run's strips are started as this run's
    are waited for (those whose slots this run holds: behind its last tile);
    an output tile leaves through a ring of two."""
    P, K = t_ref.shape
    N = w_refs[0].shape[2]
    n_w, nj, n_blk = len(w_refs), N // block_n, P // block_m
    strips, cut = fit_run_strips(K, block_m, block_n,
                                 jnp.dtype(t_ref.dtype).itemsize,
                                 jnp.dtype(w_refs[0].dtype).itemsize, n_w)
    m_steps = jnp.minimum(jnp.asarray(m_steps, jnp.int32), n_blk)

    def expert(i):
        return be_ref[base_blk + jnp.minimum(i, n_blk - 1)]

    def run_len(i):
        """Blocks of the run that starts at block ``i`` (0 past the walk)."""
        same, n = i < m_steps, jnp.int32(0)
        for d in range(cut):
            same = same & (i + d < m_steps) & (expert(i + d) == expert(i))
            n = n + same.astype(jnp.int32)
        return n

    def walk(x_buf, w_buf, o_buf, sc_buf, x_sem, w_sem, o_sem, sc_sem):
        def strips_of(first, lo, hi, do):
            """``do`` the copies of strips ``lo .. hi - 1`` of the run that
            starts at block ``first``."""
            def one(l, _):
                blk = first + l
                slot = blk % strips
                do(pltpu.make_async_copy(
                    t_ref.at[pl.ds(pl.multiple_of(blk * block_m, block_m),
                                   block_m)],
                    x_buf.at[slot], x_sem.at[slot]))
                if sc_ref is not None:
                    do(pltpu.make_async_copy(sc_ref.at[pl.ds(blk, 1)],
                                             sc_buf.at[slot],
                                             sc_sem.at[slot]))

            lax.fori_loop(lo, hi, one, None)

        def tiles_of(e, j, slot, do):
            at = pl.ds(pl.multiple_of(j * block_n, block_n), block_n)
            for k, w_ref in enumerate(w_refs):
                do(pltpu.make_async_copy(w_ref.at[pl.ds(e, 1), :, at],
                                         w_buf.at[slot, k],
                                         w_sem.at[slot, k]))

        def out_of(slot, blk, j):
            return pltpu.make_async_copy(
                o_buf.at[slot],
                o_ref.at[pl.ds(pl.multiple_of(blk * block_m, block_m),
                               block_m),
                         pl.ds(pl.multiple_of(j * block_n, block_n),
                               block_n)],
                o_sem.at[slot])

        def run(carry):
            i, n, s, c = carry
            e, nxt = expert(i), i + n
            n2, e2 = run_len(nxt), expert(nxt)
            strips_of(i, 0, n, lambda copy: copy.wait())
            early = jnp.minimum(n2, strips - n)
            strips_of(nxt, 0, early, lambda copy: copy.start())

            def column(j, carry):
                s, c = carry
                slot, last = s % 2, j == nj - 1
                pl.when(jnp.logical_not(last) | (n2 > 0))(
                    lambda: tiles_of(jnp.where(last, e2, e),
                                     jnp.where(last, 0, j + 1), 1 - slot,
                                     lambda copy: copy.start()))
                tiles_of(e, j, slot, lambda copy: copy.wait())

                def block(l, c):
                    xs, os = (i + l) % strips, c % 2
                    pl.when(c >= 2)(lambda: out_of(os, 0, 0).wait())
                    o_buf[os] = tile(
                        x_buf.at[xs], [w_buf.at[slot, k] for k in range(n_w)],
                        None if sc_ref is None else sc_buf[xs][0])
                    out_of(os, i + l, j).start()
                    return c + 1

                return s + 1, lax.fori_loop(0, n, block, c)

            s, c = lax.fori_loop(0, nj, column, (s, c))
            strips_of(nxt, early, n2, lambda copy: copy.start())
            return nxt, n2, s, c

        n0 = run_len(jnp.int32(0))

        @pl.when(n0 > 0)
        def _():
            strips_of(0, 0, n0, lambda copy: copy.start())
            tiles_of(expert(0), 0, 0, lambda copy: copy.start())

        c = lax.while_loop(lambda carry: carry[1] > 0, run,
                           (jnp.int32(0), n0, jnp.int32(0), jnp.int32(0)))[3]
        for back in (1, 2):
            pl.when(c >= back)(
                lambda back=back: out_of((c - back) % 2, 0, 0).wait())

    pl.run_scoped(
        walk,
        pltpu.VMEM((strips, block_m, K), t_ref.dtype),
        pltpu.VMEM((2, n_w, 1, K, block_n), w_refs[0].dtype),
        pltpu.VMEM((2, block_m, block_n), o_ref.dtype),
        pltpu.VMEM((strips, 1, block_m), jnp.float32),
        pltpu.SemaphoreType.DMA((strips,)),
        pltpu.SemaphoreType.DMA((2, n_w)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((strips,)))


def emit_grouped_gemm(t_ref, w_ref, o_ref, be_ref, base_blk,
                      block_m: int, block_n: int, out_dtype=None,
                      n_blocks_used=None, sc_ref=None,
                      block_k: int | None = None, acc_ref=None):
    """In-kernel pipelined grouped GEMM over HBM refs:
    ``o[i*bm:(i+1)*bm] = t[i*bm:(i+1)*bm] @ w[be_ref[base_blk + i]]``.

    ``be_ref`` is an SMEM int32 ref of per-block expert ids (flattened over
    segments; ``base_blk`` offsets into it, may be a traced value). The
    walk (``_emit_run_walk`` at full K) streams each run's expert weight
    tiles HBM→VMEM double-buffered — the in-kernel form of ``grouped_gemm``
    that the fused MoE overlap kernels call per *arrived segment*, the TPU
    analog of the reference's per-token-block ``dl.wait`` + grouped ``tl.dot``
    (kernel_consumer_m_parallel_scatter_group_gemm,
    allgather_group_gemm.py:229-316).

    ``n_blocks_used`` (traced scalar, e.g. ``used_block_count``'s result read
    from SMEM) truncates the row-block grid at runtime: padding blocks past
    it are neither DMA'd nor computed (reference parity:
    ``num_tokens_post_padded`` early-exit, allgather_group_gemm.py:278-285).
    Output rows past ``n_blocks_used * block_m`` are left UNWRITTEN — the
    caller must mask by row validity (``apply_grouped`` and the fused MoE
    unscrambles already do).

    ``sc_ref`` (optional [P // block_m, block_m] f32 ref) folds a per-row
    dequant scale into the accumulator — see ``grouped_gemm.row_scale``.

    ``block_k`` splits the contraction: x strips become (block_m, block_k)
    and weight tiles (block_k, block_n), with the k grid dimension
    innermost accumulating into ``acc_ref`` (caller-allocated
    [block_m, block_n] f32 VMEM scratch — f32 partials, one cast at the
    end). This is what lets block_m/block_n grow past the full-K strip's
    scoped-VMEM cliff (a (256, 7168) bf16 x strip alone double-buffers to
    ~7 MB; measured OOM at 17.6 MB round 5)."""
    import math

    P, H = t_ref.shape
    E, H2, N = w_ref.shape
    assert H == H2, (H, H2)
    block_n = math.gcd(min(block_n, N), N)
    assert P % block_m == 0, (P, block_m)
    out_dtype = out_dtype or o_ref.dtype
    m_steps = (P // block_m if n_blocks_used is None
               else jnp.minimum(n_blocks_used, P // block_m))
    if block_k is None or block_k >= H:
        _emit_run_walk(
            t_ref, [w_ref], sc_ref, o_ref, be_ref, base_blk, m_steps,
            block_m, block_n, lambda t_blk, w_blks, sc_row: _gemm_block(
                t_blk, w_blks[0], sc_row, out_dtype))
        return

    # the K-split form keeps the (row block, column tile, k) pipeline: a
    # shape that needs it has no room for a run's strips (``fit_block_k``)
    assert H % block_k == 0, (H, block_k)
    assert acc_ref is not None, "block_k needs an f32 VMEM acc_ref"
    nk = H // block_k

    def body_acc(t_blk, w_blk, *rest):
        o_blk = rest[-1]
        sc_row = rest[0][0] if sc_ref is not None else None
        k = pl.program_id(2)
        part = jnp.dot(t_blk[...], w_blk[0],
                       preferred_element_type=jnp.float32)

        @pl.when(k == 0)
        def _():
            acc_ref[...] = part

        @pl.when(k > 0)
        def _():
            acc_ref[...] = acc_ref[...] + part

        @pl.when(k == nk - 1)
        def _():
            acc = acc_ref[...]
            if sc_row is not None:
                acc = acc * sc_row[:, None]
            o_blk[...] = acc.astype(out_dtype)

    pltpu.emit_pipeline(
        body_acc,
        grid=(m_steps, N // block_n, nk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((1, block_k, block_n),
                         lambda i, j, k: (be_ref[base_blk + i], k, j)),
        ] + ([pl.BlockSpec((1, block_m), lambda i, j, k: (i, 0))]
             if sc_ref is not None else []),
        out_specs=[pl.BlockSpec((block_m, block_n),
                                lambda i, j, k: (i, j))],
    )(t_ref, w_ref, *((sc_ref,) if sc_ref is not None else ()), o_ref)


def grouped_gemm(tokens: jax.Array, weights: jax.Array,
                 block_expert: jax.Array, block_m: int = 128,
                 block_n: int = 128, out_dtype=None,
                 n_blocks_used: jax.Array | None = None,
                 row_scale: jax.Array | None = None,
                 masked: bool = True,
                 block_k: int | None = None) -> jax.Array:
    """``out[i*bm:(i+1)*bm] = tokens[i*bm:(i+1)*bm] @ weights[block_expert[i]]``.

    tokens: [P, H] (expert-aligned rows), weights: [E, H, N],
    block_expert: [P // block_m] int32. The scalar-prefetch index_map streams
    each block's expert weight tile HBM→VMEM double-buffered (grid analog of
    the reference's ``kernel_consumer_m_parallel_scatter_group_gemm``,
    allgather_group_gemm.py:229-316).

    ``n_blocks_used`` (traced int32 scalar from ``used_block_count``)
    truncates the row-block walk at runtime, skipping the up-to-``E`` blocks
    of pure per-expert padding in the aligned layout. That bounded walk goes
    by an expert's RUNS of row blocks at full K (``_emit_run_walk``: a weight
    tile in VMEM meets every block of the run, so a table crosses HBM once an
    expert whatever its rows) and keeps the (row block, column tile, k)
    pipeline where ``block_k`` splits K: the one rule, here and in
    ``grouped_gemm_gated`` (whose ``packed`` and convert-once forms keep the
    pipeline too). Rows past the bound
    are returned ZEROED (callers mask by row validity anyway; zero keeps the
    op total-function for reuse in autodiff contexts). ``masked=False``
    skips that zeroing pass (a full read+write of the output) and leaves
    rows past the bound UNDEFINED — for callers whose scatter-back already
    drops invalid rows by index (``apply_grouped``'s out-of-range ``src``
    with ``mode="drop"`` never reads them).

    ``row_scale`` ([P] f32) folds a per-row dequantization scale into the
    f32 accumulator: ``out_row = scale · (q_row @ w)``. Per-row scaling
    commutes with the matmul, so quantized-wire tokens (fp8/int8 rows from
    an EP dispatch with ``dequant_edge="expert"``) feed the MXU directly —
    no standalone dequant pass, halved token-read bytes, and the scale is
    applied once in f32 exactly like the reference's expert GEMM consumes
    its scale side-channel (README.md:55 fp8 protocol)."""
    import math

    P, H = tokens.shape
    E, H2, N = weights.shape
    assert H == H2, (H, H2)
    # ragged N (e.g. a 192-wide TP shard): fall back to the largest common
    # divisor, like flash_decode's block_s handling
    block_n = math.gcd(min(block_n, N), N)
    assert P % block_m == 0, (P, block_m)
    # quantized rows can't default the output to their own (wire) dtype —
    # follow the weights' compute dtype instead (bf16 weights → bf16 out,
    # f32 pipeline → f32 out)
    out_dtype = out_dtype or (tokens.dtype if row_scale is None
                              else weights.dtype)
    sc2d = (None if row_scale is None
            else row_scale.astype(jnp.float32).reshape(P // block_m,
                                                       block_m))
    n_sc = 0 if sc2d is None else 1

    if n_blocks_used is None:
        assert block_k is None or block_k >= H, (
            "block_k (K-split) is implemented on the runtime-bounded path "
            "only — pass n_blocks_used (the serving path always does)")

        def kernel(be_ref, *refs):
            o_ref = refs[-1]
            t_ref, w_ref = refs[:2]
            sc_row = refs[2][0] if n_sc else None
            o_ref[...] = _gemm_block(t_ref, w_ref, sc_row, out_dtype)

        grid = (P // block_m, N // block_n)
        sc_specs = ([pl.BlockSpec((1, block_m), lambda i, j, be: (i, 0))]
                    if n_sc else [])
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=[
                    pl.BlockSpec((block_m, H), lambda i, j, be: (i, 0)),
                    pl.BlockSpec((1, H, block_n),
                                 lambda i, j, be: (be[i], 0, j)),
                ] + sc_specs,
                out_specs=pl.BlockSpec((block_m, block_n),
                                       lambda i, j, be: (i, j)),
            ),
            out_shape=jax.ShapeDtypeStruct((P, N), out_dtype),
            cost_estimate=pl.CostEstimate(
                flops=2 * P * H * N,
                bytes_accessed=(P * H + E * H * N + P * N)
                * jnp.dtype(tokens.dtype).itemsize,
                transcendentals=0),
            name="grouped_gemm",
            interpret=default_interpret(),
        )(block_expert, tokens, weights, *(() if sc2d is None else (sc2d,)))

    # runtime-bounded path: zero-init the output, then emit_pipeline over a
    # dynamic grid — padding blocks cost neither DMA nor MXU work
    # block_n was gcd-clamped above — safe for the scratch shape directly
    nb = jnp.asarray(n_blocks_used, jnp.int32).reshape(1)
    ksplit = block_k is not None and block_k < H

    def kernel(be_ref, nb_ref, *refs):
        o_ref = refs[-1] if not ksplit else refs[-2]
        acc = refs[-1] if ksplit else None
        t_ref, w_ref = refs[:2]
        sc_ref = refs[2] if n_sc else None
        emit_grouped_gemm(t_ref, w_ref, o_ref, be_ref, 0, block_m, block_n,
                          out_dtype, n_blocks_used=nb_ref[0],
                          sc_ref=sc_ref, block_k=block_k, acc_ref=acc)

    out = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * n_sc,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=([pltpu.VMEM((block_m, block_n), jnp.float32)]
                        if ksplit else []),
        out_shape=jax.ShapeDtypeStruct((P, N), out_dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * P * H * N,
            bytes_accessed=(P * H + E * H * N + P * N)
            * jnp.dtype(tokens.dtype).itemsize,
            transcendentals=0),
        name="grouped_gemm",
        interpret=default_interpret(),
    )(block_expert, nb, tokens, weights,
      *(() if sc2d is None else (sc2d,)))
    if not masked:
        return out
    # rows past the bound were never written; zero them so the result is a
    # total function of the inputs
    row_blk = jnp.arange(P, dtype=jnp.int32) // block_m
    return jnp.where((row_blk < nb[0])[:, None], out,
                     jnp.zeros((), out_dtype))


def pack_gated_weights(w_gate: jax.Array, w_up: jax.Array,
                       block_n: int = 128) -> PackedGatedWeights:
    """Interleave gate and up weights into ONE [E, H, 2F] array whose
    column groups alternate [g_j ‖ u_j] per ``block_n``-wide tile — the
    layout ``grouped_gemm_gated(packed=True)`` consumes. Two separate
    weight streams (one DMA sequence per projection) measured ~545 GB/s
    on v5e vs the dense GEMM's ~740; packing merges them into one
    double-width tile stream. Pack ONCE at weight-load time (serving
    weights are static).

    Returns a ``PackedGatedWeights`` wrapper carrying ``block_n`` so the
    consumer can verify the pack width instead of trusting the caller to
    thread the same value to both sides."""
    E, H, F = w_gate.shape
    assert w_up.shape == (E, H, F), (w_up.shape, w_gate.shape)
    # STRICT: no silent re-tiling — the interleave is invisible in the
    # shape, so the pack width must be carried alongside the array (the
    # wrapper) and re-checked by the consumer
    assert F % block_n == 0, (
        f"pack_gated_weights: block_n={block_n} must divide F={F} exactly "
        "(and must equal the block_n passed to grouped_gemm_gated)")
    bn = block_n
    g = w_gate.reshape(E, H, F // bn, 1, bn)
    u = w_up.reshape(E, H, F // bn, 1, bn)
    return PackedGatedWeights(
        jnp.concatenate([g, u], axis=3).reshape(E, H, 2 * F), block_n)


def grouped_gemm_gated(tokens: jax.Array, w_gate: jax.Array,
                       w_up: jax.Array | None, block_expert: jax.Array,
                       block_m: int = 128, block_n: int = 128,
                       out_dtype=None,
                       n_blocks_used: jax.Array | None = None,
                       row_scale: jax.Array | None = None,
                       activation=jax.nn.silu,
                       masked: bool = True,
                       block_k: int | None = None,
                       packed: bool = False,
                       prefetch_depth: int = 2) -> jax.Array:
    """Fused gated grouped GEMM: ``out = act(x @ wg[e]) * (x @ wu[e])`` per
    expert-aligned row block — the gate and up projections of the MoE FFN in
    ONE kernel. Each x-tile is read from HBM once and contracted against
    both experts' weight tiles while resident in VMEM; the activation and
    elementwise product happen on the f32 accumulators before the result is
    cast — no intermediate gate/up arrays in HBM, no separate activation
    pass, one kernel launch instead of two (the reference runs gate and up
    as separate grouped GEMM launches plus an elementwise kernel,
    test_ep_moe_inference.py FFN; this fusion is the TPU-shaped cut).

    Signature follows ``grouped_gemm``: w_gate/w_up [E, H, F]; ``row_scale``
    folds a per-row wire-dequant scale into BOTH accumulators (scaling
    commutes with each matmul, and ``act(s·g)·(s·u)`` IS the dequantized
    math); ``n_blocks_used`` bounds the row-block walk at runtime;
    ``masked=False`` leaves rows past the bound undefined (see
    ``grouped_gemm``).

    ``packed=True``: ``w_gate`` is the ``PackedGatedWeights`` wrapper from
    ``pack_gated_weights(..., block_n)`` (``w_up`` must be None) — gate
    and up tiles ride ONE double-width DMA stream instead of two
    interleaved sequences (the measured ~545 GB/s two-stream rate vs the
    dense GEMM's ~740 is the gap this targets). Bounded path only; the
    wrapper's pack width is VERIFIED against ``block_n`` (a bare [E, H,
    2F] array is still accepted for internal callers, where divisibility
    is the only possible check).

    ``prefetch_depth`` (packed path): number of weight tiles kept in
    flight by the kernel's own multi-buffered DMA stream. Depth ≥ 2
    replaces the emit_pipeline weight stream with explicit
    ``make_async_copy`` lookahead that crosses expert-block boundaries
    without re-priming (the grouped dynamic-expert index_map is what
    keeps the generic pipeline's prefetch shallow — measured ~545 GB/s vs
    the dense GEMM's ~740). Depth is clamped to the VMEM budget; 1 (or a
    non-packed layout) falls back to the emit_pipeline stream."""
    import math

    P, H = tokens.shape
    if packed:
        assert w_up is None, "packed layout carries gate AND up in w_gate"
        assert n_blocks_used is not None, (
            "packed gated GEMM is implemented on the bounded path only")
        if isinstance(w_gate, PackedGatedWeights):
            assert w_gate.block_n == block_n, (
                f"PackedGatedWeights packed with block_n={w_gate.block_n} "
                f"but the kernel was asked for block_n={block_n} — the "
                "interleave would silently mix gate and up columns")
            w_gate = w_gate.w
        E, H2, F2 = w_gate.shape
        assert F2 % 2 == 0, F2
        F = F2 // 2
        assert F % block_n == 0, (
            f"block_n={block_n} must divide F={F}")
        # Divisibility is necessary but NOT sufficient for a bare array —
        # prefer passing the PackedGatedWeights wrapper, which carries
        # the actual pack width and is verified above.
    else:
        E, H2, F = w_gate.shape
        assert w_up.shape == (E, H2, F), (w_up.shape, w_gate.shape)
        block_n = math.gcd(min(block_n, F), F)
    assert H == H2, (H, H2)
    assert P % block_m == 0, (P, block_m)
    out_dtype = out_dtype or (tokens.dtype if row_scale is None
                              else w_gate.dtype)
    sc2d = (None if row_scale is None
            else row_scale.astype(jnp.float32).reshape(P // block_m,
                                                       block_m))
    n_sc = 0 if sc2d is None else 1
    cost = pl.CostEstimate(
        flops=4 * P * H * F,
        bytes_accessed=(P * H + 2 * E * H * F + P * F)
        * jnp.dtype(tokens.dtype).itemsize,
        transcendentals=P * F)

    if n_blocks_used is None:
        assert block_k is None or block_k >= H, (
            "block_k (K-split) is implemented on the runtime-bounded path "
            "only — pass n_blocks_used (the serving path always does)")
        def kernel(be_ref, *refs):
            o_ref = refs[-1]
            t_ref, wg_ref, wu_ref = refs[:3]
            sc_row = refs[3][0] if n_sc else None
            o_ref[...] = _gated_block(t_ref, wg_ref, wu_ref, sc_row,
                                      out_dtype, activation)

        sc_specs = ([pl.BlockSpec((1, block_m), lambda i, j, be: (i, 0))]
                    if n_sc else [])
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(P // block_m, F // block_n),
                in_specs=[
                    pl.BlockSpec((block_m, H), lambda i, j, be: (i, 0)),
                    pl.BlockSpec((1, H, block_n),
                                 lambda i, j, be: (be[i], 0, j)),
                    pl.BlockSpec((1, H, block_n),
                                 lambda i, j, be: (be[i], 0, j)),
                ] + sc_specs,
                out_specs=pl.BlockSpec((block_m, block_n),
                                       lambda i, j, be: (i, j)),
            ),
            out_shape=jax.ShapeDtypeStruct((P, F), out_dtype),
            cost_estimate=cost,
            name="grouped_gemm_gated",
            interpret=default_interpret(),
        )(block_expert, tokens, w_gate, w_up,
          *(() if sc2d is None else (sc2d,)))

    nb = jnp.asarray(n_blocks_used, jnp.int32).reshape(1)
    ksplit = block_k is not None and block_k < H
    if ksplit:
        assert H % block_k == 0, (H, block_k)
    # quantized-wire x (fp8/int8 vs bf16 weights): Mosaic re-converts the
    # x tile before the MXU once per (m, n[, k]) step, re-paying the VPU
    # convert F/block_n times per strip (the measured cost that cancelled
    # the halved read bytes, docs/benchmarks.md expert-edge table).
    # Convert ONCE per m-step into a compute-dtype VMEM scratch at the
    # first n-step and feed the MXU from it.
    convert_once = (n_sc == 1
                    and jnp.dtype(tokens.dtype).itemsize
                    < jnp.dtype(w_gate.dtype).itemsize
                    and F // block_n > 1)
    cdtype = w_gate.dtype
    n_w = 1 if packed else 2
    # Deep weight-stream prefetch (packed layout only): keep ``depth``
    # double-width weight tiles in flight via an explicit DMA ring instead
    # of emit_pipeline's single-step lookahead. The ring is clamped so it
    # plus the pipelined x strips stays under the scoped-VMEM budget; if
    # even 2 tiles don't fit, fall back to the emit_pipeline stream.
    bk_w = block_k if ksplit else H
    _w_tile_bytes = bk_w * 2 * block_n * jnp.dtype(w_gate.dtype).itemsize
    deep_depth = 0
    if packed and prefetch_depth is not None and prefetch_depth >= 2:
        _budget = 9 * 1024 * 1024
        deep_depth = min(int(prefetch_depth), _budget // _w_tile_bytes)
    deep = deep_depth >= 2
    if not deep:
        deep_depth = 0

    def split_w(w_blks):
        """(gate tile, up tile) from the weight block(s) — packed layout
        splits the double-width tile's columns."""
        if packed:
            w = w_blks[0][0]
            return w[:, :block_n], w[:, block_n:]
        return w_blks[0][0], w_blks[1][0]

    def kernel(be_ref, nb_ref, *refs):
        n_scr = ((1 if convert_once else 0) + (2 if ksplit else 0)
                 + (2 if deep else 0))
        scratch = refs[len(refs) - n_scr:] if n_scr else ()
        refs = refs[:len(refs) - n_scr]
        xcv = scratch[0] if convert_once else None
        w_buf, w_sem = (scratch[-2], scratch[-1]) if deep else (None, None)
        if ksplit:
            acc_g, acc_u = ((scratch[-4], scratch[-3]) if deep
                            else (scratch[-2], scratch[-1]))
        else:
            acc_g = acc_u = None
        o_ref = refs[-1]
        t_ref = refs[0]
        w_refs = refs[1:1 + n_w]
        sc_ref = refs[1 + n_w] if n_sc else None
        m_steps = jnp.minimum(nb_ref[0], P // block_m)
        sc_args = (sc_ref,) if sc_ref is not None else ()

        # --- deep mode: explicit multi-buffered weight DMA ring.
        # Flat step s walks the SAME (m, n[, k]) order as the pipeline
        # grid; the copy for step s+depth-1 is issued at the TOP of step
        # s (the guide's double-buffer shape generalized to depth): the
        # slot it overwrites was last read at step s-1, already consumed.
        # The dynamic-expert lookup ``be_ref[i]`` happens at ISSUE time,
        # so the ring keeps streaming across expert-block boundaries —
        # the re-priming that capped the two-stream rate at ~545 GB/s.
        nn_steps = F // block_n
        nk_steps = (H // block_k) if ksplit else 1

        def w_dma(s):
            i = s // (nn_steps * nk_steps)
            r = s % (nn_steps * nk_steps)
            j = r // nk_steps
            kk = r % nk_steps
            slot = s % deep_depth
            src = w_refs[0].at[be_ref[i], pl.ds(kk * bk_w, bk_w),
                               pl.ds(j * 2 * block_n, 2 * block_n)]
            return pltpu.make_async_copy(src, w_buf.at[slot],
                                         w_sem.at[slot])

        def w_stream(s, n_steps):
            """Warm the ring at step 0, issue the lookahead copy, wait
            for this step's tile; returns the resident (bk_w, 2bn)
            tile."""
            @pl.when(s == 0)
            def _():
                for d in range(deep_depth - 1):
                    @pl.when(d < n_steps)
                    def _(d=d):
                        w_dma(d).start()

            @pl.when(s + deep_depth - 1 < n_steps)
            def _():
                w_dma(s + deep_depth - 1).start()

            w_dma(s).wait()
            return w_buf[s % deep_depth]

        if ksplit:
            nk = H // block_k
            n_wp = 0 if deep else n_w

            def body_acc(t_blk, *rest):
                o_blk = rest[-1]
                w_blks = rest[:n_wp]
                sc_row = rest[n_wp][0] if sc_ref is not None else None
                k = pl.program_id(2)
                if convert_once:
                    j = pl.program_id(1)

                    @pl.when(j == 0)
                    def _():
                        xcv[k, :, :] = t_blk[...].astype(cdtype)

                    x_use = xcv[k, :, :]
                else:
                    x_use = t_blk[...]
                if deep:
                    i = pl.program_id(0)
                    j2 = pl.program_id(1)
                    s = (i * nn_steps + j2) * nk_steps + k
                    wtile = w_stream(s, m_steps * nn_steps * nk_steps)
                    wg_t, wu_t = wtile[:, :block_n], wtile[:, block_n:]
                else:
                    wg_t, wu_t = split_w(w_blks)
                g = jnp.dot(x_use, wg_t,
                            preferred_element_type=jnp.float32)
                u = jnp.dot(x_use, wu_t,
                            preferred_element_type=jnp.float32)

                @pl.when(k == 0)
                def _():
                    acc_g[...] = g
                    acc_u[...] = u

                @pl.when(k > 0)
                def _():
                    acc_g[...] = acc_g[...] + g
                    acc_u[...] = acc_u[...] + u

                @pl.when(k == nk - 1)
                def _():
                    o_blk[...] = _gated_math(acc_g[...], acc_u[...],
                                             sc_row, out_dtype, activation)

            sc_specs = ([pl.BlockSpec((1, block_m),
                                      lambda i, j, k: (i, 0))]
                        if sc_ref is not None else [])
            w_specs = ([] if deep else
                       ([pl.BlockSpec((1, block_k, 2 * block_n),
                                      lambda i, j, k: (be_ref[i], k, j))]
                        if packed else
                        [pl.BlockSpec((1, block_k, block_n),
                                      lambda i, j, k: (be_ref[i], k, j))]
                        * 2))
            pltpu.emit_pipeline(
                body_acc,
                grid=(m_steps, F // block_n, nk),
                in_specs=[
                    pl.BlockSpec((block_m, block_k),
                                 lambda i, j, k: (i, k)),
                ] + w_specs + sc_specs,
                out_specs=[pl.BlockSpec((block_m, block_n),
                                        lambda i, j, k: (i, j))],
            )(t_ref, *(() if deep else tuple(w_refs)), *sc_args, o_ref)
            return

        if not (packed or convert_once):
            _emit_run_walk(
                t_ref, w_refs, sc_ref, o_ref, be_ref, 0, m_steps, block_m,
                block_n, lambda t_blk, w_blks, sc_row: _gated_block(
                    t_blk, *w_blks, sc_row, out_dtype, activation))
            return

        n_wp = 0 if deep else n_w

        def body(t_blk, *rest):
            o_blk = rest[-1]
            w_blks = rest[:n_wp]
            sc_row = rest[n_wp][0] if sc_ref is not None else None
            if convert_once:
                j = pl.program_id(1)

                @pl.when(j == 0)
                def _():
                    xcv[...] = t_blk[...].astype(cdtype)

                x_use = xcv[...]
            else:
                x_use = t_blk[...]
            if deep:
                i = pl.program_id(0)
                j2 = pl.program_id(1)
                s = i * nn_steps + j2
                wtile = w_stream(s, m_steps * nn_steps)
                wg_t, wu_t = wtile[:, :block_n], wtile[:, block_n:]
            else:
                wg_t, wu_t = split_w(w_blks)
            g = jnp.dot(x_use, wg_t, preferred_element_type=jnp.float32)
            u = jnp.dot(x_use, wu_t, preferred_element_type=jnp.float32)
            o_blk[...] = _gated_math(g, u, sc_row, out_dtype, activation)

        sc_specs = ([pl.BlockSpec((1, block_m), lambda i, j: (i, 0))]
                    if sc_ref is not None else [])
        w_specs = ([] if deep else
                   ([pl.BlockSpec((1, H, 2 * block_n),
                                  lambda i, j: (be_ref[i], 0, j))]
                    if packed else
                    [pl.BlockSpec((1, H, block_n),
                                  lambda i, j: (be_ref[i], 0, j))] * 2))
        pltpu.emit_pipeline(
            body,
            grid=(m_steps, F // block_n),
            in_specs=[
                pl.BlockSpec((block_m, H), lambda i, j: (i, 0)),
            ] + w_specs + sc_specs,
            out_specs=[pl.BlockSpec((block_m, block_n),
                                    lambda i, j: (i, j))],
        )(t_ref, *(() if deep else tuple(w_refs)), *sc_args, o_ref)

    w_args = (w_gate,) if packed else (w_gate, w_up)
    out = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * n_w
        + [pl.BlockSpec(memory_space=pl.ANY)] * n_sc,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=(
            ([pltpu.VMEM(((H // block_k, block_m, block_k) if ksplit
                          else (block_m, H)), cdtype)]
             if convert_once else [])
            + ([pltpu.VMEM((block_m, block_n), jnp.float32)] * 2
               if ksplit else [])
            + ([pltpu.VMEM((deep_depth, bk_w, 2 * block_n), w_gate.dtype),
                pltpu.SemaphoreType.DMA((deep_depth,))]
               if deep else [])),
        out_shape=jax.ShapeDtypeStruct((P, F), out_dtype),
        cost_estimate=cost,
        name="grouped_gemm_gated",
        interpret=default_interpret(),
    )(block_expert, nb, tokens, *w_args,
      *(() if sc2d is None else (sc2d,)))
    if not masked:
        return out
    row_blk = jnp.arange(P, dtype=jnp.int32) // block_m
    return jnp.where((row_blk < nb[0])[:, None], out,
                     jnp.zeros((), out_dtype))


def apply_grouped(tokens: jax.Array, ids: jax.Array, num_experts: int, fn,
                  block_m: int = 128,
                  row_scale: jax.Array | None = None,
                  gather_dtype=None) -> jax.Array:
    """The shared align→gather→mask→compute→scatter-back sequence every MoE
    op needs: align rows by expert, call ``fn(x_aligned, block_expert,
    n_blocks_used) -> y_aligned`` (one or more grouped GEMMs sharing the
    alignment, runtime-bounded by the used-block count), and scatter results
    back to the original row order (invalid ids → zero rows). Returns
    [T, N].

    ``row_scale`` ([T] f32, quantized-wire rows): gathered through the same
    alignment and passed to ``fn(x, block_expert, nb, scale_aligned)`` so
    the grouped GEMMs can fold the dequant into their accumulators
    (``grouped_gemm.row_scale``); ``tokens`` then stay in the wire dtype
    end to end.

    ``gather_dtype``: cast the gathered rows inside the (fused) gather
    pass — the free place to leave a wire dtype the downstream kernels
    cannot consume (measured round 5: Mosaic rejects fp8 x-strips in the
    grouped pipelines on this toolchain; int8 compiles). The scale
    contract is unchanged — dequant still rides the accumulators."""
    T = tokens.shape[0]
    gather_idx, row_valid, block_expert, nb = align_tokens_by_expert(
        ids, num_experts, block_m, with_used_count=True)
    P_rows = gather_idx.shape[0]
    vmask = row_valid[:, None]
    x = jnp.where(vmask, tokens[gather_idx], 0).astype(gather_dtype
                                                       or tokens.dtype)
    if row_scale is not None:
        s = jnp.where(row_valid, row_scale.astype(jnp.float32)[gather_idx],
                      1.0)
        y = fn(x, block_expert, nb, s)
    else:
        y = fn(x, block_expert, nb)
    # Scatter-back is a GATHER by the inverse permutation: each source row
    # lands in at most one aligned slot, so ``out[t] = y[dest_row[t]]``
    # with out-of-range fill for unrouted rows. The scatter-add spelling
    # (`out.at[src].add`) measured 1.5 ms at the DeepSeek serving shape —
    # TPU scatter serializes; the inverse gather is a plain take. The
    # tiny int scatter building dest_row ([P] int32) is noise.
    dest_row = jnp.full((T,), P_rows, jnp.int32).at[
        jnp.where(row_valid, gather_idx, T)].set(
        jnp.arange(P_rows, dtype=jnp.int32), mode="drop")
    return jnp.take(y, dest_row, axis=0, mode="fill", fill_value=0)


def moe_ffn_local(tokens: jax.Array, ids: jax.Array, w_up: jax.Array,
                  w_down: jax.Array, block_m: int = 128,
                  activation=jax.nn.silu) -> jax.Array:
    """Per-device MoE FFN over locally-present tokens: grouped up-projection,
    activation, grouped down-projection, rows restored to their original
    positions. ``ids`` may contain -1 for padding rows (they produce zeros).
    Building block for the EP layer and the MoE overlap ops."""
    E = w_up.shape[0]

    def ffn(x, block_expert, nb):
        h = grouped_gemm(x, w_up, block_expert, block_m=block_m,
                         n_blocks_used=nb)
        h = activation(h.astype(jnp.float32)).astype(tokens.dtype)
        return grouped_gemm(h, w_down, block_expert, block_m=block_m,
                            n_blocks_used=nb)

    return apply_grouped(tokens, ids, E, ffn, block_m=block_m)


__all__ = ["align_tokens_by_expert", "used_block_count", "emit_grouped_gemm",
           "grouped_gemm", "grouped_gemm_gated", "pack_gated_weights",
           "PackedGatedWeights", "apply_grouped", "moe_ffn_local"]
