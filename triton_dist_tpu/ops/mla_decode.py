"""Latent (MLA) paged attention, absorbed form: the decode kernel over a
cache that holds ONE row a token a layer, ``[c | k_rope | pad]``.

Multi-head latent attention caches the compressed key-value row ``c``
(``kv_lora_rank`` values) and the one shared rotary key ``k_rope`` instead
of per-head keys and values. With the key up-projection absorbed into the
query (``q' = q_nope @ W_uk^T``) a head's score against a cached token is
``q' . c + q_rope . k_rope``: one dot product of ``[q' | q_rope]`` with the
stored row. The value is ``c`` itself (the value up-projection is applied
to the attention output afterwards), so ONE read of a page serves keys and
values: the value operand of the kernel is the first ``latent_dim`` columns
of the same VMEM block.

ONE walk of the stacked pool, read in place (``layer`` is a scalar-prefetch
operand): a loop inside the kernel over the live pages alone, the pattern of
``ops.flash_decode.gqa_decode_paged`` since ISSUE 29, a GROUP of pages a turn
(ISSUE 31 for the decode rows, ISSUE 34 for a prefill chunk's). Consecutive
live pages of one table row are fetched by hand into adjacent slices of one
VMEM operand and meet their query rows in ONE online-softmax update, the next
groups' copies in flight behind it: what a one-page update pays beside its
two matrix products (the accumulator loaded, rescaled and stored, two
cross-lane reductions, a chain the next update waits for) is paid once a
group. A dead page is no step, no index map and no byte; an idle row costs
nothing. The walk's block shape follows what the caller says of the tables:

- DECODE rows have a block table each: a row's 64 heads are the update's
  query operand, ``DECODE_PAGES_PER_GROUP`` pages its keys.
- A prefill CHUNK's rows all belong to one sequence: ``rows_per_block``
  consecutive rows share the block table of the block's first row (the
  caller's promise) and differ only in ``kv_len``, which masks per row. A row
  block x its heads are one [1024, 640] operand at the published widths, a
  page leaves HBM once a row block and not once a row, and the walk ends at
  the block's own last position (causality: later rows' pages, and everything
  past the prompt, are no steps). Here an update's time grows with its keys,
  so a block's short last group is attended at its own size.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.utils import default_interpret

NEG_INF = -1e30


def _softmax_update(q, kv, start, kv_len, acc, m_i, l_i, *, latent_dim: int,
                    sm_scale: float):
    """One online-softmax update of ``q`` [M, W] against the cached rows
    ``kv`` [T, W], whose first sits at position ``start``: keys at ``kv_len``
    (a scalar, or [M, 1] a row) and beyond are masked; the value operand is
    ``kv``'s first ``latent_dim`` columns."""
    scores = lax.dot_general(
        q, kv, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    pos = start + lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(pos < kv_len, scores, NEG_INF)
    m_new = jnp.maximum(m_i[...], jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_i[...] - m_new)
    p = jnp.exp(scores - m_new)                             # [M, T]
    l_i[...] = l_i[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    pv = jnp.dot(p.astype(kv.dtype), kv[:, :latent_dim],
                 preferred_element_type=jnp.float32)
    acc[...] = acc[...] * alpha + pv
    m_i[...] = m_new


def _softmax_init(acc, m_i, l_i):
    acc[...] = jnp.zeros_like(acc)
    m_i[...] = jnp.full_like(m_i, NEG_INF)
    l_i[...] = jnp.zeros_like(l_i)


def _softmax_finish(acc, l_i):
    return acc[...] / jnp.where(l_i[...] > 0, l_i[...], 1.0)


def _mla_loop_kernel(kl_ref, bt_ref, layer_ref, q_ref, pool_hbm, out_ref,
                     buf, sem, acc, m_i, l_i, *, rows: int, group: int,
                     n_pool: int, page_size: int, latent_dim: int,
                     sm_scale: float):
    """Grid (blocks of walks,) over a latent pool left in HBM. A WALK is
    ``rows`` consecutive rows of the batch that share one table row,
    ``bt_ref[walk]``, and meet its pages as one [rows x H, W] operand: a
    decode row (``rows`` 1) or a row block of a prefill chunk. ``kl_ref``
    holds every row's ``kv_len``; a walk reaches as far as the largest of its
    rows', and each row masks for itself. ONE loop over the block's live pages
    alone, walks in order and a walk's pages in order, ``group`` pages a turn.
    ``q_ref`` / ``out_ref`` hold the block's walks [walks, rows x H, .];
    ``buf`` [ring, group * page_size, W] is a ring of group operands, ``sem``
    [ring, group] a DMA semaphore a page of it.

    A turn fetches the group's LIVE pages (``bt_ref[walk, page]`` of layer
    ``layer_ref[0]``, straight out of the stacked pool) into adjacent
    [page_size, W] slices of one ring entry, the groups after it already in
    flight into the others, and makes one online-softmax update of the walk's
    rows x heads against the entry. A walk's last group may hold fewer live
    pages than ``group``: the others are not fetched. A decode row (64 query
    rows: the update costs its chain, whatever its keys) still attends the
    whole entry: the missing pages' keys are masked (``pos < kv_len``) to
    weight 0, and the rows they leave in the entry are those of an earlier
    group or the zeros THE RING IS FILLED WITH ONCE A CALL, finite either way
    (0 x inf in the value product would be NaN). A row block (1,024 query
    rows: the update costs the MXU's time for its keys) attends the live
    pages' slices alone, one branch a size: a key masked to weight 0 adds
    exactly 0, so the result is the whole entry's. A walk none of whose rows
    is live and a page past its reach are not steps at all; a table entry
    past a walk's live pages is never read and a live one is clamped into the
    pool. A row with ``kv_len`` 0 inside a live walk (a chunk's padding)
    weighs every key the walk attended alike: a finite mean nobody reads."""
    walks, pages_per_seq = q_ref.shape[0], bt_ref.shape[1]
    walk0 = pl.program_id(0) * walks
    end = walk0 + walks
    layer = layer_ref[0]
    depth = buf.shape[0]

    def reach(walk):
        """The furthest any row of the walk attends (a loop: it is traced at
        every use, inside the engine's set-up time)."""
        if rows == 1:
            return kl_ref[walk]
        return lax.fori_loop(
            0, rows, lambda r, n: jnp.maximum(n, kl_ref[walk * rows + r]), 0)

    def live_pages(walk):
        # a key past the table's last page does not exist, whatever kv_len says
        return jnp.minimum((reach(walk) + page_size - 1) // page_size,
                           pages_per_seq)

    def next_live(walk):
        return lax.while_loop(
            lambda w: (w < end) & (reach(jnp.minimum(w, end - 1)) <= 0),
            lambda w: w + 1, walk)

    def after(walk, g):
        """The group that follows (walk, g); walk ``end`` when none is left."""
        n = live_pages(jnp.minimum(walk, end - 1))
        return lax.cond((g + 1) * group >= n,
                        lambda: (next_live(walk + 1), 0),
                        lambda: (walk, g + 1))

    def pages_from(walk, first):
        """The live pages of the walk's group that starts at page ``first``:
        ``group``, or a last group's few."""
        return jnp.minimum(live_pages(walk) - first, group)

    def each_live_page(walk, g, slot, do):
        """``do`` the copy of every live page of group (walk, g)."""
        first = g * group

        def one(j, _):
            page = jnp.clip(bt_ref[walk, first + j], 0, n_pool - 1)
            at = pl.multiple_of(j * page_size, page_size)
            do(pltpu.make_async_copy(pool_hbm.at[layer, page],
                                     buf.at[slot, pl.ds(at, page_size)],
                                     sem.at[slot, j]))

        # a loop, not ``group`` conditionals: the trace and Mosaic's compile
        # are inside the serving engine's set-up time
        lax.fori_loop(0, pages_from(walk, first), one, None)

    def start(walk, g, slot):
        pl.when(walk < end)(lambda: each_live_page(
            walk, g, slot, lambda copy: copy.start()))

    # what no group writes: an idle walk's zeros, and (once a call: scratch
    # outlives a grid step) the ring rows a short group leaves unfetched
    out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(pl.program_id(0) == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)

    # the first ``depth - 1`` groups are in flight before the loop, and every
    # turn of it starts one more
    groups = [(next_live(walk0), 0)]
    for _ in range(depth - 2):
        groups.append(after(*groups[-1]))
    for slot, grp in enumerate(groups):
        start(*grp, slot)

    def attend(carry):
        w, *groups = carry                # the group attended, those in flight
        groups.append(after(*groups[-1]))
        start(*groups[-1], (w + depth - 1) % depth)
        (walk, g), slot = groups[0], w % depth
        pl.when(g == 0)(lambda: _softmax_init(acc, m_i, l_i))
        each_live_page(walk, g, slot, lambda copy: copy.wait())
        i = walk - walk0
        cap = pages_per_seq * page_size
        if rows > 1:   # [rows x H, 1]: a row's own kv_len for each of its heads
            kv_lens = jnp.concatenate([
                jnp.full((q_ref.shape[1] // rows, 1),
                         jnp.minimum(kl_ref[walk * rows + r], cap), jnp.int32)
                for r in range(rows)])

        def update(pages):
            q, kv = q_ref[i], buf[slot, :pages * page_size]
            start = g * group * page_size
            # (a decode row's is read here, as its kernel has read it since
            # ISSUE 31: the trace of that call stays the pinned one)
            kv_len = jnp.minimum(kl_ref[walk], cap) if rows == 1 else kv_lens
            _softmax_update(q, kv, start, kv_len, acc, m_i, l_i,
                            latent_dim=latent_dim, sm_scale=sm_scale)

        if rows == 1:
            update(group)
        else:          # a short last group at its own size
            lax.switch(pages_from(walk, g * group) - 1, [
                functools.partial(update, n + 1) for n in range(group)])

        @pl.when(groups[1][0] != walk)               # the walk's last group
        def _():
            out_ref[i] = _softmax_finish(acc, l_i).astype(out_ref.dtype)
        return (w + 1, *groups[1:])

    lax.while_loop(lambda c: c[1][0] < end, attend, (0, *groups))


# The decode rows' walk, chosen on the v5e (PERF.md section 6, PR 31,
# scripts/mla_probe.py: the kernel alone at Kimi-K2 widths, 32 rows of 64
# heads x 640 over a 70-page table, us a layer call; live rows x pages each):
#                               0x0   4x8  18x27  32x28  32x70
#   the grid, 7 pages a step    248   262    474    668  1,267
#   the loop, 1 page a group      8    29    311    567  1,397  (the grid's bits)
#             2                   8    21    207    363    888
#             4                   8    18    139    242    597
#             7                   8    21    118    204    495  (0.22-0.24 us a page)
#             10                  8    18    117    205    496
#   7, one group in flight        8    23    124    217    524
#   7, three in flight            8    21    117    205    495
#   7, rows a block 8 / 32        8/9  25/21 121/116 208/204 499/495
# Rows of a decode batch whose q and out sit in VMEM as one block while their
# live pages stream past (the batch is cut into blocks of gcd(R, this); 16
# rows of 64 heads x 640: 1.3 MB in, 1.0 MB out, each double-buffered).
DECODE_ROWS_PER_BLOCK = 16
# Consecutive live pages of one row that share ONE online-softmax update (a
# [7 x 128, 640] operand: 1.15 MB), and groups whose DMAs run ahead of the
# group being attended (a ring of one more operand than this).
DECODE_PAGES_PER_GROUP = 7
DECODE_GROUPS_IN_FLIGHT = 2

# A prefill chunk's walk, chosen on the v5e (PERF.md section 6, PR 34,
# scripts/mla_probe.py: the kernel alone at Kimi-K2 widths, a 512-row chunk of
# 64 heads x 640 over a 70-page table, 16 rows a block unless said, us a layer
# call at 0 / 1,536 / 3,584 / 8,448 tokens cached before the chunk; [us a
# live (row block, page) at 3,584: the MXU alone needs 1.53]):
#   the (32, 10) grid, 7 pages a step      529  1,702  3,220   6,755  [3.30]
#   the loop, 1 page a group               359  1,642  3,352   7,415  [3.43] (the grid's bits)
#   whole groups (a short one masked), 2   293  1,086  2,153   4,685
#             4                            332  1,028  1,964   4,185
#             7                            487  1,088  2,060   4,024
#   a short last group at its own size, 3  279  1,031  2,033   4,443
#             4                            270    992  1,949   4,223  [2.00] <- shipped
#             5                            267    969  1,915   4,150
#             6                            270    975  1,892   4,086  [1.94]
#             7                            310  2,486  4,960  10,828  [5.08]
#             10                           478  3,044  5,946  13,113
#   4, two groups in flight                271    985  1,945   4,226
#   4, 8 rows a block                      314  1,108  2,152   4,632
#   4, 32 rows a block (96 MiB of VMEM)    257    947  1,874   4,075
# An update's unrolled code grows with rows x keys x branches: past ~21 units
# of [1024 rows x one page] (sizes 1..7 at 16 rows: 28; 1..4 at 32 rows: 20;
# 1..6 at 16 rows: 21) a call reads 2.5 x slower at every context: the
# kernel's code no longer fits where the core keeps it. 4 pages a group (10
# units) stands clear of that edge, inside Mosaic's default 16 MB of scoped
# VMEM (12.1 MB by the compiler's count), within 3 % of the best row.
# Consecutive live pages of a row block that share ONE online-softmax update
# (a [4 x 128, 640] operand against [1024, 640] queries: [1024, 512] float32
# scores), and groups in flight behind the one attended (the walk is bound by
# the MXU, not by the 0.8 us a group's DMA takes: one suffices).
CHUNK_PAGES_PER_GROUP = 4
CHUNK_GROUPS_IN_FLIGHT = 1


def mla_decode_paged(q: jax.Array, pool: jax.Array, block_table: jax.Array,
                     kv_len: jax.Array, *, layer, latent_dim: int,
                     sm_scale: float, rows_per_block: int = 1) -> jax.Array:
    """q [R, H, W]: per row and head ``[q' | q_rope | 0]`` in the pool's
    stored width W; pool [L, P, page_size, W] (the stacked latent pool,
    read in place at ``layer``); block_table [R, pages_per_seq] int32;
    kv_len [R] int32. Returns [R, H, latent_dim]: the softmax-weighted mean
    of the cached ``c`` rows, per head, still to be up-projected by ``W_uv``.

    Either way the rows walk the live pages in one in-kernel loop, a group of
    pages an online-softmax update, and a table entry past the live pages may
    be arbitrary: nothing reads it.

    Decode rows, each with a block table of its own (``rows_per_block`` 1,
    the default): a row with ``kv_len`` 0 costs nothing and returns zeros.

    A prefill chunk's rows (``rows_per_block`` > 1, which must divide R):
    rows ``[i * rows_per_block, (i + 1) * rows_per_block)`` PROMISE to share
    one block-table row (the first's is the one read) and walk its pages
    together, as far as the largest ``kv_len`` among them. A block whose rows
    all have ``kv_len`` 0 costs nothing and returns zeros; a row with
    ``kv_len`` 0 inside a live block (a chunk's padding tail) returns a finite
    value nobody reads. At one page a group the arithmetic is the decode
    loop's at one page a group, to the bit; a larger group changes the order
    of the float32 sums inside it."""
    R, H, W = q.shape
    L, P_pool, page_size, W_pool = pool.shape
    assert W == W_pool and latent_dim <= W, (q.shape, pool.shape)
    assert latent_dim % 128 == 0 and W % 128 == 0, (
        "the value slice and the stored row are lane-aligned")
    Rb, S = rows_per_block, block_table.shape[1]
    assert R % Rb == 0, f"{R} rows in blocks of {Rb}"
    live = R * S * page_size
    cost = pl.CostEstimate(
        flops=2 * live * H * (W + latent_dim),
        bytes_accessed=(q.size + R // Rb * S * page_size * W)
        * q.dtype.itemsize,
        transcendentals=live * H)
    if Rb == 1:                       # decode rows: a table each
        n, G, F = (math.gcd(R, DECODE_ROWS_PER_BLOCK), DECODE_PAGES_PER_GROUP,
                   DECODE_GROUPS_IN_FLIGHT)
    else:                             # a chunk's row blocks: one walk a step
        n, G, F = 1, CHUNK_PAGES_PER_GROUP, CHUNK_GROUPS_IN_FLIGHT
        q, block_table = q.reshape(R // Rb, Rb * H, W), block_table[::Rb]
    G, ring, M = min(G, S), F + 1, Rb * H
    walks = lambda i, *_: (i, 0, 0)                         # noqa: E731
    kernel = functools.partial(
        _mla_loop_kernel, rows=Rb, group=G, n_pool=P_pool,
        page_size=page_size, latent_dim=latent_dim, sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R // Rb // n,),
            in_specs=[pl.BlockSpec((n, M, W), walks),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((n, M, latent_dim), walks),
            scratch_shapes=[pltpu.VMEM((ring, G * page_size, W), pool.dtype),
                            pltpu.SemaphoreType.DMA((ring, G)),
                            pltpu.VMEM((M, latent_dim), jnp.float32),
                            pltpu.VMEM((M, 1), jnp.float32),
                            pltpu.VMEM((M, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((R // Rb, M, latent_dim), q.dtype),
        cost_estimate=cost,
        name="mla_decode_paged",
        interpret=default_interpret(),
    )(kv_len.astype(jnp.int32), block_table,
      jnp.asarray(layer, jnp.int32).reshape(1), q, pool)
    return out.reshape(R, H, latent_dim)


__all__ = ["mla_decode_paged"]
