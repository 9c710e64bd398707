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

Two walks of the stacked pool, read in place (``layer`` is a scalar-prefetch
operand of both):

The DECODE rows (a block table a row) are ONE loop inside the kernel over the
batch's live pages, the pattern of ``ops.flash_decode.gqa_decode_paged`` since
ISSUE 29, a GROUP of pages a turn (ISSUE 31): ``DECODE_PAGES_PER_GROUP``
consecutive live pages of one row are fetched by hand into adjacent slices of
one VMEM operand and meet the row's heads in ONE online-softmax update, with
the next groups' copies in flight behind it. A dead page is no step, no index
map and no byte; an idle row costs nothing.

A prefill CHUNK's rows keep the (row block, page step) grid that
``gqa_decode_paged`` had until ISSUE 29: pages streamed through the block
table by index maps, dead steps revisit the last live page (no DMA), compute
skipped. Two things make it read each page of the sequence once a row block
and not once a row:

- ``rows_per_block`` consecutive rows form one grid row: they share the
  block table of the block's first row (the caller's promise: a chunk's rows
  all belong to one sequence) and differ only in ``kv_len``, which masks per
  row. Decode uses 1 (every row has its own table).
- ``pages_per_step`` pages are read a grid step (the same pool operand that
  many times over, each with its own index map): fewer, fatter grid steps
  for long block tables.
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


def _mla_kernel(kl_ref, bt_ref, layer_ref, q_ref, klr_ref, *rest,
                n_pages: int, page_size: int, latent_dim: int,
                sm_scale: float):
    """Grid (row blocks, page steps). ``q_ref`` [M, W] is the block's rows x
    heads, ``klr_ref`` [M, 1] their ``kv_len``; ``rest`` = the step's page
    blocks [page_size, W], the output [M, latent_dim], and the scratch
    (acc, m, l)."""
    del bt_ref, layer_ref
    pages, out_ref, (acc, m_i, l_i) = (rest[:n_pages], rest[n_pages],
                                       rest[n_pages + 1:])
    b, s = pl.program_id(0), pl.program_id(1)

    pl.when(s == 0)(lambda: _softmax_init(acc, m_i, l_i))

    for j, page in enumerate(pages):
        start = (s * n_pages + j) * page_size

        @pl.when(start < kl_ref[b])
        def _(page=page, start=start):
            _softmax_update(q_ref[...], page[...], start, klr_ref[...], acc,
                            m_i, l_i, latent_dim=latent_dim,
                            sm_scale=sm_scale)

    @pl.when(s == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = _softmax_finish(acc, l_i).astype(out_ref.dtype)


def _mla_loop_kernel(kl_ref, bt_ref, layer_ref, q_ref, pool_hbm, out_ref,
                     buf, sem, acc, m_i, l_i, *, group: int, n_pool: int,
                     page_size: int, latent_dim: int, sm_scale: float):
    """Grid (row blocks,) over a latent pool left in HBM: ONE loop over the
    block's live pages alone, rows in order and a row's pages in order,
    ``group`` pages a turn. ``q_ref`` / ``out_ref`` hold the block's rows
    [rows, H, .]; ``buf`` [ring, group * page_size, W] is a ring of group
    operands, ``sem`` [ring, group] a DMA semaphore a page of it.

    A turn fetches the group's LIVE pages (``bt_ref[row, page]`` of layer
    ``layer_ref[0]``, straight out of the stacked pool) into adjacent
    [page_size, W] slices of one ring entry, the groups after it already in
    flight into the others, and makes one online-softmax update of the row's
    heads against the whole entry. A row's last group may hold fewer live
    pages than ``group``: the others are not fetched, their keys are masked
    (``pos < kv_len``) to weight 0, and the rows they leave in the entry are
    those of an earlier group or the zeros THE RING IS FILLED WITH ONCE A
    CALL, finite either way (0 x inf in the value product would be NaN). An
    idle row and a page past ``kv_len`` are not steps at all; a table entry
    past a row's live pages is never read and a live one is clamped into the
    pool."""
    rows, pages_per_seq = q_ref.shape[0], bt_ref.shape[1]
    row0 = pl.program_id(0) * rows
    end = row0 + rows
    layer = layer_ref[0]
    depth = buf.shape[0]

    def live_pages(row):
        # a key past the table's last page does not exist, whatever kv_len says
        return jnp.minimum((kl_ref[row] + page_size - 1) // page_size,
                           pages_per_seq)

    def next_live(row):
        return lax.while_loop(
            lambda r: (r < end) & (kl_ref[jnp.minimum(r, end - 1)] <= 0),
            lambda r: r + 1, row)

    def after(row, g):
        """The group that follows (row, g); row ``end`` when none is left."""
        n = live_pages(jnp.minimum(row, end - 1))
        return lax.cond((g + 1) * group >= n,
                        lambda: (next_live(row + 1), 0), lambda: (row, g + 1))

    def each_live_page(row, g, slot, do):
        """``do`` the copy of every live page of group (row, g)."""
        first = g * group

        def one(j, _):
            page = jnp.clip(bt_ref[row, first + j], 0, n_pool - 1)
            at = pl.multiple_of(j * page_size, page_size)
            do(pltpu.make_async_copy(pool_hbm.at[layer, page],
                                     buf.at[slot, pl.ds(at, page_size)],
                                     sem.at[slot, j]))

        # a loop, not ``group`` conditionals: the trace and Mosaic's compile
        # are inside the serving engine's set-up time
        lax.fori_loop(0, jnp.minimum(live_pages(row) - first, group), one,
                      None)

    def start(row, g, slot):
        pl.when(row < end)(lambda: each_live_page(
            row, g, slot, lambda copy: copy.start()))

    # what no group writes: an idle row's zeros, and (once a call: scratch
    # outlives a grid step) the ring rows a short group leaves unfetched
    out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(pl.program_id(0) == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)

    # the first ``depth - 1`` groups are in flight before the loop, and every
    # turn of it starts one more
    groups = [(next_live(row0), 0)]
    for _ in range(depth - 2):
        groups.append(after(*groups[-1]))
    for slot, grp in enumerate(groups):
        start(*grp, slot)

    def attend(carry):
        w, *groups = carry                # the group attended, those in flight
        groups.append(after(*groups[-1]))
        start(*groups[-1], (w + depth - 1) % depth)
        (row, g), slot = groups[0], w % depth
        pl.when(g == 0)(lambda: _softmax_init(acc, m_i, l_i))
        each_live_page(row, g, slot, lambda copy: copy.wait())
        r = row - row0
        _softmax_update(
            q_ref[r], buf[slot], g * group * page_size,
            jnp.minimum(kl_ref[row], pages_per_seq * page_size), acc, m_i,
            l_i, latent_dim=latent_dim, sm_scale=sm_scale)

        @pl.when(groups[1][0] != row)                # the row's last group
        def _():
            out_ref[r] = _softmax_finish(acc, l_i).astype(out_ref.dtype)
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


def _loop_walk(q, pool, block_table, kv_len, layer, *, latent_dim, sm_scale,
               cost):
    R, H, W = q.shape
    _, P_pool, page_size, _ = pool.shape
    Rb = math.gcd(R, DECODE_ROWS_PER_BLOCK)
    G = min(DECODE_PAGES_PER_GROUP, block_table.shape[1])
    ring = DECODE_GROUPS_IN_FLIGHT + 1
    rows = lambda i, *_: (i, 0, 0)                          # noqa: E731
    kernel = functools.partial(
        _mla_loop_kernel, group=G, n_pool=P_pool, page_size=page_size,
        latent_dim=latent_dim, sm_scale=sm_scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R // Rb,),
            in_specs=[pl.BlockSpec((Rb, H, W), rows),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((Rb, H, latent_dim), rows),
            scratch_shapes=[pltpu.VMEM((ring, G * page_size, W), pool.dtype),
                            pltpu.SemaphoreType.DMA((ring, G)),
                            pltpu.VMEM((H, latent_dim), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((R, H, latent_dim), q.dtype),
        cost_estimate=cost,
        name="mla_decode_paged",
        interpret=default_interpret(),
    )(kv_len, block_table, layer, q, pool)


def _grid_walk(q, pool, block_table, kv_len, layer, *, latent_dim, sm_scale,
               rows_per_block, pages_per_step, cost):
    R, H, W = q.shape
    _, P_pool, page_size, _ = pool.shape
    Rb, N = rows_per_block, pages_per_step
    assert R % Rb == 0, f"{R} rows in blocks of {Rb}"
    n_blk, M = R // Rb, Rb * H
    n_steps = -(-block_table.shape[1] // N)
    kl_blk = kv_len.reshape(n_blk, Rb).max(axis=1)
    bt_blk = block_table[::Rb]
    kl_rows = jnp.repeat(kv_len, H)[:, None]                # [R * H, 1]

    def page_index(j):
        def index(b, s, kl, bt, ly):
            last = jnp.maximum((kl[b] + page_size - 1) // page_size - 1, 0)
            page = bt[b, jnp.minimum(s * N + j, last)]
            return (ly[0], jnp.clip(page, 0, P_pool - 1), 0, 0)
        return index

    rows = lambda b, s, kl, bt, ly: (b, 0)                  # noqa: E731
    kernel = functools.partial(_mla_kernel, n_pages=N, page_size=page_size,
                               latent_dim=latent_dim, sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_blk, n_steps),
            in_specs=[pl.BlockSpec((M, W), rows),
                      pl.BlockSpec((M, 1), rows)]
            + [pl.BlockSpec((None, None, page_size, W), page_index(j))
               for j in range(N)],
            out_specs=pl.BlockSpec((M, latent_dim), rows),
            scratch_shapes=[pltpu.VMEM((M, latent_dim), jnp.float32),
                            pltpu.VMEM((M, 1), jnp.float32),
                            pltpu.VMEM((M, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((R * H, latent_dim), q.dtype),
        cost_estimate=cost,
        name="mla_decode_paged",
        interpret=default_interpret(),
    )(kl_blk, bt_blk, layer, q.reshape(R * H, W), kl_rows, *([pool] * N))
    return out.reshape(R, H, latent_dim)


def mla_decode_paged(q: jax.Array, pool: jax.Array, block_table: jax.Array,
                     kv_len: jax.Array, *, layer, latent_dim: int,
                     sm_scale: float, rows_per_block: int = 1,
                     pages_per_step: int | None = None) -> jax.Array:
    """q [R, H, W]: per row and head ``[q' | q_rope | 0]`` in the pool's
    stored width W; pool [L, P, page_size, W] (the stacked latent pool,
    read in place at ``layer``); block_table [R, pages_per_seq] int32;
    kv_len [R] int32. Returns [R, H, latent_dim]: the softmax-weighted mean
    of the cached ``c`` rows, per head, still to be up-projected by ``W_uv``.

    Decode rows, each with a block table of its own (the default:
    ``pages_per_step`` None, ``rows_per_block`` 1), walk the batch's live
    pages in one in-kernel loop, a group of pages an update: a row with
    ``kv_len`` 0 costs nothing and returns zeros, and entries past a row's
    live pages may be arbitrary, nothing reads them.

    A prefill chunk's rows give ``pages_per_step`` (pages read a grid step)
    and take the (row block, page step) grid: rows ``[i * rows_per_block,
    (i + 1) * rows_per_block)`` must share one block-table row (that of the
    first); a row with ``kv_len`` 0 returns zeros if its whole block is
    empty, else a finite value nobody reads; the index maps never dereference
    an entry past a row block's live pages. One update a page, in page
    order: at one row a block it is the decode loop's arithmetic with a
    group of one page, to the bit."""
    R, H, W = q.shape
    L, P_pool, page_size, W_pool = pool.shape
    assert W == W_pool and latent_dim <= W, (q.shape, pool.shape)
    assert latent_dim % 128 == 0 and W % 128 == 0, (
        "the value slice and the stored row are lane-aligned")
    S = block_table.shape[1]
    live = R * S * page_size
    cost = pl.CostEstimate(
        flops=2 * live * H * (W + latent_dim),
        bytes_accessed=(q.size + R // rows_per_block * S * page_size * W)
        * q.dtype.itemsize,
        transcendentals=live * H)
    walk = dict(latent_dim=latent_dim, sm_scale=sm_scale, cost=cost)
    kv_len = kv_len.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if pages_per_step is None:
        assert rows_per_block == 1, "rows that share a table take the grid"
        return _loop_walk(q, pool, block_table, kv_len, layer, **walk)
    return _grid_walk(q, pool, block_table, kv_len, layer, **walk,
                      rows_per_block=rows_per_block,
                      pages_per_step=pages_per_step)


__all__ = ["mla_decode_paged"]
