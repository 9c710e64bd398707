"""Distributed Flash-Decoding (analog of reference
python/triton_dist/kernels/nvidia/flash_decode.py + the SP layer
sp_flash_decode_layer.py).

Reference structure: per-rank split-KV GQA decode kernel (flash_decode.py
:129-280) + intra-rank combine (:392-480), then a low-latency allgather of
each rank's partial (out ‖ lse) and an inter-rank lse-weighted combine
(:481-566). Sequence parallelism = KV cache sharded over ranks
(SURVEY §5.7); batch=1 decode is the target.

TPU-native mapping:

- GPU split-KV exists to fill SMs with (batch × head × split) blocks. A TPU
  core runs its grid sequentially, so the *intra-rank* split is pointless —
  the kernel is a single-pass online-softmax walk over the local KV shard
  (the grid's S dimension pipelines KV blocks HBM→VMEM instead). The
  *inter-rank* split IS the SP sharding, and the partial-merge math
  (m/l/lse bookkeeping) is identical to the reference's combine kernels.
- lse rides the wire lane-broadcast ([…, 128]) so every DMA slice stays
  tiling-aligned.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.ops.allgather import all_gather
from triton_dist_tpu.ops.common import collective_id_for
from triton_dist_tpu.shmem import device as shd
from triton_dist_tpu.shmem.context import ShmemContext
from triton_dist_tpu.utils import default_interpret

NEG_INF = -1e30


def _softmax_init(acc, m_i, l_i, sink=None):
    """An empty running softmax. With ``sink`` (a learned logit a query head,
    shaped as ``m_i``, whose value is zero) the sink is its first term: it
    starts the running maximum and holds exp(sink - m) = 1 of the sum, and
    every later update rescales it with the rest. Nothing is added to
    ``acc``: rows then sum to less than one."""
    acc[...] = jnp.zeros_like(acc)
    if sink is None:
        m_i[...] = jnp.full_like(m_i, NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)
    else:
        m_i[...] = sink
        l_i[...] = jnp.ones_like(l_i)


def _softmax_update(start, kv_len, q, k, v, acc, m_i, l_i, *, block_s: int,
                    sm_scale: float, n_kv_heads: int, lo=None):
    """One online-softmax update: the KV block ``k`` [Hkv, block_s, Dk] /
    ``v`` [Hkv, block_s, Dv], whose first key sits at position ``start``,
    against all Hq query heads ``q`` [Hq, Dk] of one row (``acc`` [Hq, Dv];
    keys and values may differ in width), as a [Hkv, G, ·] batched
    contraction (Mosaic needs the last-two block dims full/aligned, so heads
    are not split). Keys at ``kv_len`` and beyond are masked, and with ``lo``
    (a sliding window's bound) those below it. Analog of
    kernel_gqa_fwd_batch_decode_split_kv
    (flash_decode.py:129-280) with the split-KV dimension replaced by
    sequential KV-block pipelining."""
    Hq, D = acc.shape
    G = Hq // n_kv_heads
    # operands stay in the input dtype (f32 accumulate): upcasting
    # bf16 first would run the MXU at its slower f32 rate (see the
    # ring-attention pipeline note)
    q = q.reshape(n_kv_heads, G, q.shape[-1])
    scores = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * sm_scale  # [Hkv, G, bs]
    scores = scores.reshape(Hq, block_s)
    pos = start + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    seen = pos < kv_len
    if lo is not None:
        seen = jnp.logical_and(seen, pos >= lo)
    scores = jnp.where(seen, scores, NEG_INF)
    m_new = jnp.maximum(m_i[...], jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_i[...] - m_new)
    p = jnp.exp(scores - m_new)                  # [Hq, block_s]
    l_i[...] = l_i[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p.reshape(n_kv_heads, G, block_s).astype(v.dtype), v,
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).reshape(Hq, D)
    acc[...] = acc[...] * alpha + pv
    m_i[...] = m_new


def _softmax_finish(out_ref, lse_ref, acc, m_i, l_i, row=0):
    l_safe = jnp.where(l_i[...] > 0, l_i[...], 1.0)
    out_ref[row] = (acc[...] / l_safe).astype(out_ref.dtype)
    # lse = m + log(l); empty shard -> NEG_INF so combine ignores it
    lse = jnp.where(l_i[...] > 0, m_i[...] + jnp.log(l_safe), NEG_INF)
    lse_ref[row] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _decode_kernel(kv_len_ref, q_ref, k_ref, v_ref, out_ref, lse_ref,
                   acc, m_i, l_i, *, block_s: int, sm_scale: float,
                   n_kv_heads: int):
    """Grid (B, S//block_s) over a contiguous KV shard: init at s == 0, one
    update a live KV block, finalize (incl. lse) at the last step."""
    b, s = pl.program_id(0), pl.program_id(1)
    kv_len = kv_len_ref[b]
    pl.when(s == 0)(lambda: _softmax_init(acc, m_i, l_i))

    @pl.when(s * block_s < kv_len)
    def _():
        _softmax_update(s * block_s, kv_len, q_ref[0], k_ref[0], v_ref[0],
                        acc, m_i, l_i, block_s=block_s, sm_scale=sm_scale,
                        n_kv_heads=n_kv_heads)

    pl.when(s == pl.num_programs(1) - 1)(
        lambda: _softmax_finish(out_ref, lse_ref, acc, m_i, l_i))


def _decode_paged_kernel(kv_len_ref, bt_ref, layer_ref, q_ref, *refs,
                         n_pool: int, page_size: int, sm_scale: float,
                         n_kv_heads: int, window: int | None = None,
                         sinks: bool = False, fused: bool = False):
    """Grid (row blocks,) over a paged KV pool left in HBM: ONE loop over the
    block's LIVE pages alone, rows in order and a row's pages in order, each
    fetched by hand (``bt_ref[row, idx]`` of layer ``layer_ref[0]``, straight
    out of the stacked pool) into one of two VMEM buffers while the page
    before it is attended. One online-softmax update a page, in page order,
    exactly as a grid of (row, page) steps makes them; an idle row and a page
    past ``kv_len`` are not steps at all. ``q_ref`` / ``out_ref`` /
    ``lse_ref`` hold the block's rows. Analog of the reference's
    block_table-driven split-KV kernel (flash_decode.py:129-280 `page`
    indexing).

    With ``window`` a row attends its last ``window`` keys alone: its walk
    starts at the page of key ``kv_len - window`` (no page before it is a
    step or a DMA), that first page is masked below the bound, and the block
    table is a RING: logical page ``idx`` lives in column ``idx % columns``.

    With ``sinks`` one more operand follows ``q_ref``: ``sink_ref`` [Hq, 1]
    float32, the learned logit a query head that every live row's softmax
    starts from (``_softmax_init``).

    ``fused``: ONE pool whose rows are ``[K | V]`` (``gqa_decode_paged``): no
    ``v_hbm`` / ``v_buf`` operand, one copy a page, and the page's buffer is
    both operands of the update (the queries' value lanes are zeros, the
    output's key lanes are dropped by the caller)."""
    sink_ref, refs = (refs[0], refs[1:]) if sinks else (None, refs)
    if fused:
        k_hbm, out_ref, lse_ref, k_buf, sem, acc, m_i, l_i = refs
        v_buf = k_buf
    else:
        k_hbm, v_hbm, out_ref, lse_ref, k_buf, v_buf, sem, acc, m_i, l_i = refs
    sink = None if sink_ref is None else sink_ref[...]
    rows, pages_per_seq = q_ref.shape[0], bt_ref.shape[1]
    row0 = pl.program_id(0) * rows
    end = row0 + rows
    layer = layer_ref[0]

    def live_pages(row):
        # a key past the table's last page does not exist, whatever kv_len
        # says; a ring has no last page
        n = (kv_len_ref[row] + page_size - 1) // page_size
        return n if window else jnp.minimum(n, pages_per_seq)

    def bound(row):
        return jnp.maximum(kv_len_ref[row] - window, 0)

    def first_page(row):
        """(row, its first live page); row ``end`` when no row is left."""
        if not window:
            return row, 0
        return row, bound(jnp.minimum(row, end - 1)) // page_size

    def next_live(row):
        return lax.while_loop(
            lambda r: (r < end) & (kv_len_ref[jnp.minimum(r, end - 1)] <= 0),
            lambda r: r + 1, row)

    def fetch(row, idx, buf):
        # the clamp keeps even a garbage block-table entry inside the pool
        col = idx % pages_per_seq if window else idx
        page = jnp.clip(bt_ref[row, col], 0, n_pool - 1)
        k_copy = pltpu.make_async_copy(k_hbm.at[layer, page], k_buf.at[buf],
                                       sem.at[0, buf])
        if fused:
            return (k_copy,)
        return (k_copy,
                pltpu.make_async_copy(v_hbm.at[layer, page], v_buf.at[buf],
                                      sem.at[1, buf]))

    def start(row, idx, buf):
        @pl.when(row < end)
        def _():
            for copy in fetch(row, idx, buf):
                copy.start()

    def after(row, idx):
        """The live page that follows (row, idx); row ``end`` when none."""
        return lax.cond(
            idx + 1 >= live_pages(jnp.minimum(row, end - 1)),
            lambda: first_page(next_live(row + 1)), lambda: (row, idx + 1))

    # the first ``depth - 1`` live pages are in flight before the loop, and
    # every turn of it starts one more
    depth = k_buf.shape[0]
    pages = [first_page(next_live(row0))]
    for _ in range(depth - 2):
        pages.append(after(*pages[-1]))
    for buf, page in enumerate(pages):
        start(*page, buf)
    # what no page writes: an idle row's zeros and empty-shard lse
    out_ref[...] = jnp.zeros_like(out_ref)
    lse_ref[...] = jnp.full_like(lse_ref, NEG_INF)

    def attend(carry):
        w, *pages = carry                 # the page attended, those in flight
        pages.append(after(*pages[-1]))
        start(*pages[-1], (w + depth - 1) % depth)
        (row, idx), buf = pages[0], w % depth
        pl.when(idx == first_page(row)[1])(
            lambda: _softmax_init(acc, m_i, l_i, sink))
        for copy in fetch(row, idx, buf):
            copy.wait()
        r = row - row0
        _softmax_update(
            idx * page_size,
            kv_len_ref[row] if window
            else jnp.minimum(kv_len_ref[row], pages_per_seq * page_size),
            q_ref[r], k_buf[buf], v_buf[buf], acc, m_i, l_i,
            block_s=page_size, sm_scale=sm_scale, n_kv_heads=n_kv_heads,
            lo=bound(row) if window else None)
        pl.when(pages[1][0] != row)(             # the row's last live page
            lambda: _softmax_finish(out_ref, lse_ref, acc, m_i, l_i, row=r))
        return (w + 1, *pages[1:])

    lax.while_loop(lambda c: c[1][0] < end, attend, (0, *pages))


def gqa_decode_partial(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                       kv_len: jax.Array, block_s: int = 128,
                       sm_scale: float | None = None):
    """Single-device split-KV decode over a (possibly partial) KV shard.
    q [B, Hq, D]; k_cache/v_cache [B, Hkv, S, D] (head-major layout so KV
    blocks are tiling-aligned DMA slices); kv_len [B] valid keys. Returns
    (out [B, Hq, D] in q.dtype, lse [B, Hq, 128] f32 lane-broadcast).
    Entry analog: gqa_fwd_batch_decode_intra_rank (flash_decode.py:847-930).
    """
    B, Hq, D = q.shape
    _, Hkv, S, _ = k_cache.shape
    assert Hq % Hkv == 0
    block_s = min(block_s, S)
    if S % block_s != 0:
        # fall back to the largest common divisor so ragged shard lengths
        # (e.g. S=192 with block_s=128) still work; kv_len masking handles
        # the tail either way
        block_s = math.gcd(S, block_s)
    assert block_s % 8 == 0 or block_s == S, (
        f"KV shard length {S} has no tiling-aligned block size; pad the "
        f"cache (second-minor DMA dims must be multiples of 8)")
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)

    kernel = functools.partial(_decode_kernel, block_s=block_s,
                               sm_scale=sm_scale, n_kv_heads=Hkv)
    grid = (B, S // block_s)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, Hq, D), lambda b, s, kl: (b, 0, 0)),
                pl.BlockSpec((1, Hkv, block_s, D),
                             lambda b, s, kl: (b, 0, s, 0)),
                pl.BlockSpec((1, Hkv, block_s, D),
                             lambda b, s, kl: (b, 0, s, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, Hq, D), lambda b, s, kl: (b, 0, 0)),
                pl.BlockSpec((1, Hq, 128), lambda b, s, kl: (b, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((Hq, D), jnp.float32),
                pltpu.VMEM((Hq, 1), jnp.float32),
                pltpu.VMEM((Hq, 1), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, 128), jnp.float32),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * B * Hq * S * D,
            bytes_accessed=(q.size + k_cache.size + v_cache.size) * 2,
            transcendentals=B * Hq * S),
        interpret=default_interpret(),
    )(kv_len, q, k_cache, v_cache)


def _window_pages(window: int, page_size: int, rows: int) -> int:
    """Pages that ``rows`` consecutive positions' windows can touch together:
    ``window + rows - 1`` keys, starting anywhere in a page."""
    return -(-(window + rows - 1) // page_size) + 1


def _pages_at_most(window, table: int, page_size: int, rows: int) -> int:
    """Pages ``rows`` consecutive rows can walk together at most: their
    windows' pages, or the whole table."""
    return min(table, _window_pages(window, page_size, rows)) if window \
        else table


def _check_ring(window, ring: int, page_size: int, rows: int,
                widths: tuple) -> None:
    assert window is None or (window >= 1 and ring >= _window_pages(
        window, page_size, rows)), (
        f"a ring of {ring} pages of {page_size} (keys and values "
        f"{' and '.join(str(w) for w in widths)} wide) cannot hold a window "
        f"of {window} keys for {rows} consecutive rows")


def _kernel_name(base: str, window, sinks) -> str:
    """A paged GQA kernel's name in a trace: one a variant."""
    return base + ("_window" if window else "") + (
        "" if sinks is None else "_sink")


def _as_stack(k_pages, v_pages, layer):
    """The paged kernels' pool operands: the stacked pool and ``layer`` as the
    [1] int32 scalar-prefetch operand of their index maps."""
    assert (layer is not None) == (k_pages.ndim == 5), (
        "layer= goes with a stacked [L, P, Hkv, page_size, D] pool")
    if layer is None:
        # a per-layer pool is the L = 1 stack: adding a leading 1 is a
        # bitcast, never a copy
        k_pages, layer = k_pages[None], 0
        v_pages = None if v_pages is None else v_pages[None]
    return k_pages, v_pages, jnp.asarray(layer, jnp.int32).reshape(1)


def _fused_queries(q, kv_pages, sm_scale):
    """Queries for a pool whose rows are ``[K | V]`` (``v_pages`` None): q
    [.., Hq, Dk] padded with zeros over the value lanes, so that a row's
    score against the whole pool row is its score against the keys; the
    head's own scale; and Dk, where the values start in an output row."""
    Dk, W = q.shape[-1], kv_pages.shape[-1]
    assert Dk < W, f"a fused pool row of {W} holds keys of {Dk} and values"
    q = jnp.pad(q, ((0, 0),) * (q.ndim - 1) + ((0, W - Dk),))
    return q, sm_scale if sm_scale is not None else Dk ** -0.5, Dk


# The decode rows' walk is ONE grid step a block of rows, a loop over its live
# pages inside. Chosen on the v5e (PERF.md section 6, PR 29,
# scripts/prefill_attn_probe.py --decode: 16 slots at Mistral-7B widths, the
# kernel alone, us a layer call; live slots x pages of context each):
#                                        0x0   4x3   1x10  16x1  8x6   16x13
#   (16, 13) grid, one page a step,
#     dead steps clamped (before PR 29)  50.4  59.4  57.0  64.0  82.8  179.3
#   the grid, a dead operand keeping its
#     page (forward-filled index table),
#     1 / 2 / 4 / 7 pages a grid step:
#       1                                23.7  38.7  35.4  44.4   -    173.2
#       2                                23.0  35.8  32.8  41.7   -    161.1
#       4                                26.2  40.5  36.4  41.7   -    173.2
#       7                                26.3  37.3  35.6  36.3   -    148.5
#     7, idle rows moving no q / out      -    36.8  34.5   -     -    148.5
#     7, 16 rows to a q / out block      26.3  36.6  34.9  36.3   -    148.7
#   loop over live pages, 1 in flight     1.7  12.2  10.4  16.1  41.5  170.4
#   loop over live pages, 2 in flight     -    10.8   9.4  13.7  35.7  146.5
#   loop over live pages, 3 in flight     -    10.9   9.5  13.7  35.8  146.6
# A grid pays ~25 us a call for its (step x operand) bookkeeping whatever is
# live, so pages a step moved nothing; every result above is bitwise the same.

# Rows of a decode batch whose q, out and lse sit in VMEM as one block while
# their live pages stream past (the batch is cut into blocks of gcd(B, this);
# 16 rows of Mistral's 32 heads x 128: 0.5 MB, each block double-buffered).
DECODE_ROWS_PER_BLOCK = 16
# Live pages whose DMAs run ahead of the page being attended (a ring of one
# more K and V buffer than this: 3 x 0.5 MB at Mistral's 8 heads x 128 x 128).
DECODE_PAGES_IN_FLIGHT = 2


def gqa_decode_paged(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                     block_table: jax.Array, kv_len: jax.Array,
                     sm_scale: float | None = None, layer=None,
                     window: int | None = None,
                     sinks: jax.Array | None = None):
    """Paged-attention decode over a shared KV page pool (the serving-side
    cache layout; parity with the reference's block_table path and its
    ``ref_paged_attn`` golden, test_sp_decode_attn.py:81-134).

    q [B, Hq, D]; k_pages/v_pages [P, Hkv, page_size, D] (page-major pool)
    or, with ``layer`` (a traced or Python int), the whole stacked pool
    [L, P, Hkv, page_size, D] of ``models.llama.init_page_pool``: the same
    kernel then streams its pages from ``pool[layer]`` IN PLACE (``layer``
    is a scalar-prefetch operand of the page DMAs), so a layer loop
    never slices a per-layer pool out of the stack — XLA cannot fuse a
    slice into a Pallas operand, it would copy the layer's pool per call.
    The input's rank picks the form; the result is bitwise the 4-D call on
    ``pool[layer]``.
    block_table [B, pages_per_seq] int32 page ids — entries past
    ceil(kv_len/page_size) may be ARBITRARY values (even out of range):
    nothing dereferences them. kv_len [B] (0 allowed: the row
    returns zeros with lse = NEG_INF, the "empty shard" convention the SP
    combine already honors). Returns (out [B, Hq, D], lse [B, Hq, 128] f32).

    The kernel walks the batch's LIVE pages and nothing else: one loop
    over (row, page) pairs, rows and pages in order, with the next live
    page's DMA in flight behind the current page's online-softmax update
    (``kv_len`` and the block table are scalar-prefetch operands the loop
    reads). A dead page is no grid step, no index-map
    evaluation and no byte, so a short sequence in a long ``pages_per_seq``
    batch costs its own length, not the batch max, and an idle slot
    (``kv_len`` 0) nothing (before ISSUE 29 the kernel stepped a (B,
    pages_per_seq) grid, 208 steps a call of which nine in ten did nothing,
    and an idle row still fetched a page). Every page meets the same query
    in the same order with one update a page: the result is bitwise that
    grid's.

    Nothing here assumes distinct batch rows mean distinct sequences:
    rows are (block_table, kv_len) pairs, so several rows may walk the
    SAME pages at staggered ``kv_len`` — the speculative verify dispatch
    (ISSUE 20) runs B*K rows this way, row (b, i) attending its slot's
    pages at ``kv_len = pos_b + i + 1``. A prefill chunk's rows, which
    ALL share one block-table row, have ``gqa_prefill_paged`` instead:
    the same walk, shared by a block of rows.

    ``window`` (static; None = all of the above, the same program to the
    bit): row b attends keys ``max(0, kv_len_b - window) .. kv_len_b - 1``
    alone, and ``block_table`` [B, ring] is a RING of pages: logical page p
    of the sequence lives in column ``p % ring`` (the writer wraps the same
    way), so a sequence holds ``ring`` pages whatever its length. The walk
    starts at the page that holds the bound, masks the keys below it there,
    and touches no page before it: a row costs its window, not its context.
    ``ring`` pages must span the window plus one page. The kernel's name in
    a trace is then ``gqa_decode_paged_window``.

    Keys and values may differ in WIDTH: q [B, Hq, Dk] against k_pages
    [..., Dk] and v_pages [..., Dv] gives out [B, Hq, Dv] (``sm_scale``
    defaults to ``Dk ** -0.5``: a pool whose keys are zero-padded to a lane
    multiple passes the head's own). ``sinks`` [Hq] float32 (None = all of
    the above, the same program to the bit) is a learned logit a query head
    that joins every row's softmax with a value of zero: ``p_j = exp(a_j - m)
    / (sum_j exp(a_j - m) + exp(s - m))``, ``m = max(max_j a_j, s)``; rows
    then sum to less than one, and ``lse`` counts the sink. The kernel's name
    gains ``_sink``.

    ``v_pages`` None: ``k_pages`` [..., Dk + Dv] is ONE pool whose rows are
    ``[K | V]`` of a KV head side by side (heads of 64 as one 128-lane row:
    held apart, each would be padded to 128 lanes on the chip, twice the
    bytes held and read). A page is then ONE copy, and its buffer is both
    operands of the update: the queries are zero over the value lanes, so the
    scores are the keys' own, and ``p @ [K | V]`` carries ``p @ V`` in its
    last Dv lanes, which are returned. Same kernel, one operand fewer.
    """
    k_pages, v_pages, layer = _as_stack(k_pages, v_pages, layer)
    fused, values_at = v_pages is None, 0
    if fused:
        q, sm_scale, values_at = _fused_queries(q, k_pages, sm_scale)
        v_pages = k_pages
    B, Hq, Dk = q.shape
    _, P_pool, Hkv, page_size, _ = k_pages.shape
    Dv = v_pages.shape[-1]
    assert k_pages.shape[-1] == Dk and v_pages.shape[:-1] == k_pages.shape[:-1]
    assert Hq % Hkv == 0
    assert page_size % 8 == 0, f"page_size {page_size} must be 8-aligned"
    pages_per_seq = block_table.shape[1]
    _check_ring(window, pages_per_seq, page_size, 1, (Dk, Dv))
    Rb = math.gcd(B, DECODE_ROWS_PER_BLOCK)
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(Dk)
    rows = lambda i, *_: (i, 0, 0)                          # noqa: E731
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    page_buf = lambda D: pltpu.VMEM(                        # noqa: E731
        (DECODE_PAGES_IN_FLIGHT + 1, Hkv, page_size, D), k_pages.dtype)
    kernel = functools.partial(_decode_paged_kernel, n_pool=P_pool,
                               page_size=page_size, sm_scale=sm_scale,
                               n_kv_heads=Hkv, window=window,
                               sinks=sinks is not None, fused=fused)
    pools = (k_pages,) if fused else (k_pages, v_pages)
    extra, extra_specs = (), []
    if sinks is not None:
        extra = (sinks.astype(jnp.float32).reshape(Hq, 1),)
        extra_specs = [pl.BlockSpec((Hq, 1), lambda i, *_: (0, 0))]
    live = _pages_at_most(window, pages_per_seq, page_size, 1)
    width = Dk if fused else Dk + Dv       # elements a key moves and meets
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B // Rb,),
            in_specs=[pl.BlockSpec((Rb, Hq, Dk), rows), *extra_specs,
                      *(in_hbm for _ in pools)],
            out_specs=[
                pl.BlockSpec((Rb, Hq, Dv), rows),
                pl.BlockSpec((Rb, Hq, 128), rows),
            ],
            scratch_shapes=[
                *(page_buf(p.shape[-1]) for p in pools),
                pltpu.SemaphoreType.DMA((len(pools),
                                         DECODE_PAGES_IN_FLIGHT + 1)),
                pltpu.VMEM((Hq, Dv), jnp.float32),
                pltpu.VMEM((Hq, 1), jnp.float32),
                pltpu.VMEM((Hq, 1), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, Hq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, 128), jnp.float32),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * B * Hq * live * page_size * width,
            bytes_accessed=(q.size
                            + B * live * Hkv * page_size * width),
            transcendentals=B * Hq * live * page_size),
        name=_kernel_name("gqa_decode_paged", window, sinks),
        interpret=default_interpret(),
    )(kv_len.astype(jnp.int32), block_table, layer, q, *extra, *pools)
    return (out[..., values_at:] if fused else out), lse


# Rows of a prefill chunk that share one walk of the sequence's pages: with
# Mistral's 4 query heads a KV head, 64 rows are a [256, 128] operand a head.
# Chosen on the v5e (PERF.md section 6, PR 27; a 256-row chunk at 1,280
# tokens of context, us a layer): 16 rows 227, 32 rows 151, 64 rows 129; 128
# rows read 116 but need 18 MB of scoped VMEM, over Mosaic's 16 MB default.
PREFILL_ROWS_PER_BLOCK = 64

# The chunk's walk is ONE grid step a row block, a loop over its live pages
# inside, a GROUP of pages an online-softmax update. Chosen on the v5e
# (PERF.md section 6, PR 41, scripts/prefill_attn_probe.py --longdoc: the
# kernel alone at command-a-plus's widths, a 2,048-row chunk of 128 query
# heads over 8 KV heads, 32 rows a block = [512, 128] queries a head; ms a
# layer call under window 4,096 (any context) | no window, 6k | 20k of
# context before the chunk; sha1 = the result's hash):
#   the (64, 34 | 200) grid, a page a step (PR 40)  10.60 | 19.28 | 51.65
#   the loop, KV heads looped, pages a group:
#     1 (the grid's sha1)                           26.75 | 46.30 | 137.7
#     2                                             14.23 | 23.37 | 68.06
#     4                                              8.12 | 13.08 | 36.71
#     8                                              5.46 |  8.45 | 22.33
#   + the next block's first group behind this block's last, 16 / 64 rows a
#     block at 4 pages a group: 9.83 / 8.67 | 14.97 / 14.16 | 42.2 / 40.8
#     4 / 6 pages a group           7.97 / 6.50 | 12.64 / 9.84 | 36.3 / 27.7
#     8                                              5.29 |  8.00 | 21.89  <-
#     8, KV heads unrolled side by side as far as 1 / 2 / 4 MB of their
#     float32 scores go:             5.32 / 5.26 / 12.00 | 7.91 / 7.85 / 20.4
# An update costs ~12 us of fixed work a [8 x 512]-row block (two cross-lane
# reductions, the [M, 1] bookkeeping, the accumulator's rescale: 1.5 us a KV
# head) beside ~0.8 us a page: at one page a group the loop is 2.5 x the
# grid, whose unrolled heads hid one head's chain under another's products,
# and every doubling of the group nearly halves the call. The MXU alone needs
# 3.0 ms a window layer. Mosaic (libtpu 0.0.34) dies on a ``lax.switch`` of
# more than ten branches: 8 is the largest group of exact sizes. The same
# command at Mistral's widths (4 heads a group, 64 rows a block, a 256-row
# chunk; us a layer call at 0 / 512 / 1,280 / 677 tokens before it): the
# grid 31.8 / 70.8 / 129.4 / 83.1, the loop at 8 pages 36.7 / 61.4 / 100.8 /
# 67.7 (heads unrolled as far as 2 MB: 32.4 / 56.7 / 88.5 / 60.3); MiMo's
# (sink_window_probe): a window layer 0.186 -> 0.183 ms, a full layer 1.46 ->
# 0.64 at 2k, 5.70 -> 2.26 at 12k. Unrolled heads buy 0-12 % of a short walk
# and nothing of a long one, double the code Mosaic compiles, and with 4 MB
# unrolled every call read 2.3-3.4 x SLOWER (PR 34's cliff): the heads are a
# loop.
# Consecutive pages of a row block's walk that share ONE online-softmax
# update, at most; and what ONE KV head's float32 scores of such a group may
# take ([M, pages x page_size] x 4 B): a block of more rows x heads than
# [512] takes fewer pages a group.
PREFILL_PAGES_PER_GROUP = 8
PREFILL_GROUP_SCORE_BYTES = 2 << 20
# ... and of EDGE pages, which take the masked update: a block of consecutive
# rows has at most two or three on either side of its interior pages, so
# larger masked updates would be code nobody runs
PREFILL_EDGE_PAGES_PER_GROUP = 4


def chunk_walk_bounds(kv_len: jax.Array, rows: int):
    """Of every block of ``rows`` consecutive entries of ``kv_len`` [C]:
    (the smallest LIVE ``kv_len``, i.e. over rows with ``kv_len`` > 0, and 0
    for a block of padding alone; the largest). These two numbers a block are
    all a chunk's walk is planned from (``chunk_walk_pages``)."""
    kl = kv_len.reshape(-1, rows)
    kl_max = kl.max(axis=1)
    kl_min = jnp.where(kl > 0, kl, jnp.iinfo(jnp.int32).max).min(axis=1)
    return jnp.where(kl_max > 0, kl_min, 0), kl_max


def chunk_walk_pages(kl_min, kl_max, page_size: int, window=None,
                     pages_per_seq=None, xp=jnp):
    """The pages a row block walks and which of them need the mask, from the
    block's smallest live ``kv_len`` and its largest (``chunk_walk_bounds``;
    scalars or arrays, one entry a block): ``(first, lo, hi, end)``, logical
    pages.

    The block walks ``[first, end)``: from the page of its LOWEST bound
    (``kl_min - window``; page 0 without a window) to the page of its last
    key (capped at the table's ``pages_per_seq`` pages where there is no
    ring). A page is INTERIOR when every live row of the block sees every key
    of it: it ends at or before the smallest live ``kv_len`` and (under a
    window) starts at or after the HIGHEST bound, ``kl_max - window``. Those
    are ``[lo, hi)``; the pages ``[first, lo)`` and ``[hi, end)`` are EDGE
    pages, where some live row's mask drops a key (``lo == hi == end`` where
    no page is interior: the walk is one run of edge pages). A padding row
    (``kv_len`` 0) is not live: it is zeroed at the end whatever it summed, so
    it makes no page an edge; a sink changes where the softmax starts and no
    page's kind. A block of padding alone walks nothing (``first == end``).
    ``xp`` is the array module: ``jnp`` in a program or a kernel, ``numpy``
    on the host (``chunk_walk_counts``)."""
    end = (kl_max + page_size - 1) // page_size
    if window:
        first = xp.maximum(kl_min - window, 0) // page_size
        lo = (xp.maximum(kl_max - window, 0) + page_size - 1) // page_size
    else:
        end = xp.minimum(end, pages_per_seq)
        first = lo = 0 * end
    lo = xp.minimum(xp.maximum(lo, first), end)
    hi = xp.minimum(xp.maximum(kl_min // page_size, lo), end)
    # no interior page: the walk is ONE run of edge pages, ``[first, end)``
    lo = xp.where(hi > lo, lo, end)
    return first, lo, xp.maximum(hi, lo), end


def chunk_walk_counts(start: int, real: int, chunk: int, rows: int,
                      page_size: int, window=None, pages_per_seq=None):
    """(pages walked, edge pages among them) of ONE ``gqa_prefill_paged``
    call on a chunk of ``chunk`` rows in blocks of ``rows`` whose first row
    sits at position ``start`` and whose first ``real`` rows are live: summed
    over the row blocks, on the host (numpy), from the same plan the kernel
    walks by."""
    rows = math.gcd(chunk, rows)
    # row r attends start + r + 1 keys: a block's bounds (``chunk_walk_bounds``
    # of such a chunk) from its first position
    at = start + np.arange(0, chunk, rows)
    live = at < start + real
    first, lo, hi, end = chunk_walk_pages(
        np.where(live, at + 1, 0),
        np.where(live, np.minimum(at + rows, start + real), 0), page_size,
        window, pages_per_seq, np)
    return int((end - first).sum()), int((end - first - (hi - lo)).sum())


def _prefill_paged_kernel(kmin_ref, kmax_ref, bt_ref, layer_ref, q_ref,
                          klr_ref, *refs, group: int, edge: int,
                          n_pool: int, page_size: int, sm_scale: float,
                          window: int | None = None, sinks: bool = False,
                          fused: bool = False):
    """Grid (row blocks,) over a paged KV pool left in HBM: ONE loop over the
    block's pages ``[first, end)`` of ``chunk_walk_pages(kmin_ref[i],
    kmax_ref[i])`` and nothing else, a GROUP of up to ``group`` consecutive
    pages a turn. ``q_ref`` [Hkv, M, Dk] is a block of Rb rows x G heads a KV
    head (M = Rb * G), ``klr_ref`` [M, 1] their ``kv_len``, ``bt_ref`` the ONE
    table row they share. With ``sinks`` one more operand follows
    ``klr_ref``: ``sink_ref`` [Hkv, M, 1] float32, every (head, row)'s learned
    logit, which the softmax starts from (``_softmax_init``).

    A turn copies the group's pages (``bt_ref[page]``, under a window
    ``bt_ref[page % ring]``, of layer ``layer_ref[0]``, clamped into the pool)
    into adjacent [page_size] slices of one of two VMEM operands ``k_buf`` /
    ``v_buf`` [2, Hkv, group * page_size, D], the next group's copies in
    flight behind it (behind a block's last group: the NEXT block's first,
    ``turns`` counting groups across grid steps so that both sides name the
    same operand), and makes ONE online-softmax update of the block's rows
    against the group: running max, ``alpha``, the accumulator's rescale and
    the cast of ``p`` once a group, KV head by KV head (a loop: the update's
    code is one head's, whatever Hkv). Groups never straddle a change of kind
    (``[first, lo)``, ``[lo, hi)``, ``[hi, end)`` are cut into groups each on
    its own, interior runs by ``group`` pages and edge runs by ``edge``), and
    a run's short last group is attended at its own size (one branch a size:
    no stale row of the operand is ever read).

    An INTERIOR group takes the update WITHOUT the mask: every live row sees
    every key, ``where(True, s, NEG_INF) == s``, so the result is the masked
    update's to the bit (a padding row sums keys it should not see, and is
    zeroed at the end as ever). An EDGE group masks, a row at a time, keys at
    ``kv_len`` and beyond and (window) those below ``kv_len - window``. A row
    sees key 0 of the walk's first page or nothing yet: a page wholly masked
    for a row finds its running max real and adds exp(NEG_INF - m) = 0, or
    finds it NEG_INF and adds exp(0), which the row's first real key scales
    by exp(NEG_INF - m) = 0; with a sink the max is real from the start."""
    sink_ref, refs = (refs[0], refs[1:]) if sinks else (None, refs)
    if fused:
        # rows of ``[K | V]`` (``gqa_decode_paged``): one pool, one operand
        k_hbm, out_ref, k_buf, sem, turns, acc, m_i, l_i = refs
        pools, v_buf = ((k_hbm, k_buf),), k_buf
    else:
        k_hbm, v_hbm, out_ref, k_buf, v_buf, sem, turns, acc, m_i, l_i = refs
        pools = ((k_hbm, k_buf), (v_hbm, v_buf))
    i, n_blk = pl.program_id(0), pl.num_programs(0)
    Hkv, M, _ = q_ref.shape
    pages_per_seq = bt_ref.shape[0]
    layer = layer_ref[0]

    def walk(blk):
        """A block's walk: its four page bounds, the groups of its low edge
        pages, of its interior ones, and of them all."""
        first, lo, hi, end = chunk_walk_pages(
            kmin_ref[blk], kmax_ref[blk], page_size, window, pages_per_seq)
        n_low = (lo - first + edge - 1) // edge
        n_in = (hi - lo + group - 1) // group
        return (first, lo, hi, end, n_low, n_in,
                n_low + n_in + (end - hi + edge - 1) // edge)

    def group_at(of, t):
        """(first page, pages, masked?) of walk ``of``'s t-th group."""
        first, lo, hi, end, n_low, n_in, _ = of
        low, mid = t < n_low, t < n_low + n_in
        page = jnp.where(low, first + t * edge, jnp.where(
            mid, lo + (t - n_low) * group, hi + (t - n_low - n_in) * edge))
        stop = jnp.where(low, lo, jnp.where(mid, hi, end))
        return (page, jnp.minimum(stop - page, jnp.where(mid & ~low, group,
                                                         edge)), low | ~mid)

    def copies(page0, n, slot, do):
        """``do`` both copies of each of the ``n`` pages from ``page0``."""
        def one(j, _):
            col = (page0 + j) % pages_per_seq if window else page0 + j
            # the clamp keeps even a garbage block-table entry inside the pool
            page = jnp.clip(bt_ref[col], 0, n_pool - 1)
            at = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            for kv, (hbm, buf) in enumerate(pools):
                do(pltpu.make_async_copy(hbm.at[layer, page],
                                         buf.at[slot, :, at],
                                         sem.at[kv, slot, j]))

        # a loop, not ``group`` conditionals: the trace and Mosaic's compile
        # are inside the serving engine's set-up time
        lax.fori_loop(0, n, one, None)

    # groups attended by the blocks before this one: a group's operand is
    # ``turns % 2``, so that a block can start the NEXT block's first group
    # behind its own last one (scratch outlives a grid step)
    @pl.when(i == 0)
    def _():
        turns[0] = 0

    base, mine = turns[0], walk(i)
    n_groups = mine[-1]
    after = walk(jnp.minimum(i + 1, n_blk - 1))
    more = (i + 1 < n_blk) & (after[-1] > 0)

    def start_after(t, slot):
        """Start, into operand ``slot``, the group that follows this block's
        group t (t = -1: its first): its own next one, or behind its last
        the next block's first."""
        own = t + 1 < n_groups
        page0, n, _ = group_at(
            tuple(jnp.where(own, a, b) for a, b in zip(mine, after)),
            jnp.where(own, t + 1, 0))
        pl.when(own | more)(lambda: copies(page0, n, slot,
                                           lambda copy: copy.start()))

    def update(n, masked, page0, slot):
        """One online-softmax update against the ``n`` pages in ``slot``."""
        T = n * page_size

        def head(h, _):
            q, k, v = q_ref[h], k_buf[slot, h, :T], v_buf[slot, h, :T]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale   # [M, T]
            if masked:
                pos = page0 * page_size + jax.lax.broadcasted_iota(
                    jnp.int32, (M, T), 1)
                seen = pos < klr_ref[...]
                if window:
                    seen = jnp.logical_and(seen, pos >= klr_ref[...] - window)
                s = jnp.where(seen, s, NEG_INF)
            m_old = m_i[h]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new)
            l_i[h] = l_i[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)              # [M, Dv]
            acc[h] = acc[h] * alpha + pv
            m_i[h] = m_new

        # KV head by KV head, a loop: the update's code is one head's,
        # whatever Hkv (unrolled side by side the heads' code passes what the
        # core holds: the probe's table above)
        lax.fori_loop(0, Hkv, head, None)

    _softmax_init(acc, m_i, l_i, None if sink_ref is None else sink_ref[...])
    # a walk's first group is started by the block before it: the first
    # block starts its own, and a block that walks nothing hands on
    pl.when((i == 0) | (n_groups == 0))(lambda: start_after(-1, base % 2))

    def turn(t, _):
        slot = (base + t) % 2
        start_after(t, 1 - slot)
        page0, n, masked = group_at(mine, t)
        copies(page0, n, slot, lambda copy: copy.wait())
        lax.cond(masked, *(functools.partial(lax.switch, n - 1, [
            functools.partial(update, size, mask, page0, slot)
            for size in range(1, (edge if mask else group) + 1)])
            for mask in (True, False)))

    lax.fori_loop(0, n_groups, turn, None)
    turns[0] = base + n_groups
    l_safe = jnp.where(l_i[...] > 0, l_i[...], 1.0)
    out = jnp.where((klr_ref[...] > 0)[None], acc[...] / l_safe, 0.0)
    out_ref[...] = out.astype(out_ref.dtype)


def gqa_prefill_paged(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                      block_table: jax.Array, kv_len: jax.Array,
                      sm_scale: float | None = None, layer=None,
                      rows_per_block: int = PREFILL_ROWS_PER_BLOCK,
                      window: int | None = None,
                      vmem_limit_bytes: int | None = None,
                      sinks: jax.Array | None = None) -> jax.Array:
    """``gqa_decode_paged`` for rows that all belong to ONE sequence (a
    prefill chunk's queries): the same online-softmax walk of the block
    table, shared by ``rows_per_block`` rows at a time, so that a page of the
    sequence leaves HBM once a row block and meets the MXU as a
    ``[rows_per_block * G, D]`` operand a KV head, not once a row as
    ``[G, D]``.

    q [C, Hq, D]; k_pages/v_pages as in ``gqa_decode_paged`` (4-D, or with
    ``layer`` the stacked pool read in place); block_table [pages_per_seq]
    int32, the ONE row all C rows share (entries past the live pages may be
    arbitrary); kv_len [C] int32, any values: the chunk may start anywhere
    in a page and end in padding (0: the row returns zeros). Returns out
    [C, Hq, D], equal to ``gqa_decode_paged`` on C copies of the row up to
    summation order; no lse (nothing merges a chunk's partials).

    Grid (row blocks,), and inside a step ONE loop over the block's LIVE
    pages, fetched by the kernel's own double-buffered copies out of the pool
    left in HBM (``_prefill_paged_kernel``; before ISSUE 41 a (row block,
    page) grid stepped every page of the table, live or not, with one update
    a page). The walk ends at the block's own last position: the pages above
    it, not only those past the prompt, are no steps and no bytes, and a block
    of padding alone costs nothing. A GROUP of consecutive pages shares one
    online-softmax update (at most ``PREFILL_PAGES_PER_GROUP``, fewer where
    one head's float32 scores of a group would pass
    ``PREFILL_GROUP_SCORE_BYTES`` or the walk cannot be that long; a walk
    shorter than a group is one short group), and the mask runs on EDGE pages
    alone (``chunk_walk_pages``): the pages where some live row of the block
    does not see every key, i.e. those past the block's smallest live
    ``kv_len`` and, under a window, those before its highest bound. On the
    others, where(True, s, NEG_INF) == s: their result is the masked one's to
    the bit. At one page a group the result is bitwise that grid's; a larger
    group changes the order of the float32 sums inside it.

    ``window`` (static; None = the above) as in ``gqa_decode_paged``: a row
    attends its last ``window`` keys, the table is a ring, and the ring must
    span the window AND the C rows (the chunk writes its rows before it
    walks). A block's live rows are then CONSECUTIVE positions (a chunk's
    are): its walk runs from the page of the lowest bound to the page of its
    last key, at most ``ceil((window + rows_per_block - 1) / page) + 1``
    pages, never over the pages before. The kernel's name in a trace is then
    ``gqa_prefill_paged_window``.
    ``vmem_limit_bytes`` raises Mosaic's scoped-VMEM limit for a block that
    needs more than its 16 MB default. Keys and values of different widths
    (q [C, Hq, Dk], out [C, Hq, Dv]), ``sinks`` [Hq] float32 (the name gains
    ``_sink``) and ``v_pages`` None (ONE pool of ``[K | V]`` rows) are
    ``gqa_decode_paged``'s."""
    k_pages, v_pages, layer = _as_stack(k_pages, v_pages, layer)
    values_at = 0
    if v_pages is None:
        q, sm_scale, values_at = _fused_queries(q, k_pages, sm_scale)
    Rb = math.gcd(q.shape[0], rows_per_block)
    page_size, pages_per_seq = k_pages.shape[3], block_table.shape[0]
    M = Rb * (q.shape[1] // k_pages.shape[2])
    group = max(1, min(PREFILL_PAGES_PER_GROUP,
                       _pages_at_most(window, pages_per_seq, page_size, Rb),
                       PREFILL_GROUP_SCORE_BYTES // (M * page_size * 4)))
    # ONE trace a signature: the layers of a model that call with the same
    # shapes (a period's window layers; every engine a process builds) share
    # it. The module's constants and the backend's mode are read here,
    # outside, so that they are part of what a trace is found by.
    out = jax.jit(_prefill_walk, static_argnames=(
        "sm_scale", "rows_per_block", "window", "vmem_limit_bytes", "group",
        "edge", "interpret"))(
        q, k_pages, v_pages, block_table, kv_len, layer, sinks,
        sm_scale=sm_scale, rows_per_block=Rb, window=window,
        vmem_limit_bytes=vmem_limit_bytes, group=group,
        edge=min(group, PREFILL_EDGE_PAGES_PER_GROUP),
        interpret=default_interpret())
    return out[..., values_at:] if values_at else out


def _prefill_walk(q, k_pages, v_pages, block_table, kv_len, layer, sinks, *,
                  sm_scale, rows_per_block, window, vmem_limit_bytes, group,
                  edge, interpret):
    """``gqa_prefill_paged`` on the stacked pool: the head-major transposes
    and the kernel's call, in blocks of ``rows_per_block`` rows (a divisor of
    the chunk's), at most ``group`` / ``edge`` pages an interior / an edge
    group. ``v_pages`` None: ``k_pages`` holds ``[K | V]`` rows and ``q`` is
    already as wide (``_fused_queries``)."""
    C, Hq, Dk = q.shape
    _, P_pool, Hkv, page_size, _ = k_pages.shape
    fused = v_pages is None
    pools = (k_pages,) if fused else (k_pages, v_pages)
    Dv = pools[-1].shape[-1]
    assert k_pages.shape[-1] == Dk and pools[-1].shape[:-1] == k_pages.shape[:-1]
    assert Hq % Hkv == 0 and block_table.ndim == 1, (q.shape, block_table.shape)
    assert page_size % 8 == 0, f"page_size {page_size} must be 8-aligned"
    G, Rb = Hq // Hkv, rows_per_block
    n_blk, M = C // Rb, Rb * G
    pages_per_seq = block_table.shape[0]
    _check_ring(window, pages_per_seq, page_size, C, (Dk, Dv))
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(Dk)
    kv_len = kv_len.astype(jnp.int32)
    kl_rows = jnp.repeat(kv_len, G)[:, None]                # [C * G, 1]
    # head-major rows: a KV head's operand is its G query heads of every row
    q_hm = q.reshape(C, Hkv, G, Dk).swapaxes(0, 1).reshape(Hkv, C * G, Dk)
    extra, extra_specs = (), []
    if sinks is not None:
        # the sink of (head, block row r, group head g) is its query head's
        extra = (jnp.tile(sinks.astype(jnp.float32).reshape(Hkv, 1, G),
                          (1, Rb, 1)).reshape(Hkv, M, 1),)
        extra_specs = [pl.BlockSpec((Hkv, M, 1), lambda i, *_: (0, 0, 0))]
    n_pages = _pages_at_most(window, pages_per_seq, page_size, Rb)
    rows = lambda i, *_: (0, i, 0)                          # noqa: E731
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    group_buf = lambda D: pltpu.VMEM(                       # noqa: E731
        (2, Hkv, group * page_size, D), k_pages.dtype)
    live = C * n_pages * page_size
    width = Dk if fused else Dk + Dv       # elements a key moves and meets
    params = {} if vmem_limit_bytes is None else {
        "compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes)}
    out = pl.pallas_call(
        functools.partial(_prefill_paged_kernel, group=group, edge=edge,
                          n_pool=P_pool,
                          page_size=page_size, sm_scale=sm_scale,
                          window=window, sinks=sinks is not None,
                          fused=fused),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_blk,),
            in_specs=[
                pl.BlockSpec((Hkv, M, Dk), rows),
                pl.BlockSpec((M, 1), lambda i, *_: (i, 0)),
                *extra_specs,
                *(in_hbm for _ in pools),
            ],
            out_specs=pl.BlockSpec((Hkv, M, Dv), rows),
            scratch_shapes=[
                *(group_buf(p.shape[-1]) for p in pools),
                pltpu.SemaphoreType.DMA((len(pools), 2, group)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((Hkv, M, Dv), jnp.float32),
                pltpu.VMEM((Hkv, M, 1), jnp.float32),
                pltpu.VMEM((Hkv, M, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Hkv, C * G, Dv), q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * live * Hq * width,
            bytes_accessed=(q.size + C * Hq * Dv + n_blk * n_pages * Hkv
                            * page_size * width) * q.dtype.itemsize,
            transcendentals=live * Hq),
        name=_kernel_name("gqa_prefill_paged", window, sinks),
        interpret=interpret,
        **params,
    )(*chunk_walk_bounds(kv_len, Rb), block_table, layer, q_hm, kl_rows,
      *extra, *pools)
    return out.reshape(Hkv, C, G, Dv).swapaxes(0, 1).reshape(C, Hq, Dv)


def paged_kv_write(k_pages: jax.Array, v_pages: jax.Array,
                   k_new: jax.Array, v_new: jax.Array,
                   block_table: jax.Array, pos: jax.Array,
                   active: jax.Array | None = None, layer=None,
                   shared_table: bool = False
                   ) -> tuple[jax.Array, jax.Array]:
    """Put one new (k, v) row per batch slot into the page pool:
    page ``block_table[b, pos_b // page_size]``, row ``pos_b % page_size``.

    k/v_pages [P, Hkv, page_size, D], or with ``layer`` (a traced or Python
    int) the whole stacked pool [L, P, Hkv, page_size, D], written IN PLACE
    at ``pool[layer]`` and returned whole; k/v_new [B, Hkv, D]; pos [B]
    int32.
    ``active`` [B] bool (optional) PARKS the write of masked-off rows on
    the scratch page (page 0, the id the serving engine reserves): a slot
    frozen mid-scan by the multi-token decode loop (done on EOS/budget, or
    clamped by page capacity) keeps computing, but its writes can never
    land on a live sequence's page — the device-side twin of the engine's
    host-side slot parking. Rows whose block-table lookup walks past the
    owned pages hit the row's fill id (0, same scratch page) either way.

    The speculative verify dispatch (ISSUE 20) reuses both behaviors
    with B*K rows per slot: row (b, i) writes its draft's KV at
    ``pos_b + i`` (beyond-limit rows park on the scratch page), and a
    rejected suffix's rows simply become garbage past the accepted
    cursor — overwritten by the next dispatch's writes before any read,
    the same argument that makes in-page padding tails safe.

    Either form writes through the pool's row-major 2-D view
    ``[(L*)P*Hkv*page_size, D]`` (the reshape is a bitcast, and a 2-D array
    leaves the TPU layout pass nothing to choose), so the pool keeps the
    one layout ``gqa_decode_paged`` reads. The window scatter
    ``pool.at[(layer,) page, :, slot].set(new)`` writes the same rows, but
    makes the compiler hold the pool slot-major of head and re-lay it out
    around every kernel call: half of the decode program's device time
    until PR 25 (``tests/test_aot_topology.py`` holds the compiled programs
    to "no pool-shaped copy"). Which form follows what the rows ARE:

    - rows of DIFFERENT sequences (``shared_table`` False: decode slots,
      speculative verify rows, the sharded decode paths): a scatter of
      ``B * Hkv`` rows of the view, 0.07 us a row on a v5e whatever the
      row's width.
    - ONE sequence's RUN (``shared_table``: a prefill chunk; every row of
      ``block_table`` is the same row, every active row i sits at
      ``pos[i] = p + i`` for one ``p``, wrapped by the caller where the
      table is a ring): a
      page of one layer is ``Hkv * page_size`` CONSECUTIVE rows of the
      view, and C positions touch at most ``(C - 2) // page_size + 2``
      pages, so the rows land a PAGE at a time (``_write_run``): a page is
      read, the run's rows are selected into it, the page is written
      back. Masked-off rows write NOTHING there (the scratch page keeps
      its bytes, which are unspecified and never read): every page but
      page 0 holds what the scatter would have left, bit for bit.

    Both keep the window scatter's rule for stray page ids: one outside
    the pool drops the write, a negative one counts from the end.
    ``v_pages`` None: ``k_pages`` holds ``[K | V]`` rows; the row written
    is ``[k_new | v_new]`` and the result ``(pool, None)``. Keys and values
    may differ in width (k_new [B, Hkv, Dk], v_new [B, Hkv, Dv]): a row's
    index in the two 2-D views is the same.
    """
    assert (layer is not None) == (k_pages.ndim == 5), (
        "layer= goes with a stacked [L, P, Hkv, page_size, D] pool")
    if v_pages is None:
        # ONE pool of ``[K | V]`` rows (``gqa_decode_paged``): one write
        pools, news = (k_pages,), (jnp.concatenate([k_new, v_new], -1),)
    else:
        assert k_pages.shape[:-1] == v_pages.shape[:-1], (k_pages.shape,
                                                          v_pages.shape)
        pools, news = (k_pages, v_pages), (k_new, v_new)
    if shared_table:
        out = _write_run(pools, news, block_table, pos, active, layer)
    else:
        idx = _page_row_index(k_pages.shape, block_table, pos, active, layer)
        out = tuple(_write_rows(p, n, idx) for p, n in zip(pools, news))
    return out if v_pages is not None else (out[0], None)


def _page_row_index(pool_shape, block_table, pos, active, layer):
    """Rows of a pool's row-major 2-D view that ``paged_kv_write`` writes:
    [B * Hkv] int32, one per (batch slot, head); a write that must be
    dropped gets the index one past the last row."""
    B = pos.shape[0]
    P_pool, Hkv, page_size, D = pool_shape[-4:]
    n_rows = math.prod(pool_shape) // D
    page = block_table[jnp.arange(B), pos // page_size]     # [B]
    if active is not None:
        page = jnp.where(active, page, 0)
    page = jnp.where(page < 0, page + P_pool, page)
    in_pool = jnp.logical_and(page >= 0, page < P_pool)
    if layer is not None:
        page = jnp.asarray(layer, jnp.int32) * P_pool + page
    # row of (page, head h, slot) = (page * Hkv + h) * page_size + slot
    idx = ((page * Hkv)[:, None] + jnp.arange(Hkv, dtype=jnp.int32)
           ) * page_size + (pos % page_size)[:, None]       # [B, Hkv]
    return jnp.where(in_pool[:, None], idx, n_rows).reshape(B * Hkv)


def _write_rows(pool, new, idx):
    D = pool.shape[-1]
    return pool.reshape(-1, D).at[idx].set(
        new.reshape(-1, D), mode="drop").reshape(pool.shape)


def _write_run(pools, news, block_table, pos, active, layer):
    """``paged_kv_write`` for ONE sequence's run of positions, into every
    pool of ``pools`` (same ``[(L,) P, Hkv, page_size]``, any width). Each
    page the run touches (C positions that start on a page's last row reach
    ``(C - 2) // page_size + 2``) is read, its live rows replaced head-major
    as the page holds them, and written back as ``Hkv * page_size`` rows of
    the 2-D view; the loop ends with the last page that has a live row. A
    visit's page is that of its first live row; a visit with none or with a
    stray id is aimed at page 0 and changes nothing. Two things the speed
    depends on (PERF.md section 6, PR 47): the rows stay the 2-D ``[C, Hkv *
    D]`` their projection yields until a visit slices its page's worth (a
    head-major copy of all of them makes the compiler re-lay out the
    projection's weights), and a page's first row is an UNSIGNED product
    with ``Hkv * page_size`` made inside the loop, which the compiler knows
    aligned and never wraps."""
    C = pos.shape[0]
    P_pool, Hkv, page_size = pools[0].shape[-4:-1]
    V = (C + page_size - 2) // page_size + 1
    live = jnp.ones((C,), jnp.bool_) if active is None else active
    first = jnp.argmax(live).astype(jnp.int32)
    off = (pos[first] - first) % page_size
    # visit j holds positions [j * page_size - off, (j + 1) * page_size - off)
    # of the run: its mask, its first live row, that row's page
    mask = lax.dynamic_update_slice(
        jnp.zeros((V * page_size,), jnp.bool_), live, (off,)
    ).reshape(V, page_size)
    visits = jnp.arange(V, dtype=jnp.int32)
    row = jnp.clip(visits * page_size - off
                   + jnp.argmax(mask, 1).astype(jnp.int32), 0, C - 1)
    page = block_table[row, pos[row] // page_size]           # [V]
    page = jnp.where(page < 0, page + P_pool, page)
    visited = mask.any(1) & (page >= 0) & (page < P_pool)
    page = jnp.where(visited, page, 0)
    if layer is not None:
        page = jnp.asarray(layer, jnp.int32) * P_pool + page
    page = page.astype(jnp.uint32)
    keep = (mask & visited[:, None])[:, None, :, None]      # [V, 1, page, 1]
    # a page of padding before the rows and the last visit's worth behind
    rows_of = [jnp.pad(new.reshape(C, -1),
                       ((page_size, V * page_size - C), (0, 0)))
               for new in news]

    def visit(j, flat):
        out = []
        for view, rows in zip(flat, rows_of):
            D = view.shape[-1]
            at = (page[j] * (Hkv * page_size), jnp.uint32(0))
            mine = lax.dynamic_slice_in_dim(
                rows, (j + 1) * page_size - off, page_size
            ).reshape(page_size, Hkv, D).swapaxes(0, 1)
            old = lax.dynamic_slice(view, at, (Hkv * page_size, D))
            out.append(lax.dynamic_update_slice(view, jnp.where(
                keep[j], mine, old.reshape(Hkv, page_size, D)
            ).reshape(Hkv * page_size, D), at))
        return tuple(out)

    flat = lax.fori_loop(
        0, jnp.max(jnp.where(visited, visits + 1, 0)), visit,
        tuple(p.reshape(-1, p.shape[-1]) for p in pools))
    return tuple(f.reshape(p.shape) for f, p in zip(flat, pools))


def paged_rows_write(pool: jax.Array, new: jax.Array,
                     block_table: jax.Array, pos: jax.Array,
                     active: jax.Array | None = None, layer=None
                     ) -> jax.Array:
    """``paged_kv_write`` for a pool that holds ONE row a token: pool
    [(L,) P, page_size, W] (a latent cache: no head dim, keys and values in
    one row), new [B, W]. Same row scatter, same rules for ``active``,
    ``layer`` and stray page ids."""
    assert (layer is not None) == (pool.ndim == 4), (
        "layer= goes with a stacked [L, P, page_size, W] pool")
    headed = pool.shape[:-2] + (1,) + pool.shape[-2:]        # Hkv = 1
    idx = _page_row_index(headed, block_table, pos, active, layer)
    return _write_rows(pool, new, idx)


def _combine_kernel(outs_ref, lses_ref, out_ref):
    """Inter-rank lse-weighted merge (analog of
    kernel_inter_rank_gqa_fwd_batch_decode_combine_kv,
    flash_decode.py:481-566). Grid (B,): merge R partials for one batch."""
    outs = outs_ref[:, 0].astype(jnp.float32)       # [R, Hq, D]
    lses = lses_ref[:, 0, :, 0:1].astype(jnp.float32)  # [R, Hq, 1]
    m = jnp.max(lses, axis=0)                        # [Hq, 1]
    w = jnp.exp(lses - m[None])                      # [R, Hq, 1]
    denom = jnp.sum(w, axis=0)                       # [Hq, 1]
    denom = jnp.where(denom > 0, denom, 1.0)
    merged = jnp.sum(outs * w, axis=0) / denom       # [Hq, D]
    out_ref[0] = merged.astype(out_ref.dtype)


def decode_combine(partial_outs: jax.Array, partial_lses: jax.Array):
    """partial_outs [R, B, Hq, D], partial_lses [R, B, Hq, 128] →
    merged [B, Hq, D]."""
    R, B, Hq, D = partial_outs.shape
    return pl.pallas_call(
        _combine_kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((R, 1, Hq, D), lambda b: (0, b, 0, 0)),
            pl.BlockSpec((R, 1, Hq, 128), lambda b: (0, b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), partial_outs.dtype),
        interpret=default_interpret(),
    )(partial_outs, partial_lses)


def _ll_ag_merge_kernel(axis, mesh_axes, D, out_dtype,
                        part_ref, out_ref, ws_ref, bufs, obuf,
                        csems, send_sems, recv_sems):
    """Fused low-latency partial-AG + lse-merge (the decode critical path).

    Replaces the generic AG kernel + separate combine kernel with ONE
    kernel: put my packed partial (out ‖ lse, f32) to every peer (my own
    segment reads part_ref directly — no ws round-trip), then stream the
    online lse-merge over partials in CANONICAL rank order (seg 0..n-1),
    each segment waited once and prefetched into a VMEM double buffer
    behind the previous segment's merge math. Canonical order makes the fp32 accumulation identical on every
    rank, so the P(None) "replicated" output is bitwise consistent across
    devices (a swizzled start-local order would merge in a different order
    per rank and drift in the low bits, compounding across autoregressive
    steps). The merge math is the running (max, denom, acc) rescaling —
    the same online softmax the reference's inter-rank combine uses
    (kernel_inter_rank_gqa_fwd_batch_decode_combine_kv,
    flash_decode.py:481-566), fused behind the transport like the
    reference's LL allgather layer (low_latency_allgather.py:531-621,
    sp_flash_decode_layer.py:108-125).

    The entry barrier is required: the ws arrival buffer address is reused
    across calls by XLA, so without it a fast peer's call-k+1 put could
    overwrite a slot this rank's call-k merge has not read yet.
    """
    me = shd.my_pe(axis)
    n = shd.n_pes(axis)
    shd.barrier_all((axis,), mesh_axes=mesh_axes)

    rdmas = []
    for p in range(1, n):
        dst = lax.rem(me + p, n)
        pid = shd.pe_at(mesh_axes, axis, dst)
        rdmas.append(shd.putmem_nbi(ws_ref.at[me], part_ref,
                                    send_sems.at[dst], recv_sems.at[me], pid))

    # Double-buffered VMEM prefetch with own-segment bypass: segment `me`
    # reads part_ref directly (our ws slot is never written — the ws
    # round-trip the first version paid is gone), and segment seg+1's
    # HBM→VMEM fetch rides behind segment seg's VPU merge.
    def fetch(seg, slot):
        @pl.when(seg == me)
        def _():
            pltpu.make_async_copy(part_ref, bufs.at[slot],
                                  csems.at[slot]).start()

        @pl.when(seg != me)
        def _():
            shd.wait_recv(ws_ref.at[seg], recv_sems.at[seg])
            pltpu.make_async_copy(ws_ref.at[seg], bufs.at[slot],
                                  csems.at[slot]).start()

    fetch(0, 0)
    acc = m = denom = None
    for seg in range(n):
        slot = seg % 2
        if seg + 1 < n:
            fetch(seg + 1, (seg + 1) % 2)
        pltpu.make_async_copy(bufs.at[slot], bufs.at[slot],
                              csems.at[slot]).wait()
        x = bufs[slot]
        o, lse = x[..., :D], x[..., D:D + 1]   # [B*Hq,D], [B*Hq,1]
        if seg == 0:
            acc, m, denom = o, lse, jnp.ones_like(lse)
        else:
            new_m = jnp.maximum(m, lse)
            scale = jnp.exp(m - new_m)
            w = jnp.exp(lse - new_m)
            acc = acc * scale + o * w
            denom = denom * scale + w
            m = new_m

    obuf[...] = (acc / jnp.where(denom > 0, denom, 1.0)).astype(out_dtype)
    pltpu.sync_copy(obuf, out_ref)   # ANY-space outputs need a DMA store
    shd.quiet(*rdmas)


def ll_ag_merge(ctx: ShmemContext, packed: jax.Array, D: int,
                out_dtype, axis: str):
    """Host wrapper for the fused partial-AG + merge. ``packed`` is
    [n, B, Hq, D+128] f32 sharded P(axis) (rank dim leading); returns
    merged [B, Hq, D] replicated."""
    if not default_interpret() and D % 128:
        raise ValueError(
            f"fused SP decode on compiled TPU needs a lane-multiple head "
            f"dim: head_dim={D} (Mosaic tiles lanes by 128 — the packed "
            "(out ‖ lse) wire slices would be unaligned; the interpret-"
            "mode simulator does not enforce this)")
    n = ctx.axis_size(axis)
    mesh_axes = ctx.axis_names

    def f(pk):
        B, Hq, W = pk.shape[1:]
        # flatten to 2-D rows: [B*Hq, W] keeps the sublane (second-minor)
        # dim a row count Mosaic tiles cleanly; a 3-D [B, Hq<8, W] buffer
        # silently mislays rows in VMEM↔HBM DMAs on real chips
        R = B * Hq
        kernel = lambda *refs: _ll_ag_merge_kernel(
            axis, mesh_axes, D, out_dtype, *refs)
        out, _ws = pl.pallas_call(
            kernel,
            out_shape=(
                jax.ShapeDtypeStruct((R, D), out_dtype),
                jax.ShapeDtypeStruct((n, R, W), pk.dtype),  # arrival ws
            ),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[
                pltpu.VMEM((2, R, W), pk.dtype),   # prefetch double buffer
                pltpu.VMEM((R, D), out_dtype),
                pltpu.SemaphoreType.DMA((2,)),     # prefetch copy sems
                pltpu.SemaphoreType.DMA((n,)),
                pltpu.SemaphoreType.DMA((n,)),
            ],
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True,
                collective_id=collective_id_for(f"ll_ag_merge_{axis}")),
            interpret=default_interpret(),
        )(pk[0].reshape(R, W))  # local block is [1, B, Hq, W]
        return out.reshape(B, Hq, D)

    sm = ctx.shard_map(f, in_specs=P(axis), out_specs=P(None))
    return sm(packed)


def sp_gqa_flash_decode(ctx: ShmemContext, q: jax.Array, k_cache: jax.Array,
                        v_cache: jax.Array, global_kv_lens: jax.Array,
                        axis: str | None = None, block_s: int = 128,
                        ag_method: str = "fused") -> jax.Array:
    """Sequence-parallel distributed flash-decode
    (analog of SpGQAFlashDecodeAttention.forward,
    sp_flash_decode_layer.py:78-184):

    1. per-rank split-KV decode over the local KV shard,
    2. low-latency AllGather of the partial (out ‖ lse),
    3. inter-rank lse-weighted combine.

    q [B, Hq, D] replicated; k_cache/v_cache [B, Hkv, n*S_local, D] sharded
    P(None, None, axis) on S; global_kv_lens [B] total valid keys. Returns
    [B, Hq, D] replicated. Golden: dense softmax attention over the full
    cache."""
    axis = axis or ctx.axis_names[0]
    n = ctx.axis_size(axis)
    B, Hq, D = q.shape
    S = k_cache.shape[2]
    assert S % n == 0
    s_local = S // n

    def local(q, k_shard, v_shard, kv_lens):
        me = lax.axis_index(axis)
        local_len = jnp.clip(kv_lens - me * s_local, 0, s_local)
        out_p, lse_p = gqa_decode_partial(q, k_shard, v_shard,
                                          local_len.astype(jnp.int32),
                                          block_s=block_s)
        return out_p[None], lse_p[None]   # add rank dim for the gather

    def local_packed(q, k_shard, v_shard, kv_lens):
        out_p, lse_p = local(q, k_shard, v_shard, kv_lens)
        # one wire payload (out ‖ lse), f32, like the reference's fused
        # partial buffer (sp_flash_decode_layer.py:134-137)
        return jnp.concatenate(
            [out_p.astype(jnp.float32), lse_p], axis=-1)

    sm = ctx.shard_map(local_packed,
                       in_specs=(P(), P(None, None, axis),
                                 P(None, None, axis), P()),
                       out_specs=P(axis))
    packed = sm(q, k_cache, v_cache, global_kv_lens)   # [n, B, Hq, D+128]

    if ag_method == "fused":
        # latency path: one kernel does the partial AG and the streaming
        # lse-merge (no gathered HBM round-trip, no second kernel launch)
        return ll_ag_merge(ctx, packed, D, q.dtype, axis)

    g = all_gather(ctx, packed, axis=axis, method=ag_method)

    def merge(pk):
        return decode_combine(pk[..., :D].astype(q.dtype), pk[..., D:])

    smc = ctx.shard_map(merge, in_specs=P(None), out_specs=P(None))
    return smc(g)


def _pool_ag_kernel(axis, mesh_axes, k_ref, v_ref, kf_ref, vf_ref,
                    send_sems, recv_sems, sig):
    """Signal-gated start-local pool allgather (the SP half of the ISSUE 16
    overlap schedule — the reference ``allgather_gemm.py`` tile-swizzle
    "start local" idiom, restricted to the transport).

    The rank's OWN pool slice is copied into its canonical slot of the full
    pool FIRST, with no gate — it is ready while every remote shard is
    still in flight, so the consumer's paged-attention walk can begin
    issuing its earliest (local-page) reads immediately after this kernel.
    Remote shards are put to each peer's canonical slot and announced with
    one counted ``signal_op`` (``ops/page_migrate.py``'s protocol); the
    consumer gates on the aggregate count and drains arrivals in FIXED
    rank order. The assembled pool is a pure page-order concatenation —
    bitwise identical to ``lax.all_gather(tiled=True)`` — so the attention
    walk that follows keeps its single-device reduction order untouched.
    Overlap moves the SCHEDULE (local slice never waits on the wire),
    never the reduction order."""
    me = shd.my_pe(axis)
    n = shd.n_pes(axis)
    p_local = k_ref.shape[0]
    shd.barrier_all((axis,), mesh_axes=mesh_axes)
    # start local: own slice lands while the remote puts are in flight
    lk = pltpu.make_async_copy(
        k_ref, kf_ref.at[pl.ds(me * p_local, p_local)], recv_sems.at[0, me])
    lv = pltpu.make_async_copy(
        v_ref, vf_ref.at[pl.ds(me * p_local, p_local)], recv_sems.at[1, me])
    lk.start()
    lv.start()
    rdmas = []
    for p in range(1, n):
        dst = lax.rem(me + p, n)
        pid = shd.pe_at(mesh_axes, axis, dst)
        rdmas.append(shd.putmem_nbi(
            kf_ref.at[pl.ds(me * p_local, p_local)], k_ref,
            send_sems.at[0, dst], recv_sems.at[0, me], pid))
        rdmas.append(shd.putmem_nbi(
            vf_ref.at[pl.ds(me * p_local, p_local)], v_ref,
            send_sems.at[1, dst], recv_sems.at[1, me], pid))
        # announce my shard to the peer the moment its puts are in flight
        shd.signal_op(sig, 1, pe=pid)
    lk.wait()
    lv.wait()
    if n > 1:
        shd.signal_wait_until(sig, n - 1)
        for p in range(1, n):
            src = lax.rem(me + p, n)
            shd.wait_recv(kf_ref.at[pl.ds(src * p_local, p_local)],
                          recv_sems.at[0, src])
            shd.wait_recv(vf_ref.at[pl.ds(src * p_local, p_local)],
                          recv_sems.at[1, src])
    shd.quiet(*rdmas)


def pool_ag_start_local(ctx: ShmemContext, k_pages: jax.Array,
                        v_pages: jax.Array, axis: str = "sp"):
    """Host wrapper for the start-local pool allgather: global pools
    [P, Hkv, page_size, D] sharded P(axis) on the page dim in; FULL pools
    (replicated) out, assembled in canonical page order — bitwise identical
    to the tiled ``lax.all_gather`` concatenation the non-overlapped SP
    path uses (the DCN fallback IS that all_gather). One kernel moves
    both pools so K and V ride the wire together."""
    from triton_dist_tpu.ops.all_to_all import _xla_wire
    n = ctx.axis_size(axis)
    if n == 1:
        return k_pages, v_pages
    mesh_axes = ctx.axis_names

    if _xla_wire(ctx, axis):
        def f(kp_l, vp_l):
            return (lax.all_gather(kp_l, axis, axis=0, tiled=True),
                    lax.all_gather(vp_l, axis, axis=0, tiled=True))
        return ctx.shard_map(f, in_specs=(P(axis), P(axis)),
                             out_specs=(P(None), P(None)))(k_pages, v_pages)

    def f(kp_l, vp_l):
        kernel = lambda *refs: _pool_ag_kernel(axis, mesh_axes, *refs)
        full_k = jax.ShapeDtypeStruct((n * kp_l.shape[0],) + kp_l.shape[1:],
                                      kp_l.dtype)
        full_v = jax.ShapeDtypeStruct((n * vp_l.shape[0],) + vp_l.shape[1:],
                                      vp_l.dtype)
        return pl.pallas_call(
            kernel,
            out_shape=(full_k, full_v),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),) * 2,
            scratch_shapes=[
                pltpu.SemaphoreType.DMA((2, n)),
                pltpu.SemaphoreType.DMA((2, n)),
                pltpu.SemaphoreType.REGULAR,
            ],
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True,
                collective_id=collective_id_for(f"pool_ag_{axis}")),
            interpret=default_interpret(),
        )(kp_l, vp_l)

    return ctx.shard_map(f, in_specs=(P(axis), P(axis)),
                         out_specs=(P(None), P(None)))(k_pages, v_pages)


def sp_paged_attend_write(ctx: ShmemContext, q: jax.Array,
                          k_new: jax.Array, v_new: jax.Array,
                          k_pages: jax.Array, v_pages: jax.Array,
                          block_table: jax.Array, pos: jax.Array,
                          kv_len: jax.Array, axis: str = "sp",
                          active: jax.Array | None = None,
                          overlap: bool = False):
    """Sequence-parallel paged write + paged GQA decode attention: the page
    pool is sharded over ``axis`` on the PAGE dim (``page_pool_pspec``),
    rank r owning pages ``[r*Pl, (r+1)*Pl)``.

    Per rank: scatter the new (k, v) rows that land on LOCALLY-owned pages
    (non-local rows drop via an out-of-bounds index with ``mode="drop"`` —
    every row is written by exactly one rank), then allgather the pool
    shards back to the full pool and run the replicated ``gqa_decode_paged``
    walk over it. The allgather is a pure concatenation in page order, so
    the gathered pool — and therefore the attention output — is BITWISE
    identical to the single-device ``paged_kv_write`` + ``gqa_decode_paged``
    composition at any mesh size (tests/test_sharded_serving.py pins this).
    The write bandwidth is what shards; attention reads stay replicated —
    the regime where pool residency, not attention FLOPs, is the scaling
    limit (one new KV row per slot per step).

    ``active`` parks masked-off rows on the scratch page (page 0, rank 0's
    shard) exactly like ``paged_kv_write``. q [B, Hq, D]; k/v_new
    [B, Hkv, D]; k/v_pages [P, Hkv, page_size, D] GLOBAL views sharded
    P(axis); pos/kv_len [B]. Returns (attn [B, Hq, D], k_pages, v_pages)
    with the pools still P(axis)-sharded.

    ``overlap=True`` swaps the tiled ``lax.all_gather`` for the
    signal-gated start-local assembly (``pool_ag_start_local``): the
    rank's own pool slice lands in the full pool without waiting on the
    wire and remote slices are gated per-shard by counted signals —
    ISSUE 16's SP overlap. The assembled pool is a page-order
    concatenation either way, so the attention output is BITWISE identical
    to ``overlap=False`` (only the transport schedule differs).
    """
    n = ctx.axis_size(axis)
    if n == 1:
        def single(q, kn, vn, kp, vp, bt, pos, kv_len, *act):
            kp, vp = paged_kv_write(kp, vp, kn, vn, bt, pos,
                                    active=act[0] if act else None)
            out, _ = gqa_decode_paged(q, kp, vp, bt, kv_len)
            return out, kp, vp

        args = (q, k_new, v_new, k_pages, v_pages, block_table, pos, kv_len)
        if active is not None:
            args += (active,)
        if ctx.num_ranks == 1:
            return single(*args)
        # |axis| = 1 on a wider mesh (1x1x2): the kernel still runs under
        # shard_map, every rank on its replica — a pallas_call left to the
        # SPMD partitioner is a replicated side-effecting op, which it
        # refuses for interpret-mode kernels
        return ctx.shard_map(single, in_specs=(P(),) * len(args),
                             out_specs=(P(), P(), P()))(*args)

    assert k_pages.shape[0] % n == 0, (
        f"pool pages {k_pages.shape[0]} not divisible by |{axis}|={n} — "
        "pad the pool to a multiple of the SP axis (the sharded engine "
        "does this; the allocator never hands out the padding pages)")
    has_active = active is not None

    def write_shard(kp_l, vp_l, kn, vn, bt, pos, *act):
        r = lax.axis_index(axis)
        p_local = kp_l.shape[0]
        page_size = kp_l.shape[2]
        rows = jnp.arange(pos.shape[0])
        page = bt[rows, pos // page_size]                   # [B] global ids
        if has_active:
            page = jnp.where(act[0], page, 0)
        loc = page - r * p_local
        ok = (loc >= 0) & (loc < p_local)
        idx = jnp.where(ok, loc, p_local)    # OOB sentinel → dropped write
        slot = pos % page_size
        kp_l = kp_l.at[idx, :, slot].set(kn, mode="drop")
        vp_l = vp_l.at[idx, :, slot].set(vn, mode="drop")
        return kp_l, vp_l

    if overlap:
        smw = ctx.shard_map(
            write_shard,
            in_specs=(P(axis), P(axis)) + (P(),) * (4 + int(has_active)),
            out_specs=(P(axis), P(axis)))
        wargs = (k_pages, v_pages, k_new, v_new, block_table, pos)
        if has_active:
            wargs += (active,)
        kp, vp = smw(*wargs)
        kf, vf = pool_ag_start_local(ctx, kp, vp, axis=axis)
        smo = ctx.shard_map(
            lambda q, kf, vf, bt, kl: gqa_decode_paged(q, kf, vf, bt, kl)[0],
            in_specs=(P(),) * 5, out_specs=P())
        return smo(q, kf, vf, block_table, kv_len), kp, vp

    def body(kp_l, vp_l, q, kn, vn, bt, pos, kv_lens, *act):
        kp_l, vp_l = write_shard(kp_l, vp_l, kn, vn, bt, pos, *act)
        # tiled page-dim allgather = exact concatenation of the shards
        kf = lax.all_gather(kp_l, axis, axis=0, tiled=True)
        vf = lax.all_gather(vp_l, axis, axis=0, tiled=True)
        out, _ = gqa_decode_paged(q, kf, vf, bt, kv_lens)
        return out, kp_l, vp_l

    sm = ctx.shard_map(
        body,
        in_specs=(P(axis), P(axis)) + (P(),) * (6 + int(has_active)),
        out_specs=(P(), P(axis), P(axis)))
    args = (k_pages, v_pages, q, k_new, v_new, block_table, pos, kv_len)
    if has_active:
        args += (active,)
    return sm(*args)


# -- distributed flash-decode: one request's KV sharded over the SP mesh ----
#
# `sp_paged_attend_write` shards the pool across REQUESTS: every rank
# allgathers the whole pool and attends over all of it, so one long
# request's attention cost is replicated n times. `flash_decode_dist`
# shards ONE request's pages: each rank walks only the block-table pages
# resident in its pool slice, computes an independent softmax partial PER
# PAGE, announces the partial slab with one-sided puts + a counted
# `signal_op`, and every rank folds all slabs in a single FIXED order.
#
# Why per-PAGE partials (not one per-rank online-softmax partial): the
# fold must be bitwise identical at every mesh size n. A per-rank running
# (m, l, acc) partial bakes the rank's page count into its rounding, so
# merging two ranks' partials ≠ one rank's partial over both slices at the
# last bit. A per-page partial is a pure function of (q, that page's K/V)
# — identical floats no matter which rank computed it — and the fold
# visits pages in block-table order with ranks 0..n-1 interleaved at each
# page, where at most ONE rank's entry per page is real and every other
# entry is the neutral (out=0, lse=NEG_INF) element applied as an EXACT
# no-op (a `where` select of the untouched carry, never an arithmetic
# identity — `acc*1 + 0` can still flip a -0.0). The carry therefore
# walks the same float sequence at n=1, 2, 4, ... for ANY page→rank
# placement, which is also what makes the pool layout (blocked vs
# round-robin interleaved) a pure balance knob. A psum/lse-psum would
# re-associate by rank count — exactly what sigcheck's rank-count-
# dependent-reduction lint rejects — so it is refused by construction.

_FD_EMPTY = NEG_INF / 2  # "no entry" threshold: real lse never gets here


def _fd_partial_kernel(kl_ref, bt_ref, rk_ref, q_ref, k_ref, v_ref,
                       out_ref, lse_ref, *, page_size: int, p_local: int,
                       sm_scale: float, n_kv_heads: int):
    """Grid (B, pages_per_seq): one INDEPENDENT softmax partial per
    block-table page — no carry between steps, so any rank (or any
    distribution of pages over ranks) produces bit-identical entries for
    the pages it owns. Non-local / dead pages emit the neutral element."""
    b = pl.program_id(0)
    s = pl.program_id(1)
    page = bt_ref[b, s]
    base = rk_ref[0] * p_local
    mine = jnp.logical_and(page >= base, page < base + p_local)
    live = jnp.logical_and(mine, s * page_size < kl_ref[b])

    Hq, D = out_ref.shape[2], out_ref.shape[3]
    G = Hq // n_kv_heads
    q = q_ref[0].reshape(n_kv_heads, G, D)
    k = k_ref[0]                                   # [Hkv, page_size, D]
    v = v_ref[0]
    scores = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * sm_scale   # [Hkv, G, ps]
    scores = scores.reshape(Hq, page_size)
    pos = s * page_size + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, 1)
    scores = jnp.where(pos < kl_ref[b], scores, NEG_INF)
    m = jnp.max(scores, axis=1, keepdims=True)     # [Hq, 1]
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=1, keepdims=True)          # [Hq, 1]
    pv = jax.lax.dot_general(
        p.reshape(n_kv_heads, G, page_size).astype(v.dtype), v,
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32).reshape(Hq, D)
    l_safe = jnp.where(l > 0, l, 1.0)
    # keep: a live page always has ≥1 unmasked key, but garbage pool rows
    # under a dead step may be anything — the select (not a multiply)
    # guarantees the neutral entry regardless
    keep = jnp.logical_and(live, l > 0)
    out_ref[0, 0] = jnp.where(keep, pv / l_safe, 0.0)
    lse_ref[0, 0] = jnp.broadcast_to(
        jnp.where(keep, m + jnp.log(l_safe), NEG_INF), lse_ref.shape[2:])


def _fd_page_partials(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                      block_table: jax.Array, kv_len: jax.Array,
                      rank: jax.Array, sm_scale: float | None = None):
    """Per-page partial slab for one rank's pool slice: returns packed
    (out ‖ lse) [B, S, Hq, D+128] f32. ``k_pages``/``v_pages`` are the
    LOCAL slice [p_local, Hkv, page_size, D]; ``block_table`` holds GLOBAL
    device rows — rank r owns rows [r*p_local, (r+1)*p_local)."""
    B, Hq, D = q.shape
    p_local, Hkv, page_size, _ = k_pages.shape
    S = block_table.shape[1]
    sm_scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)

    def page_index(b, s, kl, bt, rk):
        # clamp into the local slice: non-local steps fetch an arbitrary
        # in-bounds page (their compute is discarded by the select)
        loc = bt[b, s] - rk[0] * p_local
        return (jnp.clip(loc, 0, p_local - 1), 0, 0, 0)

    kernel = functools.partial(_fd_partial_kernel, page_size=page_size,
                               p_local=p_local, sm_scale=sm_scale,
                               n_kv_heads=Hkv)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, S),
            in_specs=[
                pl.BlockSpec((1, Hq, D), lambda b, s, kl, bt, rk: (b, 0, 0)),
                pl.BlockSpec((1, Hkv, page_size, D), page_index),
                pl.BlockSpec((1, Hkv, page_size, D), page_index),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, Hq, D),
                             lambda b, s, kl, bt, rk: (b, s, 0, 0)),
                pl.BlockSpec((1, 1, Hq, 128),
                             lambda b, s, kl, bt, rk: (b, s, 0, 0)),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, S, Hq, D), jnp.float32),
            jax.ShapeDtypeStruct((B, S, Hq, 128), jnp.float32),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * B * Hq * S * page_size * D,
            bytes_accessed=q.size + B * S * Hkv * page_size * D * 2,
            transcendentals=B * Hq * S * page_size),
        interpret=default_interpret(),
    )(kv_len, block_table, rank, q, k_pages, v_pages)
    return jnp.concatenate([out, lse], axis=-1)


def _fd_fold(stacked: jax.Array, D: int, out_dtype):
    """Fixed-order fold of page partials: ``stacked`` [T, rows, D+128] in
    fold order (page-major, rank-minor — T = S at n=1, S*n otherwise).
    Neutral entries (lse == NEG_INF) are EXACT no-ops: the carry is passed
    through a select untouched, so the float sequence the carry walks is
    the n=1 page-order sequence at every mesh size. Never a psum."""
    init = (jnp.zeros(stacked.shape[1:-1] + (D,), jnp.float32),
            jnp.full(stacked.shape[1:-1] + (1,), NEG_INF, jnp.float32),
            jnp.zeros(stacked.shape[1:-1] + (1,), jnp.float32))

    def step(carry, x):
        acc, m, denom = carry
        xo, xl = x[..., :D], x[..., D:D + 1]
        empty = xl <= _FD_EMPTY
        new_m = jnp.maximum(m, xl)
        scale = jnp.exp(m - new_m)
        w = jnp.exp(xl - new_m)
        return (jnp.where(empty, acc, acc * scale + xo * w),
                jnp.where(empty, m, new_m),
                jnp.where(empty, denom, denom * scale + w)), None

    (acc, _m, denom), _ = lax.scan(step, init, stacked)
    return (acc / jnp.where(denom > 0, denom, 1.0)).astype(out_dtype)


def _fd_fold_kernel(axis, mesh_axes, S, BH, D, out_dtype,
                    part_ref, out_ref, ws_ref, bufs, obuf,
                    csems, send_sems, recv_sems, sig):
    """One-sided partial exchange + fixed-order page fold (the
    `paged_transport` seg-push idiom): put my page-partial slab to every
    peer and announce it with one counted ``signal_op``; consume peers'
    slabs in CANONICAL rank order, each gated by exactly one announcement
    count plus that slab's delivery credits. My own slab's VMEM fetch is
    UNGATED — local partials land while remote slabs are still in flight
    (overlap the schedule). The fold itself then walks (page s, rank r)
    in the one fixed order shared with the XLA/CPU path — at each page
    exactly one rank's entry is real, the rest are exact no-ops — so the
    reduction order never changes with n (never a psum).

    The entry barrier is required for the same reason as
    ``_ll_ag_merge_kernel``: the ws arrival buffer is reused across calls.
    VMEM note: all n slabs are resident during the fold (n*S*B*Hq*(D+128)
    f32) — fine for decode batches; streaming a per-page double buffer is
    the round-7 lever for 100k-context on-chip runs."""
    me = shd.my_pe(axis)
    n = shd.n_pes(axis)
    shd.barrier_all((axis,), mesh_axes=mesh_axes)

    rdmas = []
    for p in range(1, n):
        dst = lax.rem(me + p, n)
        pid = shd.pe_at(mesh_axes, axis, dst)
        rdmas.append(shd.putmem_nbi(ws_ref.at[me], part_ref,
                                    send_sems.at[dst], recv_sems.at[me],
                                    pid))
        # announce my partial slab the moment its put is in flight
        shd.signal_op(sig, 1, pe=pid)

    def fetch(r):
        @pl.when(r == me)
        def _():
            # own slab: no gate — it never rides the wire
            pltpu.make_async_copy(part_ref, bufs.at[r], csems.at[r]).start()

        @pl.when(r != me)
        def _():
            # exactly the signals this fold step consumes: one partial
            # announcement, then the slab's delivery credits
            shd.signal_wait_until(sig, 1)
            shd.wait_recv(ws_ref.at[r], recv_sems.at[r])
            pltpu.make_async_copy(ws_ref.at[r], bufs.at[r],
                                  csems.at[r]).start()

    # page 0 of the fold touches every rank's slab, so full residency is
    # the minimal wait set; gate in canonical order, fetches overlapping
    fetch(0)
    for r in range(n):
        if r + 1 < n:
            fetch(r + 1)
        pltpu.make_async_copy(bufs.at[r], bufs.at[r], csems.at[r]).wait()

    def fold_step(t, carry):
        acc, m, denom = carry
        r = lax.rem(t, n)
        s = t // n
        x = bufs[r, pl.ds(s * BH, BH), :]
        xo, xl = x[..., :D], x[..., D:D + 1]
        empty = xl <= _FD_EMPTY
        new_m = jnp.maximum(m, xl)
        scale = jnp.exp(m - new_m)
        w = jnp.exp(xl - new_m)
        return (jnp.where(empty, acc, acc * scale + xo * w),
                jnp.where(empty, m, new_m),
                jnp.where(empty, denom, denom * scale + w))

    init = (jnp.zeros((BH, D), jnp.float32),
            jnp.full((BH, 1), NEG_INF, jnp.float32),
            jnp.zeros((BH, 1), jnp.float32))
    acc, _m, denom = lax.fori_loop(0, S * n, fold_step, init)
    obuf[...] = (acc / jnp.where(denom > 0, denom, 1.0)).astype(out_dtype)
    pltpu.sync_copy(obuf, out_ref)   # ANY-space outputs need a DMA store
    shd.quiet(*rdmas)


def flash_decode_dist(ctx: ShmemContext, q: jax.Array,
                      k_new: jax.Array, v_new: jax.Array,
                      k_pages: jax.Array, v_pages: jax.Array,
                      block_table: jax.Array, pos: jax.Array,
                      kv_len: jax.Array, axis: str = "sp",
                      active: jax.Array | None = None):
    """Distributed flash-decode over a page pool sharded on ``axis``: the
    single-request SP axis (ROADMAP item 2). Same contract as
    ``sp_paged_attend_write`` — q [B, Hq, D]; k/v_new [B, Hkv, D];
    k/v_pages [P, Hkv, page_size, D] GLOBAL views sharded P(axis) on the
    page dim; block_table [B, S] DEVICE rows; pos/kv_len [B] — returns
    (attn [B, Hq, D], k_pages, v_pages) with the pools still sharded.

    Unlike ``sp_paged_attend_write`` (pool allgather + replicated walk:
    per-rank attention cost ∝ FULL kv_len), each rank here walks only the
    block-table pages resident in its own slice and ships one packed
    partial slab — per-rank attention compute ∝ kv_len/n, the property
    that makes 64k–100k-token contexts servable. The combine is the
    fixed-order page fold (see the section comment above): bitwise
    identical at any n and any page→rank placement, so the n=1 route —
    which runs the SAME per-page partial + fold math — IS the golden.
    """
    n = ctx.axis_size(axis)
    B, Hq, D = q.shape
    S = block_table.shape[1]

    if n == 1:
        kp, vp = paged_kv_write(k_pages, v_pages, k_new, v_new,
                                block_table, pos, active=active)
        packed = _fd_page_partials(q, kp, vp, block_table, kv_len,
                                   jnp.zeros((1,), jnp.int32))
        stacked = packed.transpose(1, 0, 2, 3).reshape(S, B * Hq, D + 128)
        return _fd_fold(stacked, D, q.dtype).reshape(B, Hq, D), kp, vp

    assert k_pages.shape[0] % n == 0, (
        f"pool pages {k_pages.shape[0]} not divisible by |{axis}|={n} — "
        "pad the pool to a multiple of the SP axis (the sharded engine "
        "does this; the allocator never hands out the padding pages)")
    from triton_dist_tpu.ops.all_to_all import _xla_wire
    wire_xla = _xla_wire(ctx, axis)
    if not wire_xla and not default_interpret() and D % 128:
        raise ValueError(
            f"flash_decode_dist on compiled TPU needs a lane-multiple "
            f"head dim: head_dim={D} (the packed (out ‖ lse) slab slices "
            "would be unaligned on the wire)")
    mesh_axes = ctx.axis_names
    has_active = active is not None
    BH = B * Hq
    W = D + 128

    def f(kp_l, vp_l, q, kn, vn, bt, pos, kl, *act):
        r = lax.axis_index(axis)
        p_local = kp_l.shape[0]
        page_size = kp_l.shape[2]
        # scatter the new rows that land on locally-owned pages (the
        # sp_paged_attend_write OOB-drop idiom: every row written once)
        rows = jnp.arange(pos.shape[0])
        page = bt[rows, pos // page_size]
        if has_active:
            page = jnp.where(act[0], page, 0)
        loc = page - r * p_local
        ok = (loc >= 0) & (loc < p_local)
        idx = jnp.where(ok, loc, p_local)   # OOB sentinel → dropped write
        slot = pos % page_size
        kp_l = kp_l.at[idx, :, slot].set(kn, mode="drop")
        vp_l = vp_l.at[idx, :, slot].set(vn, mode="drop")

        packed = _fd_page_partials(q, kp_l, vp_l, bt, kl,
                                   r.astype(jnp.int32)[None])
        slab = packed.transpose(1, 0, 2, 3).reshape(S * BH, W)

        if wire_xla:
            g = lax.all_gather(slab, axis, axis=0, tiled=False)
            # reorder to the ONE fold order: page-major, rank-minor
            stacked = g.reshape(n, S, BH, W).transpose(1, 0, 2, 3)
            out = _fd_fold(stacked.reshape(S * n, BH, W), D, q.dtype)
        else:
            kernel = lambda *refs: _fd_fold_kernel(
                axis, mesh_axes, S, BH, D, q.dtype, *refs)
            out, _ws = pl.pallas_call(
                kernel,
                out_shape=(
                    jax.ShapeDtypeStruct((BH, D), q.dtype),
                    jax.ShapeDtypeStruct((n, S * BH, W), slab.dtype),
                ),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=(pl.BlockSpec(memory_space=pl.ANY),) * 2,
                scratch_shapes=[
                    pltpu.VMEM((n, S * BH, W), jnp.float32),
                    pltpu.VMEM((BH, D), q.dtype),
                    pltpu.SemaphoreType.DMA((n,)),   # slab VMEM fetches
                    pltpu.SemaphoreType.DMA((n,)),   # send credits
                    pltpu.SemaphoreType.DMA((n,)),   # delivery credits
                    pltpu.SemaphoreType.REGULAR,     # counted announces
                ],
                compiler_params=pltpu.CompilerParams(
                    has_side_effects=True,
                    collective_id=collective_id_for(f"fd_fold_{axis}")),
                interpret=default_interpret(),
            )(slab)
        return out.reshape(B, Hq, D), kp_l, vp_l

    sm = ctx.shard_map(
        f,
        in_specs=(P(axis), P(axis)) + (P(),) * (6 + int(has_active)),
        out_specs=(P(), P(axis), P(axis)))
    args = (k_pages, v_pages, q, k_new, v_new, block_table, pos, kv_len)
    if has_active:
        args += (active,)
    return sm(*args)


__all__ = ["gqa_decode_partial", "gqa_decode_paged", "paged_kv_write",
           "paged_rows_write",
           "decode_combine", "ll_ag_merge", "sp_gqa_flash_decode",
           "sp_paged_attend_write", "pool_ag_start_local",
           "flash_decode_dist"]
