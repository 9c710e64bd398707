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

The walk is the (row, page) grid that ``ops.flash_decode.gqa_decode_paged``
had until ISSUE 29: pages streamed through the block table out of the stacked
pool in place (``layer`` is a scalar-prefetch operand of the index map),
online softmax, dead pages revisit the last live one (no DMA), compute skipped.

Two things differ, both so that a prefill CHUNK reads each page of its
sequence once a row block and not once a row:

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

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_dist_tpu.utils import default_interpret

NEG_INF = -1e30


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

    @pl.when(s == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)

    for j, page in enumerate(pages):
        start = (s * n_pages + j) * page_size

        @pl.when(start < kl_ref[b])
        def _(page=page, start=start):
            q = q_ref[...]                                  # [M, W]
            kv = page[...]                                  # [page_size, W]
            scores = jax.lax.dot_general(
                q, kv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            pos = start + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            scores = jnp.where(pos < klr_ref[...], scores, NEG_INF)
            m_new = jnp.maximum(m_i[...],
                                jnp.max(scores, axis=1, keepdims=True))
            alpha = jnp.exp(m_i[...] - m_new)
            p = jnp.exp(scores - m_new)                     # [M, page_size]
            l_i[...] = l_i[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
            pv = jnp.dot(p.astype(kv.dtype), kv[:, :latent_dim],
                         preferred_element_type=jnp.float32)
            acc[...] = acc[...] * alpha + pv
            m_i[...] = m_new

    @pl.when(s == pl.num_programs(1) - 1)
    def _():
        l_safe = jnp.where(l_i[...] > 0, l_i[...], 1.0)
        out_ref[...] = (acc[...] / l_safe).astype(out_ref.dtype)


def mla_decode_paged(q: jax.Array, pool: jax.Array, block_table: jax.Array,
                     kv_len: jax.Array, *, layer, latent_dim: int,
                     sm_scale: float, rows_per_block: int = 1,
                     pages_per_step: int = 1) -> jax.Array:
    """q [R, H, W]: per row and head ``[q' | q_rope | 0]`` in the pool's
    stored width W; pool [L, P, page_size, W] (the stacked latent pool,
    read in place at ``layer``); block_table [R, pages_per_seq] int32;
    kv_len [R] int32 (0: the row returns zeros if its whole block is empty,
    else a finite value nobody reads). Returns [R, H, latent_dim]: the
    softmax-weighted mean of the cached ``c`` rows, per head, still to be
    up-projected by ``W_uv``.

    Rows ``[i * rows_per_block, (i + 1) * rows_per_block)`` must share one
    block-table row (that of the first). Entries past a row block's live
    pages may be arbitrary: the index map never dereferences them."""
    R, H, W = q.shape
    L, P_pool, page_size, W_pool = pool.shape
    assert W == W_pool and latent_dim <= W, (q.shape, pool.shape)
    assert latent_dim % 128 == 0 and W % 128 == 0, (
        "the value slice and the stored row are lane-aligned")
    Rb, N = rows_per_block, pages_per_step
    assert R % Rb == 0, f"{R} rows in blocks of {Rb}"
    n_blk, M = R // Rb, Rb * H
    S = block_table.shape[1]
    n_steps = -(-S // N)
    kv_len = kv_len.astype(jnp.int32)
    kl_blk = kv_len.reshape(n_blk, Rb).max(axis=1)
    bt_blk = block_table[::Rb]
    kl_rows = jnp.repeat(kv_len, H)[:, None]                # [R * H, 1]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def page_index(j):
        def index(b, s, kl, bt, ly):
            last = jnp.maximum((kl[b] + page_size - 1) // page_size - 1, 0)
            page = bt[b, jnp.minimum(s * N + j, last)]
            return (ly[0], jnp.clip(page, 0, P_pool - 1), 0, 0)
        return index

    rows = lambda b, s, kl, bt, ly: (b, 0)                  # noqa: E731
    kernel = functools.partial(_mla_kernel, n_pages=N, page_size=page_size,
                               latent_dim=latent_dim, sm_scale=sm_scale)
    live = R * S * page_size
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
        cost_estimate=pl.CostEstimate(
            flops=2 * live * H * (W + latent_dim),
            bytes_accessed=(q.size + n_blk * S * page_size * W)
            * q.dtype.itemsize,
            transcendentals=live * H),
        name="mla_decode_paged",
        interpret=default_interpret(),
    )(kl_blk, bt_blk, layer, q.reshape(R * H, W), kl_rows, *([pool] * N))
    return out.reshape(R, H, latent_dim)


__all__ = ["mla_decode_paged"]
