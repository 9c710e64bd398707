"""ONE CHIP'S SHARE of a routed expert layer (sigmoid or softmax scores): what
the families that hold ``n_held`` of a layer's routed experts (``models.mla``,
``models.window_moe``, ``models.linear_attn_moe``, ``models.short_conv_moe``)
have in common. The chip routes over ALL the experts the router scores and
computes the part of the routed sum its own experts give; what the absent
experts would add is left out, with no exchange and nothing that stands in
for one. The WHOLE layer is the share of one (``n_held`` = the router's
width, ``first_held`` 0): every pick is held.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

# per-dispatch counters of a held-expert layer (``PagedFamily.counters``):
# routed assignments of live rows that landed on held experts, and held
# experts with at least one live row, each summed over layers and inner steps
COUNTERS = ("moe_local_rows", "moe_experts_touched")


def sigmoid_route(h: jax.Array, w_router: jax.Array, topk: int, bias=None,
                  scale: float = 1.0, eps: float = 1e-20
                  ) -> tuple[jax.Array, jax.Array]:
    """(expert ids [R, k], weights [R, k] float32): sigmoid scores in
    float32 over ALL routed experts, the k largest (of score + ``bias``
    where the router has a selection bias) chosen, weighed by their scores
    (without the bias) over their sum + ``eps`` (the published normaliser's,
    where a model states one), times ``scale``."""
    g = jax.nn.sigmoid(h.astype(jnp.float32) @ w_router)
    _, ids = lax.top_k(g if bias is None else g + bias, topk)
    w = jnp.take_along_axis(g, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return ids, w if scale == 1.0 else w * scale


def softmax_route(h: jax.Array, w_router: jax.Array, topk: int
                  ) -> tuple[jax.Array, jax.Array]:
    """(expert ids [R, k], weights [R, k] float32): softmax in float32 over
    ALL routed experts, the k largest chosen, their weights renormalised to
    sum 1 (the normaliser of the softmax cancels: what is left is the softmax
    over the k chosen scores)."""
    logits = h.astype(jnp.float32) @ w_router
    top, ids = lax.top_k(logits, topk)
    return ids, jax.nn.softmax(top, axis=-1)


def held_ids(ids: jax.Array, n_held: int, first_held: int, active=None):
    """(local ids [R, k] int32 with -1 for an expert another chip holds or a
    row masked off by ``active``, the layer's ``COUNTERS``). With every
    routed expert held (``first_held`` 0, ``n_held`` the router's width) no
    expert is absent, and only ``active`` drops a pick."""
    lid = ids - first_held
    held = jnp.logical_and(lid >= 0, lid < n_held)
    if active is not None:
        held = jnp.logical_and(held, active[:, None])
    lid = jnp.where(held, lid, -1).astype(jnp.int32)
    touched = jnp.any(lid[..., None] == jnp.arange(n_held), axis=(0, 1))
    return lid, {"moe_local_rows": jnp.sum(held).astype(jnp.int32),
                 "moe_experts_touched": jnp.sum(touched).astype(jnp.int32)}


def held_experts(h: jax.Array, lid: jax.Array, w: jax.Array, tables,
                 first_row, n_held: int, block_m: int = 128) -> jax.Array:
    """The held experts' part of the routed sum, float32 [R, D]: rows ``h``
    [R, D] through the gated FFNs of the experts ``lid`` [R, k] names (-1:
    dropped by ``ops.group_gemm.apply_grouped``), weighed by ``w`` [R, k].
    ``tables`` are the STACKED gate, up and down tables [layers, held, ., .]
    of every layer, read in place from row ``first_row`` (= the layer's
    index among them x held; traced or not) of their [layers * held, ., .]
    view: XLA cannot fuse a slice into a Pallas operand, a per-layer table
    would be copied every call."""
    from triton_dist_tpu.ops.group_gemm import (apply_grouped, fit_block_k,
                                                grouped_gemm,
                                                grouped_gemm_gated)
    R, D = h.shape
    k = lid.shape[1]
    wg, wu, wd = (t.reshape((-1,) + t.shape[2:]) for t in tables)
    Fe = wd.shape[1]
    size = jnp.dtype(wd.dtype).itemsize
    bn, dbn = math.gcd(128, Fe), math.gcd(512, D)

    def experts(xs, be, nb):
        be = be + first_row
        hh = grouped_gemm_gated(
            xs, wg, wu, be, block_m=block_m, block_n=bn,
            n_blocks_used=nb, masked=False,
            block_k=fit_block_k(D, block_m, bn, size, n_weights=2))
        return grouped_gemm(
            hh, wd, be, block_m=block_m, block_n=dbn, n_blocks_used=nb,
            masked=False, block_k=fit_block_k(Fe, block_m, dbn, size))

    y = apply_grouped(jnp.repeat(h, k, axis=0), lid.reshape(R * k), n_held,
                      experts, block_m=block_m)
    return jnp.sum(y.reshape(R, k, D).astype(jnp.float32) * w[..., None],
                   axis=1)


def held_picks(h: jax.Array, lid: jax.Array, w: jax.Array, tables, first_row,
               n_held: int, share: float, block_m: int = 128) -> jax.Array:
    """``held_experts`` for a SMALL share of MANY picks (``share`` = held /
    routed experts, say 32 / 512 at 10 picks a row): of the ``R x k`` picks
    one in sixteen names a held expert, and ``held_experts`` aligns, gathers
    and unscrambles all of them (at 2,048 rows x 10 picks a third of a chunk's
    device time was that bookkeeping, three times the GEMMs'). Here the held
    picks are compacted first, ``cap`` = 1.6 x their expected number at a
    time (a loop whose trip count is what the routing needs: one, but for a
    batch that crowds this share), each as a row of its own with ONE pick;
    a row's picks are added up by a one-hot product (the terms in bfloat16
    halves: two exact-selection passes carry 16 bits of each). Same result as
    ``held_experts`` up to the order of a row's sum."""
    R, k = lid.shape
    D = h.shape[1]
    cap = 128 * max(1, -(-int(1.6 * R * k * share) // 128))
    # a block of the grouped GEMMs no taller than a held expert's rows call
    # for (a power of two from 16, bfloat16's tile): at 4 rows an expert a
    # 128-row block a held expert is 32 x 128 rows of padding to gather
    block_m = min(block_m, max(16, 1 << (cap // n_held - 1).bit_length()))
    flat, wf = lid.reshape(R * k), w.reshape(R * k)
    held = flat >= 0
    n = jnp.sum(held).astype(jnp.int32)
    # the held picks first, in (row, pick) order; padded so that a window of
    # ``cap`` starting anywhere inside stays in range
    order = jnp.pad(jnp.argsort(jnp.logical_not(held), stable=True
                                ).astype(jnp.int32), (0, cap))
    rows_of = jnp.arange(R, dtype=jnp.int32)[:, None]

    def some(carry):
        i, out = carry
        idx = lax.dynamic_slice_in_dim(order, i * cap, cap)
        valid = i * cap + jnp.arange(cap, dtype=jnp.int32) < n
        rows = idx // k
        y = held_experts(
            h[rows], jnp.where(valid, flat[idx], -1)[:, None],
            jnp.where(valid, wf[idx], 0.0)[:, None], tables, first_row,
            n_held, block_m)                                # [cap, D] float32
        mine = jnp.logical_and(rows[None, :] == rows_of, valid[None, :])
        hi = y.astype(jnp.bfloat16)
        lo = (y - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        pick = mine.astype(jnp.bfloat16)                    # [R, cap], exact
        return i + 1, out + sum(jnp.dot(
            pick, part, preferred_element_type=jnp.float32)
            for part in (hi, lo))

    return lax.while_loop(lambda c: c[0] * cap < n, some,
                          (jnp.int32(0), jnp.zeros((R, D), jnp.float32)))[1]


__all__ = ["COUNTERS", "sigmoid_route", "softmax_route", "held_ids",
           "held_experts", "held_picks"]
