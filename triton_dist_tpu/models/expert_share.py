"""ONE CHIP'S SHARE of a sigmoid-routed expert layer: what the families that
hold ``n_held`` of a layer's routed experts (``models.mla``,
``models.window_moe``) have in common. The chip routes over ALL the experts
the router scores and computes the part of the routed sum its own experts
give; what the absent experts would add is left out, with no exchange and
nothing that stands in for one.
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
                  scale: float = 1.0) -> tuple[jax.Array, jax.Array]:
    """(expert ids [R, k], weights [R, k] float32): sigmoid scores in
    float32 over ALL routed experts, the k largest (of score + ``bias``
    where the router has a selection bias) chosen, weighed by their scores
    (without the bias) over their sum, times ``scale``."""
    g = jax.nn.sigmoid(h.astype(jnp.float32) @ w_router)
    _, ids = lax.top_k(g if bias is None else g + bias, topk)
    w = jnp.take_along_axis(g, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return ids, w if scale == 1.0 else w * scale


def held_ids(ids: jax.Array, n_held: int, first_held: int, active=None):
    """(local ids [R, k] int32 with -1 for an expert another chip holds or a
    row masked off by ``active``, the layer's ``COUNTERS``)."""
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


__all__ = ["COUNTERS", "sigmoid_route", "held_ids", "held_experts"]
