"""Gated-delta-rule linear attention state: the decode rows' one-step update
as a Pallas kernel, and a prefill chunk's rows in the chunked (WY) form.

The state of one sequence and layer is ``S`` [H, K, V] (value heads, key dim,
value dim; float32): a FIXED block that is rewritten every token, as a
state-space mixer's is (``ops.ssm``), but the update READS the state before it
writes it. Per value head h, served by key head ``h // (H / Hk)``, with
``alpha_t`` in (0, 1] and ``beta_t`` in [0, 1] scalars a head:

    S'  = alpha_t S_{t-1}
    u_t = beta_t (v_t - S'^T k_t)                          [V]
    S_t = S' + k_t (outer) u_t                             [K, V]
    o_t = S_t^T q_t                                        [V]

``q`` and ``k`` come L2-normalised (and ``q`` scaled) from the model
(``models.linear_attn_moe``), as do the gate and the norm after it.

- ``gdn_decode_update``: the decode program's rows. The states live in a pool
  leaf ``[L, slots, H, K, V]`` left in HBM and are updated IN PLACE
  (``input_output_aliases``), the live-row loop and hand-rolled double
  buffering of ``ops.ssm.ssm_decode_update``: a row that is not live moves no
  byte and its state is the same to the bit afterwards. BOTH contractions are
  taken on the state as it was read (``S^T k`` and ``S^T q``: one pass over
  the block), since ``o_t = alpha S^T q + (k . q) u_t``; 7 operations a state
  element, under 1 FLOP a byte: HBM bounds it. A second kernel beside
  ``ssm_decode_update`` and not a second body on it: what the two share is
  the DMA loop's thirty lines, and a body handed into that loop would change
  the state-space family's kernel (its MLIR is pinned by
  ``scripts/programs_hlo.py``) for no byte saved.
- ``gdn_chunk_scan``: T consecutive tokens of ONE sequence in blocks of
  ``block`` tokens. Inside a block the recurrence is solved in closed form
  (the inverse of a unit lower-triangular [block, block] matrix, built by
  halves); between blocks the state. Initial state in, final state out. A
  dead row (padding past the prompt) has ``beta = 0`` and ``g = 0``: it decays
  nothing and writes nothing, so the state after the chunk is the state after
  its last live row wherever in a block that is. Plain ``jnp`` in float32 at
  ``HIGHEST`` precision.
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

HIGHEST = lax.Precision.HIGHEST

# Value heads of one row whose states move as one DMA (1 MB at [16, 128, 128]
# float32; two buffers in, two out).
DECODE_HEADS_PER_BLOCK = 16


def _decode_update_kernel(n_ref, order_ref, slot_ref, layer_ref, q_ref, k_ref,
                          v_ref, alpha_ref, beta_ref, kq_ref, _state_in,
                          o_ref, state_ref, in_buf, out_buf, sem, *,
                          heads: int, key_heads: int, delta: bool):
    """``order_ref[:n_ref[0]]`` are the live rows; item i of the loop is head
    block ``i % NB`` of live row ``i // NB``. ``state_ref`` is the pool leaf
    (the aliased output: read and written through the one ref)."""
    HB, K, V = in_buf.shape[1:]
    NB = heads // HB
    per_key = heads // key_heads          # value heads a key head serves
    layer = layer_ref[0]
    n_items = n_ref[0] * NB
    o_ref[...] = jnp.zeros_like(o_ref)          # rows that are not live

    def where(i):
        row = order_ref[i // NB]
        return row, (i % NB) * HB

    def block_of(i):
        row, h0 = where(i)
        return state_ref.at[layer, slot_ref[row], pl.ds(h0, HB)]

    def fetch(i, buf):
        return pltpu.make_async_copy(block_of(i), in_buf.at[buf],
                                     sem.at[0, buf])

    def store(i, buf):
        return pltpu.make_async_copy(out_buf.at[buf], block_of(i),
                                     sem.at[1, buf])

    pl.when(n_items > 0)(lambda: fetch(0, 0).start())

    def update(i, carry):
        buf = i % 2
        pl.when(i + 1 < n_items)(lambda: fetch(i + 1, 1 - buf).start())
        fetch(i, buf).wait()
        pl.when(i >= 2)(lambda: store(i - 2, buf).wait())
        row, h0 = where(i)
        base = pl.multiple_of(row * heads + h0, HB)
        kbase = row * key_heads + h0 // per_key
        v = v_ref[pl.ds(base, HB), :]                   # [HB, V]
        alpha = alpha_ref[pl.ds(base, HB), :]           # lane-broadcast
        beta = beta_ref[pl.ds(base, HB), :]
        kq = kq_ref[pl.ds(base, HB), :]
        # a key head's q and k as lane-broadcast columns [K, V]
        col = lambda ref, g: jnp.broadcast_to(          # noqa: E731
            ref[pl.ds(kbase + g, 1), :], (V, K)).T
        outs = []
        for j in range(HB):
            if j % per_key == 0:
                kcol, qcol = col(k_ref, j // per_key), col(q_ref, j // per_key)
            s = in_buf[buf, j].astype(jnp.float32)
            sq = jnp.sum(s * qcol, axis=0, keepdims=True)       # S^T q
            a = alpha[j:j + 1]
            u = v[j:j + 1]
            if delta:
                u = u - a * jnp.sum(s * kcol, axis=0, keepdims=True)  # S'^T k
            u = beta[j:j + 1] * u
            out_buf[buf, j] = (s * a + kcol * u).astype(out_buf.dtype)
            outs.append(a * sq + kq[j:j + 1] * u)
        o_ref[pl.ds(base, HB), :] = jnp.concatenate(outs, axis=0)
        store(i, buf).start()
        return carry

    lax.fori_loop(0, n_items, update, 0)
    pl.when(n_items >= 2)(lambda: store(n_items - 2, n_items % 2).wait())
    pl.when(n_items >= 1)(
        lambda: store(n_items - 1, (n_items - 1) % 2).wait())


def gdn_decode_update(state: jax.Array, layer, slots: jax.Array,
                      live: jax.Array, q: jax.Array, k: jax.Array,
                      v: jax.Array, alpha: jax.Array, beta: jax.Array,
                      delta: bool = True) -> tuple[jax.Array, jax.Array]:
    """One step of the gated delta rule for the LIVE rows of a decode batch
    (``delta=False``: ``u_t = beta_t v_t``, plain gated linear attention, the
    state never read before it is written; the benchmark's control).

    state [L, S, H, K, V] (float32; any float dtype is updated in float32 and
    rounded on the way back): the pool leaf, returned whole and updated in
    place at ``[layer, slots[r]]`` for every live row r (live rows have
    distinct slots). ``layer`` a traced or Python int. slots [R] int32; live
    [R] bool; q, k [R, Hk, K] float32 (normalised, q scaled); v [R, H, V]
    float32; alpha, beta [R, H] float32.

    Returns (o [R, H, V] float32 = ``S_t^T q`` of the updated state, zeros for
    rows that are not live; the state leaf). Rows that are not live read and
    write nothing."""
    L, S, H, K, V = state.shape
    R, Hk = slots.shape[0], q.shape[1]
    assert q.shape == k.shape == (R, Hk, K) and v.shape == (R, H, V), (
        q.shape, k.shape, v.shape)
    assert alpha.shape == beta.shape == (R, H) and H % Hk == 0, (
        alpha.shape, H, Hk)
    per_key = H // Hk
    HB = math.gcd(DECODE_HEADS_PER_BLOCK, H)
    assert HB % per_key == 0, (HB, per_key)
    live = live.astype(jnp.bool_)
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    n = jnp.sum(live).astype(jnp.int32).reshape(1)
    slots = jnp.clip(slots.astype(jnp.int32), 0, S - 1)
    f32 = lambda a: a.astype(jnp.float32)                   # noqa: E731
    lanes = lambda a: jnp.broadcast_to(                     # noqa: E731
        f32(a)[:, :, None], (R, H, V)).reshape(R * H, V)
    kq = jnp.repeat(jnp.sum(f32(q) * f32(k), axis=-1), per_key, axis=1)
    whole = lambda shape: pl.BlockSpec(                     # noqa: E731
        shape, lambda i, *_: (0,) * len(shape))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    block = pltpu.VMEM((2, HB, K, V), state.dtype)
    o, state = pl.pallas_call(
        functools.partial(_decode_update_kernel, heads=H, key_heads=Hk,
                          delta=delta),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[whole((R * Hk, K)), whole((R * Hk, K)),
                      whole((R * H, V)), whole((R * H, V)),
                      whole((R * H, V)), whole((R * H, V)), in_hbm],
            out_specs=[whole((R * H, V)), in_hbm],
            scratch_shapes=[block, block, pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=(jax.ShapeDtypeStruct((R * H, V), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        # operand 10 (4 scalars, q, k, v, alpha, beta, kq, state) is output 1
        input_output_aliases={10: 1},
        cost_estimate=pl.CostEstimate(
            flops=7 * R * H * K * V,
            bytes_accessed=2 * R * H * K * V * state.dtype.itemsize,
            transcendentals=0),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
        name="gdn_decode_update",
        interpret=default_interpret(),
    )(n, order, slots, jnp.asarray(layer, jnp.int32).reshape(1),
      f32(q).reshape(R * Hk, K), f32(k).reshape(R * Hk, K),
      f32(v).reshape(R * H, V), lanes(alpha), lanes(beta), lanes(kq), state)
    return o.reshape(R, H, V), state


def gdn_step_reference(s, q, k, v, alpha, beta, delta: bool = True):
    """The same step in plain ``jnp`` on states s [R, H, K, V]: (o, s')."""
    per_key = v.shape[1] // q.shape[1]
    q, k = (jnp.repeat(a, per_key, axis=1) for a in (q, k))   # [R, H, K]
    s = s * alpha[:, :, None, None]
    u = beta[:, :, None] * (v - delta * jnp.einsum(
        "rhkv,rhk->rhv", s, k, precision=HIGHEST))
    s = s + k[..., None] * u[:, :, None, :]
    return jnp.einsum("rhkv,rhk->rhv", s, q, precision=HIGHEST), s


def unit_lower_inverse(m: jax.Array) -> jax.Array:
    """The inverse of unit lower-triangular matrices m [..., n, n] (n a power
    of two; what lies on or above the diagonal is not read), by halves: the
    inverse of ``[[A, 0], [C, B]]`` is ``[[A', 0], [-B' C A', B']]`` with A',
    B' the halves' inverses. Bottom up: the diagonal blocks of size b, held as
    [..., n / b, b, b], are joined in pairs; log2(n) rounds of two batched
    products, and no update of a slice (a ``dynamic-update-slice`` of the
    whole batch a block cost 2 s of a 3.3 s chunk on the chip)."""
    n = m.shape[-1]
    assert n & (n - 1) == 0, n
    ein = functools.partial(jnp.einsum, precision=HIGHEST)
    batch = m.shape[:-2]
    inv = jnp.ones(batch + (n, 1, 1), m.dtype)         # blocks of one
    b = 1
    while b < n:
        P = n // (2 * b)
        # C of pair i: rows [2bi + b, 2bi + 2b), columns [2bi, 2bi + b)
        pairs = m.reshape(batch + (P, 2, b, P, 2, b))[..., 1, :, :, 0, :]
        c = jnp.moveaxis(jnp.diagonal(pairs, axis1=-4, axis2=-2), -1, -3)
        halves = inv.reshape(batch + (P, 2, b, b))
        a_inv, b_inv = halves[..., 0, :, :], halves[..., 1, :, :]
        low = -ein("...ij,...jk,...kl->...il", b_inv, c, a_inv)
        inv = jnp.concatenate([
            jnp.concatenate([a_inv, jnp.zeros_like(a_inv)], axis=-1),
            jnp.concatenate([low, b_inv], axis=-1)], axis=-2)
        b *= 2
    return inv[..., 0, :, :]


def gdn_chunk_scan(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                   beta: jax.Array, s0: jax.Array, block: int = 64,
                   delta: bool = True) -> tuple[jax.Array, jax.Array]:
    """T consecutive tokens of one sequence through the gated delta rule, in
    the chunked form. q, k [T, Hk, K] (normalised, q scaled); v [T, H, V]; g
    [T, H] = ``log alpha`` (<= 0; 0 on a dead row); beta [T, H] (0 on a dead
    row); s0 [H, K, V] the state before the first token. All float32.
    Returns (o [T, H, V] = ``S_t^T q_t``, S_T [H, K, V]). ``block`` tokens a
    block (the largest power of two that divides both it and T).
    ``delta=False``: as ``gdn_decode_update``'s."""
    T, H, V = v.shape
    Hk, K = q.shape[1:]
    per_key = H // Hk
    Q = math.gcd(T, block)
    Q = Q & -Q
    NB = T // Q
    ein = functools.partial(jnp.einsum, precision=HIGHEST)
    # [NB, H, Q, .]: a block's rows of every value head
    heads = lambda a: a.reshape((NB, Q) + a.shape[1:]).swapaxes(1, 2)  # noqa: E731,E501
    qh, kh = (heads(jnp.repeat(a, per_key, axis=1)) for a in (q, k))
    vh, gh, bh = heads(v), heads(g), heads(beta)            # gh, bh [NB, H, Q]
    cs = jnp.cumsum(gh, axis=-1)                            # <= 0
    tril = jnp.tril(jnp.ones((Q, Q), jnp.bool_))
    # decay from row s to row t >= s of a block
    seg = jnp.exp(jnp.where(tril, cs[..., :, None] - cs[..., None, :],
                            -jnp.inf))                      # [NB, H, t, s]
    kb = kh * bh[..., None]
    u_own, w = vh * bh[..., None], jnp.zeros_like(kb)
    if delta:
        # (I + L) u = beta (v - decayed state . k), L strictly lower
        strict = jnp.tril(jnp.ones((Q, Q), jnp.bool_), -1)
        low = jnp.where(strict, ein("bhtk,bhsk->bhts", kb, kh) * seg, 0.0)
        solve = unit_lower_inverse(low + jnp.eye(Q, dtype=low.dtype))
        u_own = ein("bhts,bhsv->bhtv", solve, u_own)
        w = ein("bhts,bhsk->bhtk", solve, kb * jnp.exp(cs)[..., None])
    within = jnp.where(tril, ein("bhtk,bhsk->bhts", qh, kh) * seg, 0.0)
    q_in = qh * jnp.exp(cs)[..., None]                      # against S carried
    k_out = kh * jnp.exp(cs[..., -1:] - cs)[..., None]      # into S carried
    last = jnp.exp(cs[..., -1])                             # [NB, H]

    def one(s, blk):
        u_b, w_b, within_b, q_b, k_b, last_b = blk
        u = u_b - ein("htk,hkv->htv", w_b, s)
        o = ein("htk,hkv->htv", q_b, s) + ein("hts,hsv->htv", within_b, u)
        s = s * last_b[:, None, None] + ein("htk,htv->hkv", k_b, u)
        return s, o

    sT, o = lax.scan(one, s0.astype(jnp.float32),
                     (u_own, w, within, q_in, k_out, last))
    return o.swapaxes(1, 2).reshape(T, H, V), sT


__all__ = ["gdn_decode_update", "gdn_chunk_scan"]
