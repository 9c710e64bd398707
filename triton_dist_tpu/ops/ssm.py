"""State-space (Mamba-2) mixer state: the decode rows' one-step update as a
Pallas kernel, and a prefill chunk's scan in the chunked (SSD) form.

The state of one sequence and layer is ``h`` [H, N, P] (heads, state size,
head dim; float32): a FIXED block that is rewritten every token, where a KV
cache is walked and grows. Per head, with ``a_t = dt_t * A`` (<= 0):

    h_t = exp(a_t) h_{t-1} + B_t (outer) (dt_t x_t)        [N, P]
    y_t = C_t h_t                                          [P]

``B`` / ``C`` [G, N] are shared by the ``H / G`` heads of a group. The skip
term ``D x_t``, the gate and the norm are the model's (``models.hybrid_ssm``).

- ``ssm_decode_update``: the decode program's rows. The states live in a pool
  leaf ``[L, slots, H, N, P]`` left in HBM and are updated IN PLACE
  (``input_output_aliases``): ONE grid step whose body loops over the LIVE
  rows alone, a block of heads at a time, each block fetched by hand into one
  of two VMEM buffers while the block before it is updated and the one before
  that is written back. A row that is not live moves no byte: its state is
  the same to the bit afterwards (the form of ``gqa_decode_paged`` since PR
  29). The state is [N, P] a head so that ``x`` and ``y`` are lane vectors
  (rows of [R, H * P]); ``B`` and ``C`` become lane-broadcast columns by one
  in-kernel transpose a group. Under 1 FLOP a byte: HBM bounds it.
- ``ssd_chunk_scan``: T consecutive tokens of ONE sequence in blocks of
  ``block`` tokens: inside a block the quadratic form (a [block, block]
  decay-masked ``C B^T``), between blocks the state; initial state in, final
  state out. A dead row (padding past the prompt) has ``dt = 0``: it decays
  nothing and adds nothing, so the state after the chunk is the state after
  its last live row wherever in a block that is. Plain ``jnp`` in float32 at
  ``HIGHEST`` precision (the state is a running sum over the whole context).
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

# Heads of one row whose states move as one DMA (1 MB at [8, 256, 128]
# float32; two buffers in, two out).
DECODE_HEADS_PER_BLOCK = 8


def _decode_update_kernel(n_ref, order_ref, slot_ref, layer_ref, xdt_ref,
                          decay_ref, b_ref, c_ref, _state_in, y_ref,
                          state_ref, in_buf, out_buf, sem, *,
                          heads: int, groups: int):
    """``order_ref[:n_ref[0]]`` are the live rows; item i of the loop is head
    block ``i % NB`` of live row ``i // NB``. ``state_ref`` is the pool leaf
    (the aliased output: read and written through the one ref)."""
    HB, N, P = in_buf.shape[1:]
    NB = heads // HB
    layer = layer_ref[0]
    n_items = n_ref[0] * NB
    y_ref[...] = jnp.zeros_like(y_ref)          # rows that are not live

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
        g = row * groups + h0 // (heads // groups)
        # B and C of the block's group as lane-broadcast columns [N, P]
        col = lambda ref: jnp.broadcast_to(             # noqa: E731
            ref[pl.ds(g, 1), :], (P, N)).T
        bcol, ccol = col(b_ref), col(c_ref)
        base = pl.multiple_of(row * heads + h0, HB)
        xdt = xdt_ref[pl.ds(base, HB), :]               # [HB, P]
        decay = decay_ref[pl.ds(base, HB), :]
        ys = []
        for j in range(HB):
            h = in_buf[buf, j].astype(jnp.float32) * decay[j:j + 1] \
                + bcol * xdt[j:j + 1]
            out_buf[buf, j] = h.astype(out_buf.dtype)
            ys.append(jnp.sum(h * ccol, axis=0, keepdims=True))
        y_ref[pl.ds(base, HB), :] = jnp.concatenate(ys, axis=0)
        store(i, buf).start()
        return carry

    lax.fori_loop(0, n_items, update, 0)
    pl.when(n_items >= 2)(lambda: store(n_items - 2, n_items % 2).wait())
    pl.when(n_items >= 1)(
        lambda: store(n_items - 1, (n_items - 1) % 2).wait())


def ssm_decode_update(state: jax.Array, layer, slots: jax.Array,
                      live: jax.Array, xdt: jax.Array, decay: jax.Array,
                      b: jax.Array, c: jax.Array
                      ) -> tuple[jax.Array, jax.Array]:
    """One step of the recurrence for the LIVE rows of a decode batch.

    state [L, S, H, N, P] (float32; any float dtype is updated in float32
    and rounded on the way back): the pool leaf, returned whole and updated
    in place at ``[layer, slots[r]]`` for every live row r (live rows have
    distinct slots). ``layer`` a traced or Python int. slots [R] int32; live
    [R] bool; xdt [R, H, P] float32 = ``dt * x``; decay [R, H] float32 =
    ``exp(dt * A)``; b, c [R, G, N] float32.

    Returns (y [R, H, P] float32 = ``C h`` of the updated state, zeros for
    rows that are not live; the state leaf). Rows that are not live read and
    write nothing."""
    L, S, H, N, P = state.shape
    R, G = slots.shape[0], b.shape[1]
    assert xdt.shape == (R, H, P) and decay.shape == (R, H), (
        xdt.shape, decay.shape)
    assert b.shape == c.shape == (R, G, N) and H % G == 0, (b.shape, H)
    HB = math.gcd(DECODE_HEADS_PER_BLOCK, H // G)
    live = live.astype(jnp.bool_)
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    n = jnp.sum(live).astype(jnp.int32).reshape(1)
    slots = jnp.clip(slots.astype(jnp.int32), 0, S - 1)
    f32 = lambda a: a.astype(jnp.float32)                   # noqa: E731
    whole = lambda shape: pl.BlockSpec(                     # noqa: E731
        shape, lambda i, *_: (0,) * len(shape))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    block = pltpu.VMEM((2, HB, N, P), state.dtype)
    y, state = pl.pallas_call(
        functools.partial(_decode_update_kernel, heads=H, groups=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1,),
            in_specs=[whole((R * H, P)), whole((R * H, P)),
                      whole((R * G, N)), whole((R * G, N)), in_hbm],
            out_specs=[whole((R * H, P)), in_hbm],
            scratch_shapes=[block, block, pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=(jax.ShapeDtypeStruct((R * H, P), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        # operand 8 (4 scalars, xdt, decay, b, c, state) is output 1
        input_output_aliases={8: 1},
        cost_estimate=pl.CostEstimate(
            flops=5 * R * H * N * P,
            bytes_accessed=2 * R * H * N * P * state.dtype.itemsize,
            transcendentals=0),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=32 << 20),
        name="ssm_decode_update",
        interpret=default_interpret(),
    )(n, order, slots, jnp.asarray(layer, jnp.int32).reshape(1),
      f32(xdt).reshape(R * H, P),
      jnp.broadcast_to(f32(decay)[:, :, None], (R, H, P)).reshape(R * H, P),
      f32(b).reshape(R * G, N), f32(c).reshape(R * G, N), state)
    return y.reshape(R, H, P), state


def ssm_step_reference(h, xdt, decay, b, c):
    """The same step in plain ``jnp`` on states h [R, H, N, P]: (y, h')."""
    R, H = decay.shape
    G = b.shape[1]
    rep = lambda a: jnp.repeat(a, H // G, axis=1)           # noqa: E731
    h = h * decay[:, :, None, None] \
        + rep(b)[:, :, :, None] * xdt[:, :, None, :]
    return jnp.sum(h * rep(c)[:, :, :, None], axis=2), h


def ssd_chunk_scan(x: jax.Array, dt: jax.Array, A: jax.Array, b: jax.Array,
                   c: jax.Array, h0: jax.Array, block: int = 128
                   ) -> tuple[jax.Array, jax.Array]:
    """T consecutive tokens of one sequence through the recurrence, in the
    chunked form. x [T, H, P]; dt [T, H] (>= 0; 0 on a dead row); A [H] (< 0);
    b, c [T, G, N]; h0 [H, N, P] the state before the first token. All
    float32. Returns (y [T, H, P] = ``C_t h_t``, h_T [H, N, P]). ``block``
    tokens a block (``math.gcd(T, block)`` if it does not divide T)."""
    T, H, P = x.shape
    G, N = b.shape[1:]
    Q = math.gcd(T, block)
    hpg = H // G
    blocks = lambda a: a.reshape((T // Q, Q) + a.shape[1:])  # noqa: E731
    tril = jnp.tril(jnp.ones((Q, Q), jnp.bool_))
    ein = functools.partial(jnp.einsum, precision=HIGHEST)

    def one(h, blk):
        xb, dtb, bb, cb = blk
        cs = jnp.cumsum(dtb * A, axis=0)                    # [Q, H], <= 0
        # within the block: y_t += sum_{s <= t} exp(cs_t - cs_s) (C_t B_s)
        # dt_s x_s
        seg = jnp.where(tril[:, :, None], cs[:, None] - cs[None, :], -jnp.inf)
        w = jnp.exp(seg).reshape(Q, Q, G, hpg) \
            * ein("tgn,sgn->tsg", cb, bb)[..., None]        # [t, s, G, hpg]
        xdt = (xb * dtb[:, :, None]).reshape(Q, G, hpg, P)
        y = ein("tsgk,sgkp->tgkp", w, xdt)
        # from the state carried in: y_t += exp(cs_t) C_t h
        hg = h.reshape(G, hpg, N, P)
        y = y + ein("tgn,gknp->tgkp", cb, hg) \
            * jnp.exp(cs).reshape(Q, G, hpg, 1)
        # the state carried out
        left = jnp.exp(cs[-1] - cs).reshape(Q, G, hpg, 1)   # [s, G, hpg, 1]
        hg = hg * jnp.exp(cs[-1]).reshape(G, hpg, 1, 1) \
            + ein("sgn,sgkp->gknp", bb, xdt * left)
        return hg.reshape(H, N, P), y.reshape(Q, H, P)

    hT, y = lax.scan(one, h0.astype(jnp.float32),
                     (blocks(x), blocks(dt), blocks(b), blocks(c)))
    return y.reshape(T, H, P), hT


__all__ = ["ssm_decode_update", "ssd_chunk_scan"]
