"""Decoders whose layers are mostly LINEAR attention under a gated delta rule,
with a gated full-attention layer at the end of every period and a
softmax-routed expert FFN with a gated shared expert in every layer (the
Qwen3-Next class), as ONE CHIP'S SHARE of an expert-parallel deployment.

What differs from ``models.llama`` reaches the paged programs as data
(``LINEAR_ATTN_MOE``, a ``models.llama.PagedFamily``): the decode, multistep
and chunk programs, the layer loop, the engine, the scheduler and the page
ledger are the ones every family uses.

- **Two kinds of layer, ``layer_kinds`` a period, and each kind holds ONE kind
  of cache.** A ``linear`` layer holds a state and NO pages: ``gdn`` [linear
  layers, slots + 1, H, K, V] float32 (the recurrence's matrix state, [K, V] a
  value head: ``ops.gdn``) and ``conv`` [linear layers x (slots + 1), (taps -
  1) x channels] (the last rows of the un-convolved ``[q; k; v]``;
  two-dimensional, layer-major, as ``models.hybrid_ssm`` found necessary). A
  ``full`` layer holds pages and NO state: ``k`` / ``v`` [full layers, page,
  Hkv, page_size, Dh], the ledger's, under the engine's block table. The
  kinds' layers are stacked apart (``blocks["linear"]``, ``blocks["full"]``)
  and layer j of a period indexes the next row of ITS kind's leaves
  (``PagedFamily.period``'s ``(kind, attention)`` entries).
- **The state is the SLOT's** (``PagedFamily.slot_state``): slot s owns row
  ``1 + s`` of every linear layer of both state leaves (row 0 is scratch),
  which rides the last column of its block-table row. A decode row that is
  not live reads and writes NO matrix state (``ops.gdn.gdn_decode_update``)
  and keeps its conv rows to the bit (a layer's conv rows go out and back as
  one slab, the live rows rewritten in it); a
  chunk's rows are one slot's consecutive positions, start from the slot's
  stored state or from zero when the chunk starts a request, run the chunked
  form (``ops.gdn.gdn_chunk_scan``) and leave behind the state after the
  chunk's last LIVE row. A state cannot be rewound or shared by reference: a
  preempted request restarts, and prefix cache, speculation and page copy /
  export / import are refused by name.
- **The linear mixer:** ``[q; k; v]`` (key heads x key dim twice, value heads
  x value dim) through a causal depthwise conv and SiLU; q and k
  L2-normalised a head, q scaled; ``beta = sigmoid(b)``, ``log alpha =
  -exp(A_log) softplus(a + dt_bias)`` a value head; the gated delta rule;
  then an RMS norm a head (plain weight) BEFORE the SiLU gate ``z``, and the
  output projection.
- **The full mixer:** the query projection carries a per-head output gate
  (``[q; gate]`` a head); q and k are normed a head; RoPE (half-split) on the
  first ``rope_dims`` of the head; GQA over pages; ``attn * sigmoid(gate)``.
- **Norms are zero-centred:** ``x_hat * (1 + w)`` for the layer norms, the
  final norm and the q / k norms (not the gated norm after the recurrence).
- **The FFN** scores all ``n_routed_experts`` with a float32 softmax router,
  picks ``topk``, renormalises their weights to sum 1, and adds the shared
  expert under a scalar sigmoid gate. This chip holds ``n_experts_held`` of
  the routed experts (``models.expert_share``; one pick in sixteen names a
  held expert, so the held picks are compacted before the grouped GEMMs:
  ``held_picks``).

The projections are held as lane-aligned column blocks (``w_qkv``, ``w_z``,
``w_ba``); the published checkpoint interleaves them by key head, a fixed
permutation of columns that seeded random weights absorb.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from triton_dist_tpu.models.expert_share import (COUNTERS, held_ids,
                                                 held_picks, softmax_route)
from triton_dist_tpu.models.llama import (PagedFamily, gated_ffn, live_rows,
                                          plain_chunk_walks, rope)

# The recurrent state's dtype: a running sum over the whole context. Not a
# config field: bfloat16 is a different result, not a faster one
# (``benchmark/tools/gdn_control.py`` reads how different).
STATE_DTYPE = jnp.float32

L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class LinearAttnMoEConfig:
    vocab_size: int = 151936
    d_model: int = 2048
    n_layers: int = 48
    layer_kinds: tuple = ("linear", "linear", "linear", "full")  # one period
    # the full layers
    n_heads: int = 16
    n_kv_heads: int = 2
    head_dim: int = 256
    rope_dims: int = 64                # leading dims of a head that rotate
    rope_theta: float = 1e7
    # the linear layers
    lin_key_heads: int = 16
    lin_value_heads: int = 32
    lin_key_dim: int = 128
    lin_value_dim: int = 128
    lin_conv: int = 4
    gdn_chunk: int = 64                # tokens a block of the chunk's scan
    delta_rule: bool = True            # False: ``u = beta v`` (a control)
    # the FFN
    moe_d_ff: int = 512                # one expert's FFN
    shared_d_ff: int = 512             # the shared expert's
    n_routed_experts: int = 512        # the router's width
    n_experts_held: int = 512          # the experts on this chip ...
    first_held_expert: int = 0         # ... are first_held_expert + [0, held)
    topk: int = 10
    norm_eps: float = 1e-6
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16
    # the engine whose slots own the states (``bind``)
    state_slots: int = 0

    def __post_init__(self):
        assert set(self.layer_kinds) <= {"linear", "full"}, self.layer_kinds
        assert self.n_layers % len(self.layer_kinds) == 0, (
            f"{self.n_layers} layers are no whole number of periods "
            f"{self.layer_kinds}")
        assert self.lin_value_heads % self.lin_key_heads == 0
        assert self.rope_dims % 2 == 0 and self.rope_dims <= self.head_dim

    @property
    def d_qkv(self) -> int:
        """Channels of ``[q; k; v]``: what the conv runs over."""
        return (2 * self.lin_key_heads * self.lin_key_dim
                + self.lin_value_heads * self.lin_value_dim)

    @property
    def d_lin(self) -> int:
        return self.lin_value_heads * self.lin_value_dim

    def layers_of(self, kind: str) -> int:
        """Layers of ``kind``: the length of its stack and of its leaves."""
        return (self.n_layers // len(self.layer_kinds)
                * self.layer_kinds.count(kind))

    @property
    def paged(self) -> PagedFamily:
        return LINEAR_ATTN_MOE

    @classmethod
    def tiny(cls, held: int = 16, first: int = 0, **changes):
        """Test size, every mechanism kept: two periods of four layers, a key
        head serving two value heads, rotary on a quarter of the head, a query
        group of 2, 16 experts top-3."""
        return dataclasses.replace(cls(
            vocab_size=256, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2,
            head_dim=32, rope_dims=8, rope_theta=1e4, lin_key_heads=2,
            lin_value_heads=4, lin_key_dim=16, lin_value_dim=16, gdn_chunk=8,
            moe_d_ff=32, shared_d_ff=32, n_routed_experts=16,
            n_experts_held=held, first_held_expert=first, topk=3,
            max_seq_len=256, dtype=jnp.float32), **changes)


def bind(cfg: LinearAttnMoEConfig, num_slots: int, prefill_chunk: int
         ) -> LinearAttnMoEConfig:
    del prefill_chunk
    return dataclasses.replace(cfg, state_slots=num_slots)


def layer_state_bytes(cfg: LinearAttnMoEConfig) -> int:
    """Bytes of state a slot owns in ONE linear layer (both leaves)."""
    gdn = cfg.lin_value_heads * cfg.lin_key_dim * cfg.lin_value_dim \
        * jnp.dtype(STATE_DTYPE).itemsize
    conv = (cfg.lin_conv - 1) * cfg.d_qkv * jnp.dtype(cfg.dtype).itemsize
    return gdn + conv


def slot_state_bytes(cfg: LinearAttnMoEConfig) -> int:
    """Bytes of state a slot owns over all (linear) layers."""
    return cfg.layers_of("linear") * layer_state_bytes(cfg)


def zc_rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """The zero-centred RMS norm: ``x_hat * (1 + w)``."""
    x32 = x.astype(jnp.float32)
    rms = lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return ((x32 * rms) * (1.0 + w)).astype(x.dtype)


# -- weights -------------------------------------------------------------------

def init_params(key: jax.Array, cfg: LinearAttnMoEConfig) -> dict:
    """Seeded weights in the layout the programs take (the benchmark's
    reference draws its own in the same layout, at scales of its own):
    ``blocks["linear"]`` / ``blocks["full"]`` each kind's layers stacked,
    every layer's FFN leaves among them; ``blocks["we_*"]`` the held experts'
    tables stacked over ALL layers."""
    L, D, V = cfg.n_layers, cfg.d_model, cfg.vocab_size
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    H, Fe, Fs = cfg.lin_value_heads, cfg.moe_d_ff, cfg.shared_d_ff
    E, Eh = cfg.n_routed_experts, cfg.n_experts_held
    keys = iter(jax.random.split(key, 64))

    def f32(*shape, scale=1.0):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def w(*shape, scale=0.1):
        return f32(*shape, scale=scale).astype(cfg.dtype)

    def layers(n, kind):
        p = {"attn_norm": f32(n, D, scale=0.1),
             "mlp_norm": f32(n, D, scale=0.1),
             "w_router": f32(n, D, E, scale=D ** -0.5),
             "ws_gate": w(n, D, Fs), "ws_up": w(n, D, Fs),
             "ws_down": w(n, Fs, D), "w_shared_gate": f32(n, D, scale=0.1)}
        if kind == "full":
            return {**p, "wq": w(n, D, Hq * 2 * Dh), "wk": w(n, D, Hkv * Dh),
                    "wv": w(n, D, Hkv * Dh), "wo": w(n, Hq * Dh, D),
                    "q_norm": f32(n, Dh, scale=0.1),
                    "k_norm": f32(n, Dh, scale=0.1)}
        return {**p, "w_qkv": w(n, D, cfg.d_qkv, scale=0.2),
                "w_z": w(n, D, cfg.d_lin), "w_ba": w(n, D, 2 * H, scale=0.2),
                "conv_w": f32(n, cfg.lin_conv, cfg.d_qkv, scale=0.4),
                "A_log": f32(n, H) - 1.0, "dt_bias": f32(n, H),
                "gdn_norm": 1.0 + f32(n, cfg.lin_value_dim, scale=0.1),
                "w_out": w(n, cfg.d_lin, D)}

    blocks = {kind: layers(cfg.layers_of(kind), kind)
              for kind in ("linear", "full")}
    blocks.update(we_gate=w(L, Eh, D, Fe), we_up=w(L, Eh, D, Fe),
                  we_down=w(L, Eh, Fe, D))
    return {"embed": w(V, D, scale=0.5), "blocks": blocks,
            "final_norm": f32(D, scale=0.1), "lm_head": w(D, V, scale=0.3)}


# -- cache -----------------------------------------------------------------------

def init_pools(cfg: LinearAttnMoEConfig, num_pages: int, page_size: int
               ) -> dict:
    """``k`` / ``v`` [full layers, num_pages, Hkv, page, Dh]: the ledger's
    pages (``models.llama.init_page_pool``'s life: carried whole, written and
    read in place). ``gdn`` [linear layers, slots + 1, H, K, V] and ``conv``
    [linear layers x (slots + 1), (taps - 1) x channels]: a layer's scratch
    row and every slot's state. No layer has both kinds."""
    assert page_size % 8 == 0, f"page_size {page_size} must be 8-aligned"
    assert cfg.state_slots > 0, "bind() the config to an engine first"
    Ll, S = cfg.layers_of("linear"), cfg.state_slots + 1
    kv = (cfg.layers_of("full"), num_pages, cfg.n_kv_heads, page_size,
          cfg.head_dim)
    return {"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
            "gdn": jnp.zeros((Ll, S, cfg.lin_value_heads, cfg.lin_key_dim,
                              cfg.lin_value_dim), STATE_DTYPE),
            "conv": jnp.zeros((Ll * S, (cfg.lin_conv - 1) * cfg.d_qkv),
                              cfg.dtype)}


# -- the two mixers --------------------------------------------------------------
# (``chunk_starts_fresh``, ``decay_and_beta``, ``output_gate`` and
# ``shared_gate`` are functions of their own so that the benchmark's controls,
# ``benchmark/tools/gdn_control.py``, can put ONE of them wrong at a time)

def chunk_starts_fresh(pos0: jax.Array) -> jax.Array:
    """Whether a chunk whose first row sits at position ``pos0`` starts a
    request (zero state) or continues one (the slot's stored state)."""
    return pos0 == 0


def decay_and_beta(p, a: jax.Array, b: jax.Array):
    """(``log alpha`` [R, H] <= 0, ``beta`` [R, H]) of rows' a and b."""
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    return g, jax.nn.sigmoid(b)


def output_gate(attn: jax.Array, gate: jax.Array) -> jax.Array:
    """The full layers' per-head output gate on attn [R, Hq, Dh]."""
    return attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(attn.dtype)


def shared_gate(p, h: jax.Array) -> jax.Array:
    """The shared expert's scalar gate a row, float32 [R, 1]."""
    return jax.nn.sigmoid(jnp.sum(h.astype(jnp.float32) * p["w_shared_gate"],
                                  axis=-1, keepdims=True))


def _l2norm(x: jax.Array) -> jax.Array:
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _my_layer(cfg: LinearAttnMoEConfig, kind: str, rank: int, layer):
    """Layer ``layer`` (the ``rank``-th ``kind`` layer of its period) among
    the layers of its kind: its row of that kind's pool leaves."""
    return (layer // len(cfg.layer_kinds)) * cfg.layer_kinds.count(kind) \
        + rank



def _linear_mixer(rank: int, cfg: LinearAttnMoEConfig, p, h, layer, pool,
                  block_table, pos, kv_len, active, shared_table, lin,
                  attn_io):
    """The gated-delta-rule mixer on normed rows h [R, D]. Decode rows: row r
    is one step of the slot its table's last column names, if live. A chunk
    (``shared_table``): the rows are ONE slot's consecutive positions from
    ``pos[0]``, the live ones first."""
    from triton_dist_tpu.ops.gdn import gdn_chunk_scan, gdn_decode_update
    assert attn_io is None, "the linear-attention family has no attn_io hook"
    R = h.shape[0]
    H, Hk = cfg.lin_value_heads, cfg.lin_key_heads
    K, V, taps = cfg.lin_key_dim, cfg.lin_value_dim, cfg.lin_conv
    live, slot = live_rows(kv_len, active), block_table[:, -1]
    mine = _my_layer(cfg, "linear", rank, jnp.asarray(layer, jnp.int32))
    gdn, conv2d = pool["gdn"], pool["conv"]
    S = gdn.shape[1]
    f32 = lambda a: a.astype(jnp.float32)                   # noqa: E731
    with jax.named_scope("gdn_mixer"):
        qkv = lin(h, p["w_qkv"], "w_qkv")                   # [R, channels]
        z = lin(h, p["w_z"], "w_z")
        ba = f32(lin(h, p["w_ba"], "w_ba"))
        g, beta = decay_and_beta(p, ba[:, H:], ba[:, :H])

        def heads(rows):
            """Convolved rows [R, channels] as normalised q, k and v."""
            q, k, v = jnp.split(f32(rows), [Hk * K, 2 * Hk * K], axis=-1)
            q = _l2norm(q.reshape(R, Hk, K)) * K ** -0.5
            return q, _l2norm(k.reshape(R, Hk, K)), v.reshape(R, H, V)

        base = mine * S
        if shared_table:
            fresh = chunk_starts_fresh(pos[0])
            row = base + slot[0]
            before = jnp.where(fresh, 0, lax.dynamic_slice_in_dim(
                conv2d, row, 1)[0]).reshape(taps - 1, -1)
            rows = jnp.concatenate([before, qkv])           # [taps - 1 + R, .]
            rows32 = f32(rows)
            out = sum(rows32[t:t + R] * p["conv_w"][t] for t in range(taps))
            q, k, v = heads(jax.nn.silu(out).astype(cfg.dtype))
            at = (mine, slot[0], 0, 0, 0)
            s0 = jnp.where(fresh, 0, f32(lax.dynamic_slice(
                gdn, at, (1, 1, H, K, V))[0, 0]))
            dead = jnp.logical_not(live)[:, None]
            with jax.named_scope("gdn_scan"):
                o, sT = gdn_chunk_scan(q, k, v, jnp.where(dead, 0.0, g),
                                       jnp.where(dead, 0.0, beta), s0,
                                       block=cfg.gdn_chunk,
                                       delta=cfg.delta_rule)
            gdn = lax.dynamic_update_slice(
                gdn, sT.astype(gdn.dtype)[None, None], at)
            # the taps - 1 rows before row n_live of the chunk
            n_live = jnp.sum(live).astype(jnp.int32)
            after = lax.dynamic_slice_in_dim(rows, n_live, taps - 1)
            conv2d = lax.dynamic_update_slice(
                conv2d, after.reshape(1, -1).astype(conv2d.dtype), (row, 0))
        else:
            # the layer's rows of the conv leaf, out and back as ONE slab
            # (6 MB): gathered from and scattered into the whole leaf, XLA
            # moved all 76 MB of it three times a layer call on the v5e
            mine_rows = lax.dynamic_slice_in_dim(conv2d, base, S)
            before = mine_rows.at[jnp.where(live, slot, 0)].get(
                mode="promise_in_bounds")
            rows = jnp.concatenate([before.reshape(R, taps - 1, -1),
                                    qkv[:, None]], axis=1)  # [R, taps, .]
            out = jnp.einsum("rtc,tc->rc", f32(rows), p["conv_w"])
            q, k, v = heads(jax.nn.silu(out).astype(cfg.dtype))
            o, gdn = gdn_decode_update(gdn, mine, slot, live, q, k, v,
                                       jnp.exp(g), beta,
                                       delta=cfg.delta_rule)
            # a row that is not live writes nothing (an index past the last)
            mine_rows = mine_rows.at[jnp.where(live, slot, S)].set(
                rows[:, 1:].reshape(R, -1).astype(conv2d.dtype), mode="drop")
            conv2d = lax.dynamic_update_slice_in_dim(conv2d, mine_rows, base,
                                                     0)
        # the norm a head BEFORE the gate; its weight is plain
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg.norm_eps) * p["gdn_norm"]
        o = o.reshape(R, H * V) * jax.nn.silu(f32(z))
        out = lin(o.astype(cfg.dtype), p["w_out"], "w_out")
    counts = {"gdn_state_rows": jnp.int32(0) if shared_table
              else jnp.sum(live).astype(jnp.int32)}
    return out, {**pool, "gdn": gdn, "conv": conv2d}, counts


def _rotate(cfg: LinearAttnMoEConfig, x: jax.Array, positions) -> jax.Array:
    """RoPE on x [R, H, Dh] at ``positions`` [R, 1]: its first ``rope_dims``
    (half-split among themselves), the rest unchanged."""
    r = cfg.rope_dims
    if r == x.shape[-1]:
        return rope(x[:, None], positions, cfg.rope_theta)[:, 0]
    return jnp.concatenate(
        [rope(x[:, None, :, :r], positions, cfg.rope_theta)[:, 0],
         x[..., r:]], -1)


def _gated_attention(rank: int, cfg: LinearAttnMoEConfig, p, h, layer, pool,
                     block_table, pos, kv_len, active, shared_table, lin,
                     attn_io):
    """The gated full attention on normed rows h [R, D]: GQA over the
    ledger's pages (the table's columns before the slot's)."""
    from triton_dist_tpu.ops.flash_decode import (gqa_decode_paged,
                                                  gqa_prefill_paged,
                                                  paged_kv_write)
    assert attn_io is None, "the linear-attention family has no attn_io hook"
    R = h.shape[0]
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    mine = _my_layer(cfg, "full", rank, jnp.asarray(layer, jnp.int32))
    table = block_table[:, :-1]
    with jax.named_scope("gated_attention"):
        qg = lin(h, p["wq"], "wq").reshape(R, Hq, 2, Dh)
        q, gate = qg[:, :, 0], qg[:, :, 1]
        k = lin(h, p["wk"], "wk").reshape(R, Hkv, Dh)
        v = lin(h, p["wv"], "wv").reshape(R, Hkv, Dh)
        positions = pos[:, None].astype(jnp.int32)
        q = _rotate(cfg, zc_rmsnorm(q, p["q_norm"], cfg.norm_eps), positions)
        k = _rotate(cfg, zc_rmsnorm(k, p["k_norm"], cfg.norm_eps), positions)
        counts = {"attn_full_keys": jnp.sum(
            jnp.where(live_rows(kv_len, active), kv_len, 0)).astype(jnp.int32)}
        kp, vp = paged_kv_write(pool["k"], pool["v"], k, v, table, pos,
                                active=active, layer=mine,
                                shared_table=shared_table)
        if shared_table:
            attn = gqa_prefill_paged(q, kp, vp, table[0], kv_len, layer=mine)
        else:
            attn, _lse = gqa_decode_paged(q, kp, vp, table, kv_len,
                                          layer=mine)
        out = lin(output_gate(attn, gate).reshape(R, Hq * Dh), p["wo"], "wo")
    return out, {**pool, "k": kp, "v": vp}, counts


def _period(cfg: LinearAttnMoEConfig) -> tuple:
    """A period's ``(kind, attention)`` entries: each kind's layers are
    stacked on their own (``PagedFamily.period``)."""
    mixers = {"linear": _linear_mixer, "full": _gated_attention}
    seen = {"linear": 0, "full": 0}
    period = []
    for kind in cfg.layer_kinds:
        period.append((kind, functools.partial(mixers[kind], seen[kind])))
        seen[kind] += 1
    return tuple(period)


# -- FFN -------------------------------------------------------------------------

def sparse_ffn(cfg: LinearAttnMoEConfig, p, h: jax.Array, layer, active=None,
               *, tables):
    """A layer's FFN on this chip: the held experts' part of the routed sum
    (softmax over ALL experts, the k largest renormalised) plus the shared
    expert under its scalar sigmoid gate. ``tables``: the stacked expert
    tables [layers, held, ., .], read in place."""
    Eh = cfg.n_experts_held
    with jax.named_scope("moe_router"):
        ids, w = softmax_route(h, p["w_router"], cfg.topk)
        lid, counts = held_ids(ids, Eh, cfg.first_held_expert, active)
    with jax.named_scope("moe_routed_experts"):
        routed = held_picks(h, lid, w, tables, layer * Eh, Eh,
                            Eh / cfg.n_routed_experts)
    with jax.named_scope("moe_shared_expert"):
        shared = gated_ffn(h, p["ws_gate"], p["ws_up"], p["ws_down"])
        shared = shared.astype(jnp.float32) * shared_gate(p, h)
    return (routed + shared).astype(h.dtype), counts


def _segments(cfg: LinearAttnMoEConfig, params: dict) -> list:
    """One periodic run. The expert tables stay OUT of the scanned params (a
    scan slices what it scans over) and reach ``sparse_ffn`` whole."""
    blocks = params["blocks"]
    tables = tuple(blocks[n] for n in ("we_gate", "we_up", "we_down"))
    rest = {n: a for n, a in blocks.items() if not n.startswith("we_")}
    return [(rest, 0, cfg.n_layers,
             functools.partial(sparse_ffn, tables=tables))]


LINEAR_ATTN_MOE = PagedFamily(
    name="linear_attn_moe", init_pool=init_pools, segments=_segments,
    period=_period, norm=zc_rmsnorm,
    counters=COUNTERS + ("gdn_state_rows", "attn_full_keys"),
    # a state is the slot's and cannot be rewound, shared or copied by page
    lacks=("speculate", "prefix_cache", "hooks"),
    slot_state=slot_state_bytes, bind=bind,
    # the full layers alone walk pages
    chunk_walks=lambda cfg: plain_chunk_walks(cfg.layers_of("full")))


__all__ = ["LinearAttnMoEConfig", "LINEAR_ATTN_MOE", "init_params",
           "init_pools", "bind", "zc_rmsnorm", "sparse_ffn",
           "slot_state_bytes", "layer_state_bytes", "chunk_starts_fresh",
           "decay_and_beta", "output_gate", "shared_gate"]
