"""Decoders whose layers are mostly GATED SHORT CONVOLUTIONS, with a GQA layer
of narrow heads opening every period, a leading run of dense layers and a
bias-selected, sigmoid-routed expert FFN after it (the LFM2-MoE class), every
layer WHOLE on its chip (a pipeline stage: no layer is shared).

What differs from ``models.llama`` reaches the paged programs as data
(``SHORT_CONV_MOE``, a ``models.llama.PagedFamily``): the decode, multistep
and chunk programs, the layer loop, the engine, the scheduler and the page
ledger are the ones every family uses.

- **Two kinds of layer, and each kind holds ONE kind of cache.** A ``conv``
  layer holds NO pages and almost no state: the last ``conv_taps - 1`` rows of
  the gated product ``B * u``, ``conv`` [conv layers x (slots + 1), (taps - 1)
  x d_model] in the activations' dtype (two-dimensional, layer-major, as
  ``models.hybrid_ssm`` found necessary): 8 KB a slot and layer at 2 rows of
  2,048 in bfloat16. A ``full`` layer holds pages and no state: ``kv`` [full
  layers, page, Hkv, page_size, 2 x head_dim], the ledger's, under the
  engine's block table, K AND V OF A KV HEAD SIDE BY SIDE IN ONE ROW: heads
  of 64 are then one 128-lane row at their published bytes (held apart, each
  would be padded to 128 lanes on the chip; ``ops.flash_decode``'s
  ``v_pages=None``).
- **The state is the SLOT's** (``PagedFamily.slot_state``): slot s owns row
  ``1 + s`` of every conv layer of the leaf (row 0 is scratch), which rides
  the last column of its block-table row. A decode row that is not live keeps
  its rows to the bit (a layer's rows go out and back as one slab, the live
  rows rewritten in it); a chunk's rows are one slot's consecutive positions,
  start from the slot's stored rows or from zeros when the chunk starts a
  request (``chunk_starts_fresh``), and leave behind the rows before the
  chunk's first padding row. A state cannot be rewound or shared by
  reference: a preempted request restarts, and prefix cache, speculation and
  page copy / export / import are refused by name.
- **The conv mixer:** ``[B; C; u] = h W_in``; ``z = B * u``; a depthwise
  causal convolution of ``conv_taps`` taps over ``z`` (the last tap on the
  current row, no bias); ``out = (C * y) W_out``.
- **The full mixer:** q and k normed a head (plain weight), RoPE (half-split)
  on the whole head, GQA over pages, no bias, no gate.
- **The first ``n_dense_layers`` layers** (conv layers, by the published
  pattern) carry a dense FFN of ``d_ff``; they are a segment of their own
  (``params["dense"]``) OUTSIDE the period, and index the same ``conv`` leaf
  as the periods' conv layers after them.
- **The FFN after them** scores all ``n_routed_experts`` with a float32
  sigmoid router, picks the ``topk`` largest of score + ``router_bias``,
  weighs them by their scores over their sum (+ ``ROUTE_EPS``) times
  ``routed_scale``; no shared expert. This chip holds ``n_experts_held`` of
  them from ``first_held_expert`` (``models.expert_share``): ALL of them in
  the published deployment, where every pick is held and the grouped GEMMs'
  row block follows the rows an expert sees (``expert_block_m``).
- **The head is tied:** logits = ``rmsnorm(x) E^T`` with the embedding ``E``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from triton_dist_tpu.models.expert_share import (COUNTERS, held_experts,
                                                 held_ids, sigmoid_route)
from triton_dist_tpu.models.llama import (PagedFamily, live_rows,
                                          plain_chunk_walks, rmsnorm, rope,
                                          swiglu_ffn)

# the published normaliser of the chosen experts' weights: s_i / (sum + this)
ROUTE_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class ShortConvMoEConfig:
    vocab_size: int = 65536
    d_model: int = 2048
    # the dense run + WHOLE periods: the published 40 layers end in half a
    # period (full, conv), which no pipeline stage of ten but the last meets
    n_layers: int = 10
    n_dense_layers: int = 2            # leading conv layers with a dense FFN
    layer_kinds: tuple = ("full", "conv", "conv", "conv")  # a period after them
    d_ff: int = 11776                  # the dense layers' FFN
    # the full layers
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    # the conv layers
    conv_taps: int = 3
    # the FFN of the periodic layers
    moe_d_ff: int = 1536               # one expert's FFN
    n_routed_experts: int = 64         # the router's width
    n_experts_held: int = 64           # the experts on this chip ...
    first_held_expert: int = 0         # ... are first_held_expert + [0, held)
    topk: int = 4
    routed_scale: float = 1.0
    norm_eps: float = 1e-5
    max_seq_len: int = 128000
    dtype: Any = jnp.bfloat16
    # the engine whose slots own the conv rows (``bind``)
    state_slots: int = 0

    def __post_init__(self):
        assert set(self.layer_kinds) <= {"conv", "full"}, self.layer_kinds
        assert self.n_periodic % len(self.layer_kinds) == 0, (
            f"{self.n_periodic} layers are no whole number of periods "
            f"{self.layer_kinds}")
        assert self.head_dim % 2 == 0

    @property
    def n_periodic(self) -> int:
        return self.n_layers - self.n_dense_layers

    def periodic_layers_of(self, kind: str) -> int:
        """Layers of ``kind`` among the periods: the length of its stack."""
        return (self.n_periodic // len(self.layer_kinds)
                * self.layer_kinds.count(kind))

    def layers_of(self, kind: str) -> int:
        """Layers of ``kind``, the leading dense run's (all conv) among them:
        the layers of its pool leaf."""
        return (self.n_dense_layers * (kind == "conv")
                + self.periodic_layers_of(kind))

    @property
    def paged(self) -> PagedFamily:
        return SHORT_CONV_MOE

    @classmethod
    def tiny(cls, held: int = 16, first: int = 0, **changes):
        """Test size, every mechanism kept: two dense conv layers, then two
        periods of four layers, a query group of 2, 16 experts top-3."""
        return dataclasses.replace(cls(
            vocab_size=256, d_model=64, n_layers=10, d_ff=128, n_heads=4,
            n_kv_heads=2, head_dim=16, rope_theta=1e4, moe_d_ff=32,
            n_routed_experts=16, n_experts_held=held,
            first_held_expert=first, topk=3, max_seq_len=256,
            dtype=jnp.float32), **changes)


def bind(cfg: ShortConvMoEConfig, num_slots: int, prefill_chunk: int
         ) -> ShortConvMoEConfig:
    del prefill_chunk
    return dataclasses.replace(cfg, state_slots=num_slots)


def layer_state_bytes(cfg: ShortConvMoEConfig) -> int:
    """Bytes of state a slot owns in ONE conv layer."""
    return (cfg.conv_taps - 1) * cfg.d_model * jnp.dtype(cfg.dtype).itemsize


def slot_state_bytes(cfg: ShortConvMoEConfig) -> int:
    """Bytes of state a slot owns over all (conv) layers."""
    return cfg.layers_of("conv") * layer_state_bytes(cfg)


def kv_bytes_per_token(cfg: ShortConvMoEConfig) -> int:
    """Bytes a token holds in ONE full layer's pages: the pool row of every
    KV head (``[K | V]``: nothing is padded)."""
    return cfg.n_kv_heads * 2 * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize


# -- weights -------------------------------------------------------------------

def init_params(key: jax.Array, cfg: ShortConvMoEConfig) -> dict:
    """Seeded weights in the layout the programs take (the benchmark's
    reference draws its own in the same layout, at scales of its own):
    ``dense`` the leading conv layers with their dense FFN, stacked;
    ``blocks["conv"]`` / ``blocks["full"]`` each kind's periodic layers
    stacked, every layer's router among them; ``blocks["we_*"]`` the held
    experts' tables stacked over the periodic layers; no ``lm_head`` (tied)."""
    D, V, F = cfg.d_model, cfg.vocab_size, cfg.d_ff
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    E, Eh, Fe, Lp = (cfg.n_routed_experts, cfg.n_experts_held, cfg.moe_d_ff,
                     cfg.n_periodic)
    keys = iter(jax.random.split(key, 64))

    def f32(*shape, scale=1.0):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def w(*shape, scale=0.1):
        return f32(*shape, scale=scale).astype(cfg.dtype)

    def layers(n, kind, sparse=True):
        p = {"attn_norm": 1.0 + f32(n, D, scale=0.1),
             "mlp_norm": 1.0 + f32(n, D, scale=0.1)}
        if kind == "full":
            p.update(wq=w(n, D, Hq * Dh), wk=w(n, D, Hkv * Dh),
                     wv=w(n, D, Hkv * Dh), wo=w(n, Hq * Dh, D),
                     q_norm=1.0 + f32(n, Dh, scale=0.1),
                     k_norm=1.0 + f32(n, Dh, scale=0.1))
        else:
            p.update(w_in=w(n, D, 3 * D, scale=0.2),
                     conv_w=f32(n, cfg.conv_taps, D, scale=0.5),
                     w_out=w(n, D, D))
        if not sparse:
            return {**p, "w_gate": w(n, D, F), "w_up": w(n, D, F),
                    "w_down": w(n, F, D)}
        return {**p, "w_router": f32(n, D, E, scale=D ** -0.5),
                "router_bias": f32(n, E, scale=0.1)}

    blocks = {kind: layers(cfg.periodic_layers_of(kind), kind)
              for kind in ("conv", "full")}
    blocks.update(we_gate=w(Lp, Eh, D, Fe), we_up=w(Lp, Eh, D, Fe),
                  we_down=w(Lp, Eh, Fe, D))
    return {"embed": w(V, D, scale=0.5), "blocks": blocks,
            "dense": layers(cfg.n_dense_layers, "conv", sparse=False),
            "final_norm": 1.0 + f32(D, scale=0.1)}


# -- cache -----------------------------------------------------------------------

def init_pools(cfg: ShortConvMoEConfig, num_pages: int, page_size: int
               ) -> dict:
    """``kv`` [full layers, num_pages, Hkv, page, 2 x head_dim]: the ledger's
    pages, a row ``[K | V]`` of one KV head (``models.llama.init_page_pool``'s
    life: carried whole, written and read in place). ``conv`` [conv layers x
    (slots + 1), (taps - 1) x d_model]: a layer's scratch row and every slot's
    rows. No layer has both kinds."""
    assert page_size % 8 == 0, f"page_size {page_size} must be 8-aligned"
    assert cfg.state_slots > 0, "bind() the config to an engine first"
    return {"kv": jnp.zeros((cfg.layers_of("full"), num_pages, cfg.n_kv_heads,
                             page_size, 2 * cfg.head_dim), cfg.dtype),
            "conv": jnp.zeros((cfg.layers_of("conv") * (cfg.state_slots + 1),
                               (cfg.conv_taps - 1) * cfg.d_model), cfg.dtype)}


# -- the two mixers --------------------------------------------------------------
# (``chunk_starts_fresh``, ``taps_of``, ``output_gate``, ``normed_heads``,
# ``rotated`` and ``route`` are functions of their own so that the benchmark's
# controls, ``benchmark/tools/short_conv_control.py``, can put ONE of them
# wrong at a time)

def chunk_starts_fresh(pos0: jax.Array) -> jax.Array:
    """Whether a chunk whose first row sits at position ``pos0`` starts a
    request (zero rows before it) or continues one (the slot's stored rows)."""
    return pos0 == 0


def taps_of(p) -> jax.Array:
    """The conv's taps [taps, D] float32: the LAST on the current row."""
    return p["conv_w"]


def output_gate(y: jax.Array, c: jax.Array) -> jax.Array:
    """The conv mixer's output gate: ``C * y`` (y float32 [R, D])."""
    return c.astype(jnp.float32) * y


def normed_heads(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """q or k [R, H, Dh] normed a head before rotary (plain weight)."""
    return rmsnorm(x, w, eps)


def rotated(cfg: ShortConvMoEConfig, x: jax.Array, positions) -> jax.Array:
    """RoPE (half-split) on all of x [R, H, Dh] at ``positions`` [R, 1]."""
    return rope(x[:, None], positions, cfg.rope_theta)[:, 0]


def route(cfg: ShortConvMoEConfig, p, h: jax.Array):
    """(expert ids [R, k], weights [R, k] float32) of a sparse layer."""
    return sigmoid_route(h, p["w_router"], cfg.topk, bias=p["router_bias"],
                         scale=cfg.routed_scale, eps=ROUTE_EPS)


def _mine(cfg: ShortConvMoEConfig, kind: str, rank, layer):
    """Layer ``layer`` among the layers of its kind (its row of that kind's
    pool leaf): a layer of the leading dense run (``rank`` None; all conv) is
    its own index; after it, the ``rank``-th ``kind`` layer of its period."""
    if rank is None:
        return layer
    return (cfg.n_dense_layers * (kind == "conv")
            + (layer - cfg.n_dense_layers) // len(cfg.layer_kinds)
            * cfg.layer_kinds.count(kind) + rank)



def _conv_mixer(rank, cfg: ShortConvMoEConfig, p, h, layer, pool,
                block_table, pos, kv_len, active, shared_table, lin,
                attn_io):
    """The doubly gated short convolution on normed rows h [R, D]. Decode
    rows: row r is one step of the slot its table's last column names, if
    live. A chunk (``shared_table``): the rows are ONE slot's consecutive
    positions from ``pos[0]``, the live ones first."""
    assert attn_io is None, "the short-conv family has no attn_io hook"
    R, D, taps = h.shape[0], cfg.d_model, cfg.conv_taps
    live, slot = live_rows(kv_len, active), block_table[:, -1]
    mine = _mine(cfg, "conv", rank, jnp.asarray(layer, jnp.int32))
    conv2d = pool["conv"]
    S = cfg.state_slots + 1
    base = mine * S
    f32 = lambda a: a.astype(jnp.float32)                   # noqa: E731
    with jax.named_scope("short_conv"):
        bcu = lin(h, p["w_in"], "w_in")                     # [R, 3 D]
        z = bcu[:, :D] * bcu[:, 2 * D:]                     # B * u
        k = taps_of(p)
        if shared_table:
            row = base + slot[0]
            before = jnp.where(chunk_starts_fresh(pos[0]), 0,
                               lax.dynamic_slice_in_dim(conv2d, row, 1)[0]
                               ).reshape(taps - 1, D)
            rows = jnp.concatenate([before, z])             # [taps - 1 + R, D]
            rows32 = f32(rows)
            y = sum(rows32[t:t + R] * k[t] for t in range(taps))
            # the taps - 1 rows before row n_live of the chunk
            n_live = jnp.sum(live).astype(jnp.int32)
            after = lax.dynamic_slice_in_dim(rows, n_live, taps - 1)
            conv2d = lax.dynamic_update_slice(
                conv2d, after.reshape(1, -1).astype(conv2d.dtype), (row, 0))
        else:
            # the layer's rows of the leaf, out and back as ONE slab (0.8 MB
            # at 97 rows of 4,096): gathered from and scattered into the
            # whole leaf, XLA moves all of it (``models.linear_attn_moe``)
            mine_rows = lax.dynamic_slice_in_dim(conv2d, base, S)
            before = mine_rows.at[jnp.where(live, slot, 0)].get(
                mode="promise_in_bounds")
            rows = jnp.concatenate([before.reshape(R, taps - 1, D),
                                    z[:, None]], axis=1)    # [R, taps, D]
            y = jnp.einsum("rtc,tc->rc", f32(rows), k)
            # a row that is not live writes nothing (an index past the last)
            mine_rows = mine_rows.at[jnp.where(live, slot, S)].set(
                rows[:, 1:].reshape(R, -1).astype(conv2d.dtype), mode="drop")
            conv2d = lax.dynamic_update_slice_in_dim(conv2d, mine_rows, base,
                                                     0)
        out = lin(output_gate(y, bcu[:, D:2 * D]).astype(cfg.dtype),
                  p["w_out"], "w_out")
    counts = {"conv_state_rows": jnp.int32(0) if shared_table
              else jnp.sum(live).astype(jnp.int32)}
    return out, {**pool, "conv": conv2d}, counts


def _full_attention(rank, cfg: ShortConvMoEConfig, p, h, layer, pool,
                    block_table, pos, kv_len, active, shared_table, lin,
                    attn_io):
    """GQA on normed rows h [R, D] over the ledger's pages (the table's
    columns before the slot's), rows of ``[K | V]``."""
    from triton_dist_tpu.ops.flash_decode import (gqa_decode_paged,
                                                  gqa_prefill_paged,
                                                  paged_kv_write)
    assert attn_io is None, "the short-conv family has no attn_io hook"
    R = h.shape[0]
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    mine = _mine(cfg, "full", rank, jnp.asarray(layer, jnp.int32))
    table = block_table[:, :-1]
    with jax.named_scope("full_attention"):
        q = lin(h, p["wq"], "wq").reshape(R, Hq, Dh)
        k = lin(h, p["wk"], "wk").reshape(R, Hkv, Dh)
        v = lin(h, p["wv"], "wv").reshape(R, Hkv, Dh)
        positions = pos[:, None].astype(jnp.int32)
        q = rotated(cfg, normed_heads(q, p["q_norm"], cfg.norm_eps), positions)
        k = rotated(cfg, normed_heads(k, p["k_norm"], cfg.norm_eps), positions)
        counts = {"attn_full_keys": jnp.sum(
            jnp.where(live_rows(kv_len, active), kv_len, 0)).astype(jnp.int32)}
        kv, _ = paged_kv_write(pool["kv"], None, k, v, table, pos,
                               active=active, layer=mine,
                               shared_table=shared_table)
        if shared_table:
            attn = gqa_prefill_paged(q, kv, None, table[0], kv_len,
                                     layer=mine)
        else:
            attn, _lse = gqa_decode_paged(q, kv, None, table, kv_len,
                                          layer=mine)
        out = lin(attn.reshape(R, Hq * Dh), p["wo"], "wo")
    return out, {**pool, "kv": kv}, counts


_MIXERS = {"conv": _conv_mixer, "full": _full_attention}


def _period(cfg: ShortConvMoEConfig) -> tuple:
    """A period's ``(kind, attention)`` entries: each kind's layers are
    stacked on their own (``PagedFamily.period``)."""
    seen = {"conv": 0, "full": 0}
    period = []
    for kind in cfg.layer_kinds:
        period.append((kind, functools.partial(_MIXERS[kind], seen[kind])))
        seen[kind] += 1
    return tuple(period)


# -- FFN -------------------------------------------------------------------------

def expert_block_m(rows: int, cfg: ShortConvMoEConfig) -> int:
    """The grouped GEMMs' row block for ``rows`` rows of a dispatch: no taller
    than an expert's expected rows call for (a power of two from 16,
    bfloat16's tile, to 128). Every touched expert streams its tables once a
    block whatever the rows in it; at 5 rows an expert (96 decode rows x 4 of
    64) a 128-row block is 64 x 123 rows of padding to gather and to feed the
    MXU, at 128 rows an expert (a 2,048-row chunk) a block is full."""
    per_expert = max(1, rows * cfg.topk // cfg.n_routed_experts)
    return min(128, max(16, 1 << (per_expert - 1).bit_length()))


def sparse_ffn(cfg: ShortConvMoEConfig, p, h: jax.Array, layer, active=None,
               *, tables):
    """A periodic layer's FFN on this chip: the held experts' part of the
    routed sum (all of it where the chip holds the whole layer). ``tables``:
    the stacked expert tables [periodic layers, held, ., .], read in place."""
    Eh = cfg.n_experts_held
    with jax.named_scope("moe_router"):
        ids, w = route(cfg, p, h)
        lid, counts = held_ids(ids, Eh, cfg.first_held_expert, active)
    with jax.named_scope("moe_routed_experts"):
        routed = held_experts(h, lid, w, tables,
                              (layer - cfg.n_dense_layers) * Eh, Eh,
                              expert_block_m(h.shape[0], cfg))
    return routed.astype(h.dtype), counts


def _segments(cfg: ShortConvMoEConfig, params: dict) -> list:
    """The leading dense run (its own period: one conv layer), then the
    periodic run. The expert tables stay OUT of the scanned params (a scan
    slices what it scans over) and reach ``sparse_ffn`` whole."""
    blocks = params["blocks"]
    tables = tuple(blocks[n] for n in ("we_gate", "we_up", "we_down"))
    rest = {n: a for n, a in blocks.items() if not n.startswith("we_")}
    segs = [(rest, cfg.n_dense_layers, cfg.n_periodic,
             functools.partial(sparse_ffn, tables=tables))]
    if cfg.n_dense_layers:
        segs.insert(0, (params["dense"], 0, cfg.n_dense_layers, swiglu_ffn,
                        (functools.partial(_conv_mixer, None),)))
    return segs


def _tied_head(cfg: ShortConvMoEConfig, params: dict, x: jax.Array, lin):
    """logits = x E^T: the embedding table is the head, held once and
    contracted over its second axis (no [D, V] copy)."""
    del cfg, lin
    return jnp.einsum("rd,vd->rv", x, params["embed"],
                      preferred_element_type=jnp.float32)


SHORT_CONV_MOE = PagedFamily(
    name="short_conv_moe", init_pool=init_pools, segments=_segments,
    period=_period, head=_tied_head,
    counters=COUNTERS + ("conv_state_rows", "attn_full_keys"),
    # a state is the slot's and cannot be rewound, shared or copied by page
    lacks=("speculate", "prefix_cache", "hooks"),
    slot_state=slot_state_bytes, bind=bind,
    # the full layers alone walk pages
    chunk_walks=lambda cfg: plain_chunk_walks(cfg.layers_of("full")))


__all__ = ["ShortConvMoEConfig", "SHORT_CONV_MOE", "init_params",
           "init_pools", "bind", "sparse_ffn", "slot_state_bytes",
           "layer_state_bytes", "kv_bytes_per_token", "expert_block_m",
           "chunk_starts_fresh", "taps_of", "output_gate", "normed_heads",
           "rotated", "route", "ROUTE_EPS"]
