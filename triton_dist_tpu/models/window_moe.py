"""Decoders that mix sliding-window and full attention in one model, with a
parallel block and shared-plus-routed experts (the Cohere Command-A class with
experts), as ONE CHIP'S SHARE of an expert-parallel deployment.

What differs from ``models.llama`` reaches the paged programs as data
(``WINDOW_MOE``, a ``models.llama.PagedFamily``): the decode, multistep and
chunk programs, the layer loop, the engine, the scheduler and the page ledger
are the ones every family uses.

- **Two kinds of layer, ``layer_kinds`` a period.** A ``window`` layer rotates
  q and k (RoPE over the whole head) and row t attends keys ``t - window < j
  <= t``; a ``full`` layer has NO positional encoding and attends every ``j <=
  t``. Both are GQA over K/V pages (``ops.flash_decode``), the window ones
  through the kernels' ``window=``.
- **Each kind has its own page pool.** The full layers' K/V live in the
  ledger's pages under the engine's block table, as a dense model's do, and
  grow with the context. A window layer never needs more than its window and
  the chunk being written, so its K/V live in a RING of ``ring_pages`` pages
  that the engine slot owns (``PagedFamily.slot_ring``): position p in ring
  page ``(p // page) % ring``, whatever the context. The slot's first ring
  page rides the last column of its block-table row.
- **The block is parallel:** ``y = x + attn(u) + ffn(u)``, ``u = LayerNorm(x)``
  (mean-subtracting, weight only), one norm a layer.
- **The FFN** scores all ``n_routed_experts`` with a float32 sigmoid router,
  picks ``topk``, weighs by the picked scores over their sum, and adds the
  MEAN of ``n_shared_experts`` shared experts every token goes through. This
  chip holds ``n_experts_held`` of the routed experts (``models.expert_share``).
  The shared experts run as ONE gated FFN of their summed width (gate and up
  columns side by side, down rows stacked), times ``1 / n_shared_experts``:
  the same sum.
- **The head is tied:** logits = ``LayerNorm(y) E^T * logit_scale`` with the
  embedding table ``E`` held once (the contraction runs over its second axis).

RoPE is half-split (``rotate_half``): the published checkpoints interleave the
rotary pairs, a fixed permutation of weight columns that seeded random weights
absorb.

All of the above is the record's DEFAULT; each point is a field of the config,
and the MiMo-V2-Flash class sets them otherwise (``WindowMoEConfig.tiny_sink``
is that class at test size):

- the kinds may differ in SHAPE (``full_kv_heads``; a learned ``sinks`` logit
  a query head in the window layers' softmax). Their layers are then stacked a
  kind (``blocks["window"]`` [window layers, ...], ``blocks["full"]``), and the
  scanned body takes a period's slice of each (``PagedFamily.period``);
- keys and values may differ in width (``head_dim`` / ``v_head_dim``), the key
  pool padded with zeros to ``k_pool_width`` lanes; the values are scaled by
  ``value_scale`` BEFORE the cache;
- the first ``rope_dims`` of a head rotate and the rest pass through, the full
  layers at ``full_rope_theta`` (0: no position, as above);
- ``n_dense_layers`` leading layers stand OUTSIDE the period: full attention
  and a dense FFN of ``d_ff`` (``params["dense"]``), a segment of their own;
- ``selection_bias``: the k experts are the largest of score + bias, weighed
  by the scores alone; ``n_shared_experts`` 0: no shared expert;
- ``sequential``: the block is ``x + attn(n(x))`` then ``x + ffn(n(x))`` with
  n = RMSNorm, two norms a layer, and the head is untied (``lm_head``): the
  family record is then ``SINK_WINDOW_MOE``, the same but for those fields.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from triton_dist_tpu.models.expert_share import (COUNTERS, held_experts,
                                                 held_ids, sigmoid_route)
from triton_dist_tpu.models.llama import (PagedFamily, gated_ffn, rope,
                                          swiglu_ffn)


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig:
    vocab_size: int = 262144
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 128
    n_kv_heads: int = 8
    head_dim: int = 128
    window: int = 4096
    layer_kinds: tuple = ("window", "window", "window", "full")  # one period
    moe_d_ff: int = 4096               # one expert's FFN
    n_routed_experts: int = 128        # the router's width
    n_experts_held: int = 128          # the experts on this chip ...
    first_held_expert: int = 0         # ... are first_held_expert + [0, held)
    topk: int = 8
    n_shared_experts: int = 4
    rope_theta: float = 50000.0
    norm_eps: float = 1e-5
    logit_scale: float = 1.0
    max_seq_len: int = 200000
    dtype: Any = jnp.bfloat16
    # the engine whose slots own the window layers' rings (``bind``)
    ring_slots: int = 0
    ring_chunk: int = 0
    # what the MiMo-V2-Flash class sets otherwise (module docstring); a zero
    # means "as the fields above say"
    v_head_dim: int = 0                # values' width (0: head_dim)
    k_pool_width: int = 0              # key pool's lanes (0: head_dim)
    full_kv_heads: int = 0             # full layers' KV heads (0: n_kv_heads)
    rope_dims: int = 0                 # leading dims that rotate (0: all)
    full_rope_theta: float = 0.0       # full layers' theta (0: no position)
    sinks: bool = False                # window layers' learned sink logit
    value_scale: float = 1.0           # on v, before the cache
    n_dense_layers: int = 0            # leading full-attention dense layers
    d_ff: int = 0                      # ... and their FFN
    selection_bias: bool = False       # top-k of score + bias
    sequential: bool = False           # two RMSNorms a layer, untied head

    def __post_init__(self):
        assert set(self.layer_kinds) <= {"window", "full"}, self.layer_kinds
        assert self.n_periodic % len(self.layer_kinds) == 0, (
            f"{self.n_periodic} layers are no whole number of periods "
            f"{self.layer_kinds}")
        assert self.head_dim <= self.k_width and self.rope_dims % 2 == 0

    @property
    def n_periodic(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def v_dim(self) -> int:
        return self.v_head_dim or self.head_dim

    @property
    def k_width(self) -> int:
        return self.k_pool_width or self.head_dim

    @property
    def per_kind_stacks(self) -> bool:
        """Whether the kinds' layers differ in shape, and are stacked apart."""
        return self.sinks or self.kv_heads("full") != self.n_kv_heads

    def kv_heads(self, kind: str) -> int:
        return (self.full_kv_heads if kind == "full" else 0) \
            or self.n_kv_heads

    def periodic_layers_of(self, kind: str) -> int:
        """Layers of ``kind`` among the periods: the length of its stack."""
        return (self.n_periodic // len(self.layer_kinds)
                * self.layer_kinds.count(kind))

    def layers_of(self, kind: str) -> int:
        """Layers of ``kind``, the leading dense run's (all full) among them:
        the layers of its pool."""
        return (self.n_dense_layers * (kind == "full")
                + self.periodic_layers_of(kind))

    def ring_pages(self, page_size: int) -> int:
        """Pages a sequence holds in a window layer. The chunk program writes
        its rows before it walks, so the ring spans the window AND a chunk:
        ``window + chunk - 1`` keys starting anywhere in a page. Where the
        window is smaller than the chunk (128 under 512) the chunk is most of
        it: 6 pages of 128 where the window alone needs 2."""
        assert self.ring_chunk > 0, "bind() the config to an engine first"
        return -(-(self.window + self.ring_chunk - 1) // page_size) + 1

    @property
    def paged(self) -> PagedFamily:
        return SINK_WINDOW_MOE if self.sequential else WINDOW_MOE

    @classmethod
    def tiny(cls, n_layers: int = 4, held: int = 16, first: int = 0):
        """Test size: contexts of a few pages cross the window many times."""
        return cls(vocab_size=256, d_model=64, n_layers=n_layers, n_heads=4,
                   n_kv_heads=2, head_dim=16, window=32, moe_d_ff=128,
                   n_routed_experts=16, n_experts_held=held,
                   first_held_expert=first, topk=4, n_shared_experts=2,
                   max_seq_len=256, dtype=jnp.float32)

    @classmethod
    def tiny_sink(cls, held: int = 16, first: int = 0, **changes):
        """The MiMo-V2-Flash class at test size, every mechanism kept: a
        leading dense full layer, then a period (window x 4, full, window) of
        8 / 4 KV heads, keys of 24 in a pool of 32 lanes, values of 16, 8
        rotary dims at two thetas, sinks, 16 bias-selected experts."""
        return dataclasses.replace(cls(
            vocab_size=256, d_model=64, n_layers=7, n_heads=8, n_kv_heads=8,
            head_dim=24, window=32,
            layer_kinds=("window",) * 4 + ("full", "window"), moe_d_ff=128,
            n_routed_experts=16, n_experts_held=held,
            first_held_expert=first, topk=4, n_shared_experts=0,
            rope_theta=1e4, max_seq_len=256, dtype=jnp.float32,
            v_head_dim=16, k_pool_width=32, full_kv_heads=4, rope_dims=8,
            full_rope_theta=5e6, sinks=True, value_scale=0.707,
            n_dense_layers=1, d_ff=256, selection_bias=True,
            sequential=True), **changes)


def bind(cfg: WindowMoEConfig, num_slots: int, prefill_chunk: int
         ) -> WindowMoEConfig:
    return dataclasses.replace(cfg, ring_slots=num_slots,
                               ring_chunk=prefill_chunk)


def layernorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """Mean-subtracting LayerNorm, weight only (no bias)."""
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return ((x32 * rstd) * w).astype(x.dtype)


# -- weights -------------------------------------------------------------------

def init_params(key: jax.Array, cfg: WindowMoEConfig) -> dict:
    """Seeded weights in the layout the programs take: ``blocks`` stacked on
    a leading layer dim; the shared experts' gate, up and down tables
    concatenated along their FFN width; no ``lm_head`` where the head is
    tied. Where the kinds differ in shape (``per_kind_stacks``) ``blocks``
    holds a stack a kind beside the expert tables, which stay stacked over
    all the periodic layers; leading dense layers are ``dense``."""
    L, D, V = cfg.n_periodic, cfg.d_model, cfg.vocab_size
    Hq, Dk, Dv = cfg.n_heads, cfg.head_dim, cfg.v_dim
    Fe, Fs = cfg.moe_d_ff, cfg.moe_d_ff * cfg.n_shared_experts
    E, Eh = cfg.n_routed_experts, cfg.n_experts_held
    keys = iter(jax.random.split(key, 64))
    s, down = 0.02, 0.02 / math.sqrt(2 * cfg.n_layers)

    def w(*shape, scale=s):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(cfg.dtype)

    def layers(n, kind, sparse=True):
        """``n`` layers of one kind: attention, then the FFN's small leaves."""
        Hkv = cfg.kv_heads(kind)
        p = {"attn_norm": jnp.ones((n, D), jnp.float32),
             "wq": w(n, D, Hq * Dk), "wk": w(n, D, Hkv * Dk),
             "wv": w(n, D, Hkv * Dv), "wo": w(n, Hq * Dv, D, scale=down)}
        if cfg.sinks and kind == "window":
            p["sinks"] = jax.random.normal(next(keys), (n, Hq), jnp.float32)
        if cfg.sequential:
            p["mlp_norm"] = jnp.ones((n, D), jnp.float32)
        if not sparse:
            F = cfg.d_ff
            return {**p, "w_gate": w(n, D, F), "w_up": w(n, D, F),
                    "w_down": w(n, F, D, scale=down)}
        p["w_router"] = jax.random.normal(next(keys), (n, D, E),
                                          jnp.float32) * s
        if cfg.selection_bias:
            p["router_bias"] = jax.random.normal(next(keys), (n, E),
                                                 jnp.float32) * 0.1
        if Fs:
            p.update(ws_gate=w(n, D, Fs), ws_up=w(n, D, Fs),
                     ws_down=w(n, Fs, D, scale=down))
        return p

    if cfg.per_kind_stacks:
        blocks = {kind: layers(cfg.periodic_layers_of(kind), kind)
                  for kind in ("window", "full")}
    else:
        blocks = layers(L, "window")
    blocks.update(we_gate=w(L, Eh, D, Fe), we_up=w(L, Eh, D, Fe),
                  we_down=w(L, Eh, Fe, D, scale=down))
    params = {"embed": w(V, D), "blocks": blocks,
              "final_norm": jnp.ones((D,), jnp.float32)}
    if cfg.n_dense_layers:
        params["dense"] = layers(cfg.n_dense_layers, "full", sparse=False)
    if cfg.sequential:
        params["lm_head"] = w(D, V)
    return params


# -- cache -----------------------------------------------------------------------

def init_pools(cfg: WindowMoEConfig, num_pages: int, page_size: int) -> dict:
    """Two kinds of page: ``k`` / ``v`` [full layers, num_pages, Hkv, page, .]
    are the ledger's pages (``models.llama.init_page_pool``'s life: carried
    whole, written and read in place); ``wk`` / ``wv`` [window layers, 1 +
    slots x ring, Hkv, page, .] hold a scratch page and every slot's ring.
    Keys are ``k_width`` wide (the head's, or its lane-padded width), values
    ``v_dim``; each kind has its own KV heads."""
    assert page_size % 8 == 0, f"page_size {page_size} must be 8-aligned"
    assert cfg.ring_slots > 0, "bind() the config to an engine first"
    full = (cfg.layers_of("full"), num_pages, cfg.kv_heads("full"), page_size)
    ring = (cfg.layers_of("window"),
            1 + cfg.ring_slots * cfg.ring_pages(page_size),
            cfg.kv_heads("window"), page_size)
    zeros = lambda shape, d: jnp.zeros(shape + (d,), cfg.dtype)  # noqa: E731
    return {"k": zeros(full, cfg.k_width), "v": zeros(full, cfg.v_dim),
            "wk": zeros(ring, cfg.k_width), "wv": zeros(ring, cfg.v_dim)}


# -- attention -------------------------------------------------------------------

# Rows of a prefill chunk that share one walk, and the scoped VMEM their block
# needs. With 16 query heads a KV head, 16 rows are a [256, 128] operand a
# head (Mistral's at 64 rows) and fit Mosaic's 16 MB default; 32 rows need
# more. Chosen on the v5e (PERF.md section 6, PR 30, call 1,
# scripts/prefill_attn_probe.py --group 16 --chunk 2048; ms a layer at 6k /
# 20k tokens of context): window layers 11.63 / 11.63 at 16 rows, 10.60 /
# 10.60 at 32, 10.41 / 10.41 at 64; the full layer 22.8 / 57.7, 19.0 / 51.4,
# 18.0 / 50.9.
CHUNK_ROWS_PER_BLOCK = 32
CHUNK_VMEM_LIMIT = 48 << 20


def _rotate(cfg: WindowMoEConfig, x: jax.Array, positions, theta: float):
    """RoPE on x [R, H, Dk] at ``positions`` [R, 1]: the whole head, or its
    first ``rope_dims`` (half-split among themselves), the rest unchanged."""
    r = cfg.rope_dims
    if not r:
        return rope(x[:, None], positions, theta)[:, 0]
    return jnp.concatenate(
        [rope(x[:, None, :, :r], positions, theta)[:, 0], x[..., r:]], -1)


def _attention(kind: str, rank: int, cfg: WindowMoEConfig, p, h, layer, pool,
               block_table, pos, kv_len, active, shared_table, lin, attn_io):
    """Layer ``layer``, the ``rank``-th ``kind`` layer of its period (None:
    a layer of the leading dense run). The block table's last column is the
    first page of the slot's ring; the columns before it are the sequence's
    pages in the full layers' pool."""
    from triton_dist_tpu.ops.flash_decode import (gqa_decode_paged,
                                                  gqa_prefill_paged,
                                                  paged_kv_write)
    assert attn_io is None, "the window family has no attn_io hook"
    R = h.shape[0]
    Hq, Hkv, Dk, Dv = cfg.n_heads, cfg.kv_heads(kind), cfg.head_dim, cfg.v_dim
    windowed = kind == "window"
    # this layer's index among the layers of its kind: the leading run's
    # layers are all full; after it, those of the periods before, then rank
    P, dense = len(cfg.layer_kinds), cfg.n_dense_layers
    mine = layer if rank is None else (
        dense * (kind == "full") + (layer - dense if dense else layer) // P
        * cfg.layer_kinds.count(kind) + rank)
    with jax.named_scope("swa_attention" if windowed else "full_attention"):
        q = lin(h, p["wq"], "wq").reshape(R, Hq, Dk)
        k = lin(h, p["wk"], "wk").reshape(R, Hkv, Dk)
        v = lin(h, p["wv"], "wv").reshape(R, Hkv, Dv)
        if cfg.value_scale != 1.0:
            v = v * cfg.value_scale
        live = kv_len > 0 if active is None else jnp.logical_and(
            active, kv_len > 0)
        theta = cfg.rope_theta if windowed else cfg.full_rope_theta
        if theta:
            positions = pos[:, None].astype(jnp.int32)
            q = _rotate(cfg, q, positions, theta)
            k = _rotate(cfg, k, positions, theta)
        extra = {}
        if cfg.k_width != Dk:
            # the key pool's lanes past the head are zeros: the scores are
            # the head's own, and so is their scale
            pad = ((0, 0), (0, 0), (0, cfg.k_width - Dk))
            q, k = jnp.pad(q, pad), jnp.pad(k, pad)
            extra["sm_scale"] = Dk ** -0.5
        if windowed and cfg.sinks:
            extra["sinks"] = p["sinks"]
        if windowed:
            names, window = ("wk", "wv"), cfg.window
            page_size = pool["wk"].shape[-2]
            ring = cfg.ring_pages(page_size)
            # the slot's ring as a table; position p lands in its page
            # (p // page) % ring, which is where p % (ring x page) lands
            table = block_table[:, -1:] + jnp.arange(ring, dtype=jnp.int32)
            at = pos % (ring * page_size)
            attended, counter = jnp.minimum(kv_len, window), "attn_window_keys"
        else:
            names, window = ("k", "v"), None
            table, at = block_table[:, :-1], pos
            attended, counter = kv_len, "attn_full_keys"
        counts = {counter: jnp.sum(jnp.where(live, attended, 0)
                                   ).astype(jnp.int32)}
        kp, vp = paged_kv_write(pool[names[0]], pool[names[1]], k, v, table,
                                at, active=active, layer=mine,
                                shared_table=shared_table)
        if shared_table:
            attn = gqa_prefill_paged(
                q, kp, vp, table[0], kv_len, layer=mine, window=window,
                rows_per_block=CHUNK_ROWS_PER_BLOCK,
                vmem_limit_bytes=CHUNK_VMEM_LIMIT, **extra)
        else:
            attn, _lse = gqa_decode_paged(q, kp, vp, table, kv_len,
                                          layer=mine, window=window, **extra)
        out = lin(attn.reshape(R, Hq * Dv), p["wo"], "wo")
    return out, {**pool, names[0]: kp, names[1]: vp}, counts


def _period(cfg: WindowMoEConfig) -> tuple:
    """A period's attentions; ``(kind, attention)`` where each kind's layers
    are stacked on their own (``PagedFamily.period``)."""
    seen = {"window": 0, "full": 0}
    period = []
    for kind in cfg.layer_kinds:
        attention = functools.partial(_attention, kind, seen[kind])
        period.append((kind, attention) if cfg.per_kind_stacks else attention)
        seen[kind] += 1
    return tuple(period)


# -- FFN -------------------------------------------------------------------------

def sparse_ffn(cfg: WindowMoEConfig, p, h: jax.Array, layer, active=None, *,
               tables, block_m: int = 128):
    """A layer's FFN on this chip: the held experts' part of the routed sum
    (``expert_share``: no scaling factor; the k experts chosen by score +
    ``router_bias`` where the config has a selection bias) plus the mean of
    the shared experts, if any, which run as one gated FFN of their summed
    width. ``tables``: the stacked expert tables [periodic layers, held, .,
    .], read in place."""
    Eh = cfg.n_experts_held
    with jax.named_scope("moe_router"):
        ids, w = sigmoid_route(
            h, p["w_router"], cfg.topk,
            bias=p["router_bias"] if cfg.selection_bias else None)
        lid, counts = held_ids(ids, Eh, cfg.first_held_expert, active)
    if cfg.n_dense_layers:
        layer = layer - cfg.n_dense_layers
    with jax.named_scope("moe_routed_experts"):
        routed = held_experts(h, lid, w, tables, layer * Eh, Eh, block_m)
    if not cfg.n_shared_experts:
        return routed.astype(h.dtype), counts
    with jax.named_scope("moe_shared_experts"):
        shared = gated_ffn(h, p["ws_gate"], p["ws_up"], p["ws_down"])
    mean = shared.astype(jnp.float32) * (1.0 / cfg.n_shared_experts)
    return (routed + mean).astype(h.dtype), counts


def _segments(cfg: WindowMoEConfig, params: dict) -> list:
    """The leading dense run (its own period: one full layer), then the
    periodic run. The expert tables stay OUT of the scanned params (a scan
    slices what it scans over) and reach ``sparse_ffn`` whole."""
    blocks = params["blocks"]
    tables = tuple(blocks[n] for n in ("we_gate", "we_up", "we_down"))
    rest = {n: a for n, a in blocks.items() if not n.startswith("we_")}
    segs = [(rest, cfg.n_dense_layers, cfg.n_periodic,
             functools.partial(sparse_ffn, tables=tables))]
    if cfg.n_dense_layers:
        segs.insert(0, (params["dense"], 0, cfg.n_dense_layers, swiglu_ffn,
                        (functools.partial(_attention, "full", None),)))
    return segs


def _tied_head(cfg: WindowMoEConfig, params: dict, x: jax.Array, lin):
    """logits = x E^T * logit_scale: the embedding table is the head, held
    once and contracted over its second axis (no [D, V] copy)."""
    del lin
    logits = jnp.einsum("rd,vd->rv", x, params["embed"],
                        preferred_element_type=jnp.float32)
    return logits if cfg.logit_scale == 1.0 else logits * cfg.logit_scale


WINDOW_MOE = PagedFamily(
    name="window_moe", init_pool=init_pools, segments=_segments,
    period=_period, norm=layernorm, parallel=True, head=_tied_head,
    counters=COUNTERS + ("attn_window_keys", "attn_full_keys"),
    # ring pages are the slot's: nothing copies, exports or indexes them
    lacks=("speculate", "prefix_cache", "hooks"),
    slot_ring=lambda cfg, page_size: cfg.ring_pages(page_size), bind=bind,
    chunk_walks=lambda cfg: (
        (cfg.layers_of("window"), CHUNK_ROWS_PER_BLOCK, cfg.window),
        (cfg.layers_of("full"), CHUNK_ROWS_PER_BLOCK, None)))

# ``sequential`` configs: RMSNorm twice a layer and an untied ``lm_head``
# (``PagedFamily``'s defaults); everything else is the record above
SINK_WINDOW_MOE = dataclasses.replace(
    WINDOW_MOE, name="sink_window_moe", norm=None, parallel=False, head=None)


__all__ = ["WindowMoEConfig", "WINDOW_MOE", "SINK_WINDOW_MOE", "init_params",
           "init_pools", "bind", "layernorm", "sparse_ffn"]
