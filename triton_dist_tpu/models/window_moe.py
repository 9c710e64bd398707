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
from triton_dist_tpu.models.llama import PagedFamily, gated_ffn, rope


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

    def __post_init__(self):
        assert set(self.layer_kinds) <= {"window", "full"}, self.layer_kinds
        assert self.n_layers % len(self.layer_kinds) == 0, (
            f"{self.n_layers} layers are no whole number of periods "
            f"{self.layer_kinds}")

    def layers_of(self, kind: str) -> int:
        return (self.n_layers // len(self.layer_kinds)
                * self.layer_kinds.count(kind))

    def ring_pages(self, page_size: int) -> int:
        """Pages a sequence holds in a window layer: the chunk program writes
        its rows before it walks, so the ring spans the window AND a chunk."""
        assert self.ring_chunk > 0, "bind() the config to an engine first"
        return -(-(self.window + self.ring_chunk - 1) // page_size) + 1

    @property
    def paged(self) -> PagedFamily:
        return WINDOW_MOE

    @classmethod
    def tiny(cls, n_layers: int = 4, held: int = 16, first: int = 0):
        """Test size: contexts of a few pages cross the window many times."""
        return cls(vocab_size=256, d_model=64, n_layers=n_layers, n_heads=4,
                   n_kv_heads=2, head_dim=16, window=32, moe_d_ff=128,
                   n_routed_experts=16, n_experts_held=held,
                   first_held_expert=first, topk=4, n_shared_experts=2,
                   max_seq_len=256, dtype=jnp.float32)


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
    concatenated along their FFN width; no ``lm_head`` (tied)."""
    L, D, V = cfg.n_layers, cfg.d_model, cfg.vocab_size
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Fe, Fs = cfg.moe_d_ff, cfg.moe_d_ff * cfg.n_shared_experts
    E, Eh = cfg.n_routed_experts, cfg.n_experts_held
    keys = iter(jax.random.split(key, 16))
    s, down = 0.02, 0.02 / math.sqrt(2 * L)

    def w(*shape, scale=s):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(cfg.dtype)

    blocks = {"attn_norm": jnp.ones((L, D), jnp.float32),
              "wq": w(L, D, Hq * Dh), "wk": w(L, D, Hkv * Dh),
              "wv": w(L, D, Hkv * Dh), "wo": w(L, Hq * Dh, D, scale=down),
              "w_router": jax.random.normal(next(keys), (L, D, E),
                                            jnp.float32) * s,
              "we_gate": w(L, Eh, D, Fe), "we_up": w(L, Eh, D, Fe),
              "we_down": w(L, Eh, Fe, D, scale=down),
              "ws_gate": w(L, D, Fs), "ws_up": w(L, D, Fs),
              "ws_down": w(L, Fs, D, scale=down)}
    return {"embed": w(V, D), "blocks": blocks,
            "final_norm": jnp.ones((D,), jnp.float32)}


# -- cache -----------------------------------------------------------------------

def init_pools(cfg: WindowMoEConfig, num_pages: int, page_size: int) -> dict:
    """Two kinds of page: ``k`` / ``v`` [full layers, num_pages, Hkv, page, Dh]
    are the ledger's pages (``models.llama.init_page_pool``'s life: carried
    whole, written and read in place); ``wk`` / ``wv`` [window layers, 1 +
    slots x ring, Hkv, page, Dh] hold a scratch page and every slot's ring."""
    assert page_size % 8 == 0, f"page_size {page_size} must be 8-aligned"
    assert cfg.ring_slots > 0, "bind() the config to an engine first"
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
    full = (cfg.layers_of("full"), num_pages, Hkv, page_size, Dh)
    ring = (cfg.layers_of("window"),
            1 + cfg.ring_slots * cfg.ring_pages(page_size), Hkv, page_size, Dh)
    zeros = lambda shape: jnp.zeros(shape, cfg.dtype)       # noqa: E731
    return {"k": zeros(full), "v": zeros(full),
            "wk": zeros(ring), "wv": zeros(ring)}


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


def _attention(kind: str, rank: int, cfg: WindowMoEConfig, p, h, layer, pool,
               block_table, pos, kv_len, active, shared_table, lin, attn_io):
    """Layer ``layer``, the ``rank``-th ``kind`` layer of its period. The
    block table's last column is the first page of the slot's ring; the
    columns before it are the sequence's pages in the full layers' pool."""
    from triton_dist_tpu.ops.flash_decode import (gqa_decode_paged,
                                                  gqa_prefill_paged,
                                                  paged_kv_write)
    assert attn_io is None, "the window family has no attn_io hook"
    R = h.shape[0]
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    windowed = kind == "window"
    # this layer's index among the layers of its kind
    mine = (layer // len(cfg.layer_kinds)) * cfg.layer_kinds.count(kind) + rank
    with jax.named_scope("swa_attention" if windowed else "full_attention"):
        q = lin(h, p["wq"], "wq").reshape(R, Hq, Dh)
        k = lin(h, p["wk"], "wk").reshape(R, Hkv, Dh)
        v = lin(h, p["wv"], "wv").reshape(R, Hkv, Dh)
        live = kv_len > 0 if active is None else jnp.logical_and(
            active, kv_len > 0)
        if windowed:
            positions = pos[:, None].astype(jnp.int32)
            q = rope(q[:, None], positions, cfg.rope_theta)[:, 0]
            k = rope(k[:, None], positions, cfg.rope_theta)[:, 0]
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
                                at, active=active, layer=mine)
        if shared_table:
            attn = gqa_prefill_paged(
                q, kp, vp, table[0], kv_len, layer=mine, window=window,
                rows_per_block=CHUNK_ROWS_PER_BLOCK,
                vmem_limit_bytes=CHUNK_VMEM_LIMIT)
        else:
            attn, _lse = gqa_decode_paged(q, kp, vp, table, kv_len,
                                          layer=mine, window=window)
        out = lin(attn.reshape(R, Hq * Dh), p["wo"], "wo")
    return out, {**pool, names[0]: kp, names[1]: vp}, counts


def _period(cfg: WindowMoEConfig) -> tuple:
    seen = {"window": 0, "full": 0}
    period = []
    for kind in cfg.layer_kinds:
        period.append(functools.partial(_attention, kind, seen[kind]))
        seen[kind] += 1
    return tuple(period)


# -- FFN -------------------------------------------------------------------------

def sparse_ffn(cfg: WindowMoEConfig, p, h: jax.Array, layer, active=None, *,
               tables, block_m: int = 128):
    """A layer's FFN on this chip: the held experts' part of the routed sum
    (``expert_share``: no selection bias, no scaling factor) plus the mean of
    the shared experts, which run as one gated FFN of their summed width.
    ``tables``: the stacked expert tables [L, held, ., .], read in place."""
    Eh = cfg.n_experts_held
    with jax.named_scope("moe_router"):
        ids, w = sigmoid_route(h, p["w_router"], cfg.topk)
        lid, counts = held_ids(ids, Eh, cfg.first_held_expert, active)
    with jax.named_scope("moe_routed_experts"):
        routed = held_experts(h, lid, w, tables, layer * Eh, Eh, block_m)
    with jax.named_scope("moe_shared_experts"):
        shared = gated_ffn(h, p["ws_gate"], p["ws_up"], p["ws_down"])
    mean = shared.astype(jnp.float32) * (1.0 / cfg.n_shared_experts)
    return (routed + mean).astype(h.dtype), counts


def _segments(cfg: WindowMoEConfig, params: dict) -> list:
    """One run of layers. The expert tables stay OUT of the scanned params
    (a scan slices what it scans over) and reach ``sparse_ffn`` whole."""
    blocks = params["blocks"]
    tables = tuple(blocks[n] for n in ("we_gate", "we_up", "we_down"))
    rest = {n: a for n, a in blocks.items() if not n.startswith("we_")}
    return [(rest, 0, cfg.n_layers,
             functools.partial(sparse_ffn, tables=tables))]


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
    slot_ring=lambda cfg, page_size: cfg.ring_pages(page_size), bind=bind)


__all__ = ["WindowMoEConfig", "WINDOW_MOE", "init_params", "init_pools",
           "bind", "layernorm", "sparse_ffn"]
