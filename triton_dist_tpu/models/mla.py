"""Latent-attention, shared-plus-routed-expert decoders (the DeepSeek-V3 /
Kimi-K2 family), as ONE CHIP'S SHARE of an expert-parallel deployment.

What differs from ``models.llama`` is the layer, and it reaches the paged
programs as data (``LATENT_MOE``, a ``models.llama.PagedFamily``): the decode,
multistep and chunk programs, the layer loop, the engine, the scheduler and
the page ledger are the ones every family uses.

- **Attention is multi-head latent attention (MLA).** A token's cache is one
  row a layer, ``[c | k_rope | pad]``: the RMS-normed compressed key-value
  row ``c`` (``kv_lora_rank``) and the one rotary key all heads share, padded
  to the lane width (``cache_width``). Both paged programs attend in the
  ABSORBED form (``ops.mla_decode``): ``q' = q_nope W_uk`` per head, score =
  ``[q' | q_rope] . [c | k_rope]``, value = ``c``, then ``W_uv`` and ``W_o``.
  The chunk's C rows go through the same kernel in row blocks that share the
  sequence's block table, so a page is read once a row block. The plain form
  (``latent_attention_plain``: up-project the cached rows to per-head keys
  and values first) is the non-paged ``forward``'s and the tests'.
- **The FFN is dense in the leading ``n_dense_layers`` and sparse after.**
  A sparse layer scores all ``n_routed_experts`` with a float32 sigmoid
  router, picks ``topk`` by score + ``router_bias``, weighs by the picked
  scores over their sum times ``routed_scaling_factor``, and adds a shared
  expert every token goes through. THIS CHIP HOLDS ``n_experts_held`` of the
  routed experts, ids ``first_held_expert ..``: it routes over all of them
  and computes the part of the sum its own experts give (the other ids
  become -1 and ``ops.group_gemm.apply_grouped`` drops them); what the
  absent experts would add is left out and the partial sum goes on to the
  next layer. No exchange, and nothing that stands in for one. With
  ``n_experts_held == n_routed_experts`` it is the whole layer.

RoPE is YaRN (``yarn_inv_freq``), half-split (``rotate_half``) layout: the
published checkpoints interleave the rotary pairs first, a fixed permutation
of weight columns that seeded random weights absorb.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from triton_dist_tpu.models.expert_share import (COUNTERS, held_experts,
                                                 held_ids, sigmoid_route)
from triton_dist_tpu.models.llama import (PagedFamily, gated_ffn, rmsnorm,
                                          swiglu_ffn)


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    vocab_size: int = 163840
    d_model: int = 7168
    n_layers: int = 61                 # dense + sparse
    n_dense_layers: int = 1            # first_k_dense_replace
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 18432                  # the dense layers' FFN
    moe_d_ff: int = 2048               # one expert's FFN
    n_routed_experts: int = 384        # the router's width
    n_experts_held: int = 384          # the experts on this chip ...
    first_held_expert: int = 0         # ... are first_held_expert + [0, held)
    topk: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.827
    rope_theta: float = 50000.0
    rope_factor: float = 32.0          # YaRN
    rope_original_max_pos: int = 4096
    rope_beta_fast: float = 1.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    norm_eps: float = 1e-6
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def cache_width(self) -> int:
        """Stored values a token a layer: ``c`` and ``k_rope``, padded to a
        multiple of the 128-lane width (the kernel's one dot product runs
        over the whole stored row)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def sm_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    @property
    def paged(self) -> PagedFamily:
        return LATENT_MOE

    @classmethod
    def tiny(cls, n_layers: int = 3, held: int = 16, first: int = 0):
        """Test size: every kernel dim stays tile-friendly."""
        return cls(vocab_size=256, d_model=128, n_layers=n_layers,
                   n_heads=2, q_lora_rank=64, kv_lora_rank=128,
                   qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
                   d_ff=256, moe_d_ff=128, n_routed_experts=16,
                   n_experts_held=held, first_held_expert=first, topk=4,
                   rope_original_max_pos=32, rope_factor=4.0,
                   max_seq_len=256, dtype=jnp.float32)


# -- rotary embedding (YaRN) ---------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: LatentMoEConfig) -> np.ndarray:
    """Inverse frequencies [rope_dim / 2]: ``1 / theta^(2i/d)`` below the
    first correction index, that over ``factor`` above the second, a linear
    blend between (the indices where a dimension turns ``beta_fast`` /
    ``beta_slow`` times over the original context)."""
    d, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    inter = extra / cfg.rope_factor

    def corr(turns):
        return d * math.log(cfg.rope_original_max_pos
                            / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.rope_beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def yarn_rope(x: jax.Array, positions: jax.Array,
              cfg: LatentMoEConfig) -> jax.Array:
    """x [..., d] at ``positions`` [...] (broadcast against x's leading
    dims); half-split layout."""
    half = x.shape[-1] // 2
    scale = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
             / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    ang = positions[..., None].astype(jnp.float32) * yarn_inv_freq(cfg)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


# -- weights -------------------------------------------------------------------

def init_params(key: jax.Array, cfg: LatentMoEConfig,
                bias_std: float = 0.001) -> dict:
    """Seeded weights in the layout the programs take: ``dense`` and
    ``blocks`` are the leading dense layers and the sparse layers, each
    stacked on a leading layer dim. The key-value up-projection is held as
    its two per-head halves, ``w_uk`` [L, H, nope, c] (absorbed into the
    query) and ``w_uv`` [L, H, c, v] (applied to the output): the published
    ``kv_b_proj`` [c, H * (nope + v)] with its columns regrouped."""
    D, H, V = cfg.d_model, cfg.n_heads, cfg.vocab_size
    qr, c = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rp, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    F, Fe, Fs = cfg.d_ff, cfg.moe_d_ff, cfg.moe_d_ff * cfg.n_shared_experts
    E, Eh = cfg.n_routed_experts, cfg.n_experts_held
    keys = iter(jax.random.split(key, 40))
    s, down = 0.02, 0.02 / math.sqrt(2 * cfg.n_layers)

    def w(*shape, scale=s):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(cfg.dtype)

    def attn(L):
        return {"attn_norm": jnp.ones((L, D), jnp.float32),
                "wq_a": w(L, D, qr), "q_norm": jnp.ones((L, qr), jnp.float32),
                "wq_b": w(L, qr, H * (nope + rp)),
                "wkv_a": w(L, D, c + rp),
                "kv_norm": jnp.ones((L, c), jnp.float32),
                "w_uk": w(L, H, nope, c), "w_uv": w(L, H, c, vd),
                "wo": w(L, H * vd, D, scale=down),
                "mlp_norm": jnp.ones((L, D), jnp.float32)}

    Ld, Lm = cfg.n_dense_layers, cfg.n_moe_layers
    dense = {**attn(Ld), "w_gate": w(Ld, D, F), "w_up": w(Ld, D, F),
             "w_down": w(Ld, F, D, scale=down)}
    blocks = {**attn(Lm),
              "w_router": jax.random.normal(next(keys), (Lm, D, E),
                                            jnp.float32) * s,
              "router_bias": jax.random.normal(next(keys), (Lm, E),
                                               jnp.float32) * bias_std,
              "we_gate": w(Lm, Eh, D, Fe), "we_up": w(Lm, Eh, D, Fe),
              "we_down": w(Lm, Eh, Fe, D, scale=down),
              "ws_gate": w(Lm, D, Fs), "ws_up": w(Lm, D, Fs),
              "ws_down": w(Lm, Fs, D, scale=down)}
    return {"embed": w(V, D), "dense": dense, "blocks": blocks,
            "final_norm": jnp.ones((D,), jnp.float32), "lm_head": w(D, V)}


# -- attention -------------------------------------------------------------------

def latent_qkv(cfg: LatentMoEConfig, p, h: jax.Array, positions: jax.Array,
               lin=None):
    """The projections of one layer on ``h`` [..., D] at ``positions``
    [...]: (q_nope [..., H, nope], q_rope [..., H, rope] rotated, c [..., c]
    normed, k_rope [..., rope] rotated)."""
    lin = lin or (lambda x, w, name: x @ w)
    H, nope, rp = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    c_q = rmsnorm(lin(h, p["wq_a"], "wq_a"), p["q_norm"], cfg.norm_eps)
    q = lin(c_q, p["wq_b"], "wq_b").reshape(h.shape[:-1] + (H, nope + rp))
    kv = lin(h, p["wkv_a"], "wkv_a")
    c = rmsnorm(kv[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = yarn_rope(kv[..., cfg.kv_lora_rank:], positions, cfg)
    q_rope = yarn_rope(q[..., nope:], positions[..., None], cfg)
    return q[..., :nope], q_rope, c, k_rope


def latent_attention_plain(cfg: LatentMoEConfig, p, q_nope, q_rope, c,
                           k_rope, mask) -> jax.Array:
    """The PLAIN form over one sequence: queries [T, H, .] against cached
    rows ``c`` [S, c], ``k_rope`` [S, rope], up-projected to per-head keys
    and values first; ``mask`` [T, S] bool. Returns [T, H * v]."""
    f32 = jnp.float32
    k_nope = jnp.einsum("sc,hdc->shd", c, p["w_uk"],
                        preferred_element_type=f32)
    v = jnp.einsum("sc,hcv->shv", c, p["w_uv"], preferred_element_type=f32)
    scores = (jnp.einsum("thd,shd->hts", q_nope.astype(f32), k_nope)
              + jnp.einsum("thr,sr->hts", q_rope.astype(f32),
                           k_rope.astype(f32))) * cfg.sm_scale
    scores = jnp.where(mask[None], scores, -1e30)
    out = jnp.einsum("hts,shv->thv", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(out.shape[0], -1).astype(q_nope.dtype)


def init_latent_pool(cfg: LatentMoEConfig, num_pages: int,
                     page_size: int) -> dict:
    """The latent page pool: ONE array [L, P, page_size, cache_width], a row
    a token a layer (``[c | k_rope | 0]``). Same life as the K/V pool of
    ``models.llama.init_page_pool``: carried whole through the layer loop,
    written by the row scatter, read in place by the kernel."""
    assert page_size % 8 == 0, f"page_size {page_size} must be 8-aligned"
    return {"ckv": jnp.zeros((cfg.n_layers, num_pages, page_size,
                              cfg.cache_width), cfg.dtype)}


# Rows of a prefill chunk that share ONE walk of the sequence's latent pages
# (``ops.mla_decode``: a row block x 64 heads = 1,024 rows of one MXU operand
# meet a group of live pages in one online-softmax update; the pages a group
# stand beside the kernel). From the kernel alone on a v5e at the published
# widths (PERF.md section 6, PR 34, ``scripts/mla_probe.py``, us a layer call
# at 3,584 tokens of context): 8 rows a block 2,152, 16 rows 1,949, 32 rows
# 1,874 at twice the VMEM (over Mosaic's 16 MB default) and twice the code.
CHUNK_ROWS_PER_BLOCK = 16


def _latent_attention(cfg: LatentMoEConfig, p, h, layer, pool, block_table,
                      pos, kv_len, active, shared_table, lin, attn_io):
    from triton_dist_tpu.ops.flash_decode import paged_rows_write
    from triton_dist_tpu.ops.mla_decode import mla_decode_paged
    assert attn_io is None, "the latent family has no attn_io hook"
    R = h.shape[0]
    with jax.named_scope("mla_attention"):
        q_nope, q_rope, c, k_rope = latent_qkv(cfg, p, h, pos, lin)
        pad = cfg.cache_width - cfg.kv_lora_rank - cfg.qk_rope_head_dim
        row = jnp.concatenate(
            [c, k_rope, jnp.zeros((R, pad), c.dtype)], axis=-1)
        ckv = paged_rows_write(pool["ckv"], row, block_table, pos,
                               active=active, layer=layer)
        q_abs = jnp.einsum("rhd,hdc->rhc", q_nope, p["w_uk"])
        q_cat = jnp.concatenate(
            [q_abs, q_rope, jnp.zeros((R, cfg.n_heads, pad), q_abs.dtype)],
            axis=-1)                                       # [R, H, W]
        # a chunk's rows share one table, and so the walk of its pages, a
        # row block at a time; decode rows, a table each, walk alone
        shared = shared_table and R % CHUNK_ROWS_PER_BLOCK == 0
        o = mla_decode_paged(
            q_cat, ckv, block_table, kv_len, layer=layer,
            latent_dim=cfg.kv_lora_rank, sm_scale=cfg.sm_scale,
            rows_per_block=CHUNK_ROWS_PER_BLOCK if shared else 1)
        out = jnp.einsum("rhc,hcv->rhv", o, p["w_uv"])
        return lin(out.reshape(R, -1), p["wo"], "wo"), {"ckv": ckv}


# -- FFN -------------------------------------------------------------------------

def route(cfg: LatentMoEConfig, h: jax.Array, w_router: jax.Array,
          bias: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(expert ids [R, k], weights [R, k] float32): sigmoid scores in
    float32 over ALL routed experts, the k largest of score + bias chosen,
    weighed by their scores (without the bias) over their sum, times the
    scaling factor (``expert_share.sigmoid_route``)."""
    return sigmoid_route(h, w_router, cfg.topk, bias,
                         cfg.routed_scaling_factor)


def sparse_ffn(cfg: LatentMoEConfig, p, h: jax.Array, layer, active=None,
               *, tables, block_m: int = 128):
    """A sparse layer's FFN on this chip: the held experts' part of the
    routed sum plus the shared expert. ``p`` is the layer's params without
    its expert tables; ``tables`` are the STACKED gate, up and down tables
    [Lm, held, ., .] of all sparse layers, read in place at ``layer`` (the
    model's layer index, traced or not: ``expert_share.held_experts``). Rows
    masked off by ``active`` are routed nowhere and not counted. Returns
    (out, {assignments that landed on held experts, held experts with at
    least one row}: ``expert_share.COUNTERS``)."""
    Eh = cfg.n_experts_held
    with jax.named_scope("moe_router"):
        ids, w = route(cfg, h, p["w_router"], p["router_bias"])
        lid, counts = held_ids(ids, Eh, cfg.first_held_expert, active)
    with jax.named_scope("moe_routed_experts"):
        routed = held_experts(h, lid, w, tables,
                              (layer - cfg.n_dense_layers) * Eh, Eh, block_m)
    with jax.named_scope("moe_shared_expert"):
        shared = gated_ffn(h, p["ws_gate"], p["ws_up"], p["ws_down"])
    return (routed + shared.astype(jnp.float32)).astype(h.dtype), counts


def _segments(cfg: LatentMoEConfig, params: dict) -> list:
    """The leading dense layers, then the sparse ones. The expert tables
    stay OUT of the scanned params (a scan slices what it scans over) and
    reach ``sparse_ffn`` whole."""
    blocks = params["blocks"]
    tables = tuple(blocks[n] for n in ("we_gate", "we_up", "we_down"))
    rest = {n: a for n, a in blocks.items() if not n.startswith("we_")}
    segs = [(params["dense"], 0, cfg.n_dense_layers, swiglu_ffn),
            (rest, cfg.n_dense_layers, cfg.n_moe_layers,
             functools.partial(sparse_ffn, tables=tables))]
    return [s for s in segs if s[2]]


def forward(params: dict, tokens: jax.Array, cfg: LatentMoEConfig
            ) -> jax.Array:
    """Full-sequence forward, no cache: tokens [B, S] -> logits [B, S, V]
    float32. Attention in the PLAIN form; the same FFNs as the paged
    programs. For tests, not a serving path."""
    from triton_dist_tpu.models.llama import LayerParams
    B, S = tokens.shape
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    causal = jnp.tril(jnp.ones((S, S), jnp.bool_))
    for blocks, first, n, ffn in _segments(cfg, params):
        for j in range(n):
            p = LayerParams(blocks, j)
            h = rmsnorm(x, p["attn_norm"], cfg.norm_eps)
            qn, qr, c, kr = latent_qkv(cfg, p, h, positions)
            attn = jax.vmap(lambda a, b, c_, d: latent_attention_plain(
                cfg, p, a, b, c_, d, causal))(qn, qr, c, kr)
            x = x + attn @ p["wo"]
            h = rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
            ff, _ = ffn(cfg, p, h.reshape(B * S, -1), first + j, None)
            x = x + ff.reshape(B, S, -1).astype(x.dtype)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return (x @ params["lm_head"]).astype(jnp.float32)


LATENT_MOE = PagedFamily(
    name="latent_moe", init_pool=init_latent_pool, segments=_segments,
    attention=_latent_attention,
    counters=COUNTERS, lacks=("speculate", "prefix_cache", "hooks"))


__all__ = ["LatentMoEConfig", "LATENT_MOE", "init_params", "forward",
           "init_latent_pool", "latent_qkv", "latent_attention_plain",
           "route", "sparse_ffn", "yarn_inv_freq", "yarn_rope", "yarn_mscale"]
