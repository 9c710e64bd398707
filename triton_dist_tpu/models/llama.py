"""Flagship dense model family: Llama-style decoder-only transformer.

The reference is a kernel library, not a model zoo — its "models" are the
benchmark shape tables (LLaMA-7B/8B/70B/405B, Mistral-7B, Qwen2-72B,
reference python/triton_dist/test/nvidia/test_ag_gemm_intra_node.py:153-160)
plus module-level layers (SpGQAFlashDecodeAttention, EPAll2AllLayer). This
framework goes one step further and wires those layers into a full
functional model so the overlap kernels are exercised in situ.

Design is TPU-first and functional:
- params are a pytree of stacked per-layer arrays (leading ``L`` dim) so the
  layer loop is a single-trace ``lax.scan`` — one compile of one block.
- the standard forward is pure jnp/einsum: under jit with GSPMD sharding
  annotations XLA inserts the TP collectives itself (the baseline the
  overlap kernels must beat).
- ``forward_tp_overlap`` runs the same math through the hand-overlapped
  Pallas AG-GEMM / GEMM-RS kernels (Megatron sequence-parallel residual
  layout: activations sequence-sharded between blocks), the analog of the
  reference's tutorial-07/08 TP forward.
"""

from __future__ import annotations

import collections.abc
import dataclasses
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.shmem.context import ShmemContext


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 11008
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def paged(self) -> "PagedFamily":
        """What the paged programs and the serving engine ask of this
        config's family (see ``PagedFamily``)."""
        return GQA_DENSE

    # -- benchmark shape presets (cf. test_ag_gemm_intra_node.py:153-160) --
    @classmethod
    def llama_7b(cls):
        return cls()

    @classmethod
    def llama3_8b(cls):
        return cls(vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
                   n_kv_heads=8, d_ff=14336, rope_theta=5e5)

    @classmethod
    def llama3_70b(cls):
        return cls(vocab_size=128256, d_model=8192, n_layers=80, n_heads=64,
                   n_kv_heads=8, d_ff=28672, rope_theta=5e5)

    @classmethod
    def llama3_405b(cls):
        return cls(vocab_size=128256, d_model=16384, n_layers=126,
                   n_heads=128, n_kv_heads=8, d_ff=53248, rope_theta=5e5)

    @classmethod
    def mistral_7b(cls):
        return cls(vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
                   n_kv_heads=8, d_ff=14336)

    @classmethod
    def qwen2_72b(cls):
        return cls(vocab_size=152064, d_model=8192, n_layers=80, n_heads=64,
                   n_kv_heads=8, d_ff=29568)

    @classmethod
    def tiny(cls, n_layers: int = 2):
        """Test/dryrun config: every sharded dim stays tile-friendly."""
        return cls(vocab_size=512, d_model=128, n_layers=n_layers, n_heads=4,
                   n_kv_heads=2, d_ff=256, max_seq_len=128)


def init_params(key: jax.Array, cfg: LlamaConfig) -> dict:
    """Stacked-per-layer param pytree. Truncated-normal-ish init (scaled
    normal) in ``cfg.dtype`` (bf16 keeps the MXU fed); norm gains in f32."""
    L, D, F = cfg.n_layers, cfg.d_model, cfg.d_ff
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = jax.random.split(key, 9)
    s = 0.02

    def norm(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(cfg.dtype)

    return {
        "embed": norm(keys[0], cfg.vocab_size, D),
        "blocks": {
            "attn_norm": jnp.ones((L, D), jnp.float32),
            "wq": norm(keys[1], L, D, Hq * Dh),
            "wk": norm(keys[2], L, D, Hkv * Dh),
            "wv": norm(keys[3], L, D, Hkv * Dh),
            "wo": norm(keys[4], L, Hq * Dh, D) / math.sqrt(2 * L),
            "mlp_norm": jnp.ones((L, D), jnp.float32),
            "w_gate": norm(keys[5], L, D, F),
            "w_up": norm(keys[6], L, D, F),
            "w_down": norm(keys[7], L, F, D) / math.sqrt(2 * L),
        },
        "final_norm": jnp.ones((D,), jnp.float32),
        "lm_head": norm(keys[8], D, cfg.vocab_size),
    }


def param_specs(cfg: LlamaConfig, tp: str | None = "tp",
                pp: str | None = None) -> dict:
    """GSPMD PartitionSpecs matching ``init_params``'s tree: Megatron TP
    layout (qkv/gate/up column-sharded, o/down row-sharded, embedding
    vocab-sharded), with the stacked layer dim optionally pipeline-sharded."""
    return {
        "embed": P(tp, None),
        "blocks": {
            "attn_norm": P(pp, None),
            "wq": P(pp, None, tp),
            "wk": P(pp, None, tp),
            "wv": P(pp, None, tp),
            "wo": P(pp, tp, None),
            "mlp_norm": P(pp, None),
            "w_gate": P(pp, None, tp),
            "w_up": P(pp, None, tp),
            "w_down": P(pp, tp, None),
        },
        "final_norm": P(None),
        "lm_head": P(None, tp),
    }


class LayerParams(collections.abc.Mapping):
    """Layer ``layer`` of a stacked-per-layer ``blocks`` tree, for the
    Python-unrolled layer loops. ``p[name]`` is ``blocks[name][layer]``,
    sliced when asked for. A consumer that can index the stacked array in
    place takes ``p.blocks[name]`` and ``p.layer`` instead: XLA cannot
    fuse a slice into a Pallas operand, so ``p["we_gate"]`` handed to the
    grouped-GEMM kernels costs a layer-sized HBM copy per layer per call
    (at Mixtral widths: the expert tables held twice)."""

    def __init__(self, blocks: dict, layer: int):
        self.blocks, self.layer = blocks, layer

    def __getitem__(self, name):
        return self.blocks[name][self.layer]

    def __iter__(self):
        return iter(self.blocks)

    def __len__(self):
        return len(self.blocks)


# ---------------------------------------------------------------------------
# math building blocks
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return ((x32 * rms) * w).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x [..., S, H, Dh]; positions [..., S]. Half-split RoPE."""
    Dh = x.shape[-1]
    half = Dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(angles)[..., None, :]                        # [..., S, 1, half]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def _attention(q, k, v, sm_scale: float) -> jax.Array:
    """Causal GQA attention. q [B,S,Hq,Dh]; k,v [B,S,Hkv,Dh]."""
    B, S, Hq, Dh = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    q = q.reshape(B, S, Hkv, G, Dh)
    scores = jnp.einsum("bshgd,bthd->bhgst", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    mask = jnp.tril(jnp.ones((S, S), jnp.bool_))[None, None, None]
    scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, S, Hq, Dh).astype(q.dtype)


def block_apply(cfg: LlamaConfig, x: jax.Array, p: dict,
                positions: jax.Array, act_spec: P | None = None,
                attn_fn=None) -> jax.Array:
    """One transformer block. x [B,S,D]. ``act_spec`` re-pins the residual
    stream sharding after each sublayer (GSPMD sequence/data parallel).
    ``attn_fn(q, k, v, sm_scale)`` replaces the dense attention (e.g. the
    context-parallel ring kernel, parallel.train cp plan)."""
    B, S, D = x.shape
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    def pin(h):
        if act_spec is not None:
            h = lax.with_sharding_constraint(h, act_spec)
        return h

    h = rmsnorm(x, p["attn_norm"], cfg.norm_eps)
    q = (h @ p["wq"]).reshape(B, S, Hq, Dh)
    k = (h @ p["wk"]).reshape(B, S, Hkv, Dh)
    v = (h @ p["wv"]).reshape(B, S, Hkv, Dh)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    attn = (attn_fn or _attention)(q, k, v, 1.0 / math.sqrt(Dh))
    x = pin(x + attn.reshape(B, S, Hq * Dh) @ p["wo"])

    h = rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
    ff = jax.nn.silu((h @ p["w_gate"]).astype(jnp.float32)).astype(h.dtype) \
        * (h @ p["w_up"])
    x = pin(x + ff @ p["w_down"])
    return x


def forward(params: dict, tokens: jax.Array, cfg: LlamaConfig,
            act_spec: P | None = None, remat: bool = False,
            attn_fn=None) -> jax.Array:
    """Full-sequence forward → logits [B,S,V]. Pure jnp: under jit + sharded
    params, XLA inserts TP collectives (the compiler baseline the overlap
    kernels race against, cf. tutorial 07's torch baseline). ``attn_fn``
    swaps in a distributed attention kernel (ring attention for cp)."""
    B, S = tokens.shape
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = jnp.arange(S)[None, :].repeat(B, 0)

    def body(x, p):
        return block_apply(cfg, x, p, positions, act_spec, attn_fn), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = lax.scan(body, x, params["blocks"])
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return (x @ params["lm_head"]).astype(jnp.float32)


def mlp_tp_overlap(ctx, x2d: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                   w_down: jax.Array, axis: str | None = None,
                   gemm_cfg=None) -> jax.Array:
    """Llama MLP over the differentiable overlap kernels, for the Megatron
    sequence-parallel residual layout: x2d [T, D] sharded P(axis) on rows →
    [T, D] sharded P(axis). Gate and up weights are fused per-shard into
    one [D, 2F] operand so the sequence shard crosses the wire ONCE
    (a single AG-GEMM instead of two); the down projection is the GEMM-RS
    adjoint. Fully differentiable (ops.autodiff), so this is a *training*
    MLP with hand-overlapped comms on both passes — beyond the reference's
    inference-only scope."""
    from triton_dist_tpu.ops.autodiff import ag_gemm_diff, gemm_rs_diff
    from triton_dist_tpu.ops.gemm import GemmConfig

    axis = axis or ctx.axis_names[0]
    n = ctx.axis_size(axis)
    D, F = w_gate.shape
    assert F % n == 0, f"FFN width {F} not divisible by TP size {n}"
    T_local = x2d.shape[0] // n
    if gemm_cfg is not None:
        cfg_ag = cfg_rs = gemm_cfg
    else:  # largest power-of-two tiles ≤128 that divide each stage
        cfg_ag = GemmConfig(math.gcd(128, T_local),
                            math.gcd(128, 2 * (F // n)))
        cfg_rs = GemmConfig(math.gcd(128, T_local), math.gcd(128, D))
    # per-shard [gate_i ‖ up_i] interleave: all reshape/concat stay inside
    # shards (no comms), and the fused output splits the same way
    wf = jnp.concatenate([w_gate.reshape(D, n, F // n),
                          w_up.reshape(D, n, F // n)], axis=2)
    wf = wf.reshape(D, 2 * F)
    h2 = ag_gemm_diff(ctx, axis, cfg_ag, x2d, wf)          # [T, 2F] P(None, ax)
    h2 = h2.reshape(-1, n, 2 * (F // n))
    gate, up = h2[..., :F // n], h2[..., F // n:]
    ff = (jax.nn.silu(gate.astype(jnp.float32)).astype(x2d.dtype)
          * up).reshape(-1, F)
    return gemm_rs_diff(ctx, axis, cfg_rs, ff, w_down)     # [T, D] P(ax)


# ---------------------------------------------------------------------------
# the plain contiguous reference (KV cache + flash-decode kernel):
# ``init_kv_cache`` / ``prefill`` / ``decode_step`` / ``generate``. No engine
# runs them; they are what the tests hold the paged programs below to.
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: LlamaConfig, batch: int, max_seq: int) -> dict:
    """The reference's contiguous cache, head-major [L, B, Hkv, S, D] — KV
    blocks are tiling-aligned DMA slices for the decode kernel
    (ops.flash_decode)."""
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
    shape = (cfg.n_layers, batch, Hkv, max_seq, Dh)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def prefill(params: dict, tokens: jax.Array, cfg: LlamaConfig,
            cache: dict) -> tuple[jax.Array, dict]:
    """Full-sequence forward that also writes K/V into ``cache[:, :, :S]``.
    Returns (last-position logits [B, V], cache). The reference that
    ``prefill_chunk_paged`` (what the engines admit through) is tested
    against: same rows, position for position, in a contiguous cache."""
    B, S = tokens.shape
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = jnp.arange(S)[None, :].repeat(B, 0)

    def body(x, layer):
        p, ck, cv = layer
        h = rmsnorm(x, p["attn_norm"], cfg.norm_eps)
        q = rope((h @ p["wq"]).reshape(B, S, Hq, Dh), positions,
                 cfg.rope_theta)
        k = rope((h @ p["wk"]).reshape(B, S, Hkv, Dh), positions,
                 cfg.rope_theta)
        v = (h @ p["wv"]).reshape(B, S, Hkv, Dh)
        ck = lax.dynamic_update_slice(
            ck, k.transpose(0, 2, 1, 3), (0, 0, 0, 0))
        cv = lax.dynamic_update_slice(
            cv, v.transpose(0, 2, 1, 3), (0, 0, 0, 0))
        attn = _attention(q, k, v, 1.0 / math.sqrt(Dh))
        x = x + attn.reshape(B, S, Hq * Dh) @ p["wo"]
        h = rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
        ff = jax.nn.silu((h @ p["w_gate"]).astype(jnp.float32)
                         ).astype(h.dtype) * (h @ p["w_up"])
        x = x + ff @ p["w_down"]
        return x, (ck, cv)

    x, (ks, vs) = lax.scan(body, x, (params["blocks"], cache["k"],
                                     cache["v"]))
    x = rmsnorm(x[:, -1], params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits, {"k": ks, "v": vs}


def decode_step(params: dict, token: jax.Array, pos: jax.Array,
                cfg: LlamaConfig, cache: dict,
                ffn=None) -> tuple[jax.Array, dict]:
    """One-token decode of the contiguous reference via the flash-decode
    kernel. ``token`` [B] int32, ``pos`` scalar int32 (cache slots filled
    so far). Returns (logits [B, V], cache).
    Attention = ops.flash_decode.gqa_decode_partial
    over the cache (the single-rank half of SpGQAFlashDecodeAttention).

    ``ffn(h, p) -> [B, D]`` overrides the per-layer FFN block (same hook as
    ``decode_step_sp`` — lets single-device references for MoE variants
    reuse this plumbing). With a custom ``ffn`` the layer loop unrolls in
    Python instead of ``lax.scan`` (the callback may close over shard_map'd
    kernels that don't compose with scan on every backend)."""
    from triton_dist_tpu.ops.flash_decode import gqa_decode_partial

    B = token.shape[0]
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = params["embed"][token].astype(cfg.dtype)          # [B, D]
    positions = jnp.full((B, 1), pos, jnp.int32)

    def body(x, layer):
        p, ck, cv = layer
        h = rmsnorm(x, p["attn_norm"], cfg.norm_eps)
        q = rope((h @ p["wq"]).reshape(B, 1, Hq, Dh), positions,
                 cfg.rope_theta)[:, 0]                     # [B, Hq, Dh]
        k = rope((h @ p["wk"]).reshape(B, 1, Hkv, Dh), positions,
                 cfg.rope_theta)
        v = (h @ p["wv"]).reshape(B, 1, Hkv, Dh)
        ck = lax.dynamic_update_slice(ck, k.transpose(0, 2, 1, 3),
                                      (0, 0, pos, 0))
        cv = lax.dynamic_update_slice(cv, v.transpose(0, 2, 1, 3),
                                      (0, 0, pos, 0))
        kv_len = jnp.full((B,), pos + 1, jnp.int32)
        attn, _lse = gqa_decode_partial(q, ck, cv, kv_len)
        x = x + attn.reshape(B, Hq * Dh) @ p["wo"]
        h = rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
        if ffn is None:
            ff = (jax.nn.silu((h @ p["w_gate"]).astype(jnp.float32)
                              ).astype(h.dtype) * (h @ p["w_up"])
                  ) @ p["w_down"]
        else:
            ff = ffn(h, p)
        x = x + ff.astype(x.dtype)
        return x, (ck, cv)

    if ffn is None:
        x, (ks, vs) = lax.scan(body, x, (params["blocks"], cache["k"],
                                         cache["v"]))
    else:
        ks_l, vs_l = [], []
        for i in range(cfg.n_layers):
            p = LayerParams(params["blocks"], i)
            x, (ck, cv) = body(x, (p, cache["k"][i], cache["v"][i]))
            ks_l.append(ck)
            vs_l.append(cv)
        ks, vs = jnp.stack(ks_l), jnp.stack(vs_l)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits, {"k": ks, "v": vs}


def init_page_pool(cfg: LlamaConfig, num_pages: int, page_size: int) -> dict:
    """Paged KV pool: stacked page-major arrays [L, P, Hkv, page_size, D]
    — each page of each layer is the tiling-aligned DMA slice
    ``gqa_decode_paged`` streams by (layer, block-table index). The pool
    has ONE layout (row-major) and lives in ONE place: the decode and
    chunk programs carry it whole through their layer loop, write new
    rows into it in place (``paged_kv_write(layer=)``) and read pages out
    of it in place (``gqa_decode_paged(layer=)``); with the pool donated
    nothing pool-shaped is sliced, stacked, copied or re-laid out (see
    ``_paged_layers``). The serving runtime (``triton_dist_tpu.serving``)
    owns page accounting; this is just the device memory."""
    Hkv, Dh = cfg.n_kv_heads, cfg.head_dim
    assert page_size % 8 == 0, f"page_size {page_size} must be 8-aligned"
    shape = (cfg.n_layers, num_pages, Hkv, page_size, Dh)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


class Walks(NamedTuple):
    """``PagedFamily.walks(cfg)``: ``n`` walks of the whole stack a
    token-step. ``start(cfg, params, x) -> state`` (any pytree) before the
    first; ``end(cfg, params, x, t, state, live) -> (x, state, counts)`` at
    the end of walk ``t`` (a traced int32; ``live`` [R] bool the rows that
    count; ``counts`` as an attention's); ``rows(state) -> [R, D]`` the rows
    the head takes once the last walk has ended."""
    n: int
    start: Any
    end: Any
    rows: Any


@dataclasses.dataclass(frozen=True)
class PagedFamily:
    """A model family, as the paged programs and ``serving.engine`` see it:
    the kind of cache, of attention, of FFN, of norm and of block are DATA
    here, and the config's type picks the record (``cfg.paged``). The layer
    loop (``_paged_layers``), the decode, multistep and chunk programs, the
    scheduler and the page ledger are one for every family.

    - ``init_pool(cfg, num_pages, page_size)``: the page pool, a pytree of
      arrays ``[layer, page, ...]`` (the engine's page copy, export and
      import map over its leaves; the ledger never sees its shape).
    - ``segments(cfg, params)``: the runs of layers that share a body, in
      order: ``[(blocks, first_layer, n_layers, ffn[, period])]`` with
      ``blocks`` the run's stacked per-layer params and ``ffn(cfg, p, h,
      layer, active) -> (out [R, D], counts)``; ``counts`` maps names of
      ``counters`` to int32 scalars (``{}``: none). A run may bring its OWN
      ``period`` (a leading dense run of one kind of layer before a periodic
      one); without one it has the family's.
    - ``attention(cfg, p, h, layer, pool, block_table, pos, kv_len, active,
      shared_table, lin, attn_io) -> (out [R, D], pool[, counts])``:
      projections, the pool write, the paged walk and the output projection
      of one layer.
    - ``period(cfg)``: for a family whose layers are not all of one kind, the
      attentions of ONE PERIOD of its layer pattern, in order (each as
      ``attention`` above; say three window layers and a full one). The
      period is what the layer loop scans: its body is ``len(period)``
      layers, so 32 layers of period 4 are 8 trips of one compiled body.
      None: every layer runs ``attention`` (a period of one). Where the
      kinds of a period differ in SHAPE (other KV heads, a sink), an entry
      is ``(kind, attention)`` and the run's ``blocks`` is ``{kind: stack}``,
      each kind's layers stacked on their own: the body takes a period's
      slice of every stack, and layer j of the period is the next of its
      kind's.
    - ``norm(x, w, eps)``: the layers' and the head's norm (``rmsnorm``).
    - ``parallel``: the block form. False: ``x + attn(norm(x))`` then ``x +
      ffn(norm(x))``, two norms a layer. True: ``x + attn(u) + ffn(u)`` with
      ``u = norm(x)``, ONE norm (``attn_norm``) a layer.
    - ``head(cfg, params, x, lin) -> logits float32``: the output head on
      normed rows; None: ``x @ params["lm_head"]``. A tied head contracts
      with the embedding table here.
    - ``counters``: names of the per-dispatch counters the FFNs and
      attentions return; the multistep program sums them over layers and
      inner steps and appends one row each to its token slab.
    - ``decode_speculate``: the speculative decode program, where the
      family has one. The other two programs the engine jits,
      ``decode_multistep_paged`` and ``prefill_chunk_paged``, are the same
      functions for every family (they take the family from ``cfg.paged``)
      and are not part of the record.
    - ``lacks``: the engine options this family does not take, of
      ``speculate``, ``prefix_cache``, ``hooks`` (``ffn`` / ``attn_io`` /
      ``linear``); the engine refuses them by name.
    - ``slot_ring(cfg, page_size)``: pages of the RING every engine slot owns
      in a second kind of pool leaf (layers whose cache is bounded, such as a
      sliding window's), beside the ledger's pages; None: the family has
      none. The ring is the slot's, not the ledger's: slot s owns ring pages
      ``1 + s * ring ..`` (page 0 is scratch, as the ledger's), the engine
      appends that first page to the slot's block-table row (the programs'
      tables are then ``pages_per_seq + 1`` wide), and ``bind(cfg, num_slots,
      prefill_chunk)`` returns the config sized for an engine's slots and
      chunk (the ring spans the window and a chunk; ``init_pool`` makes
      ``num_slots`` rings).
    - ``slot_state(cfg)``: bytes of STATE THAT IS NOT PAGES every engine slot
      owns over all layers (a recurrent layer's fixed block a sequence,
      rewritten every token), in pool leaves of ``1 + num_slots`` rows a
      layer; None: the family has none. Slot s owns row ``1 + s`` (row 0 is
      scratch), which rides one more column of its block-table row exactly
      as a ring's first page does; ``bind`` sizes the leaves. ``attention``
      is then the layer's whole reader of the normed rows (say a mixer
      beside an attention): it takes the rows' slot off that column, starts
      a chunk whose first row sits at position 0 from a zero state, leaves
      every row that is not live untouched to the bit, and returns the
      rewritten leaves with the pool. A state cannot be rewound, shared by
      reference or copied by page: the engine restarts a preempted request
      and refuses page copy, export and import by name.
    - ``embed(cfg, params, tokens) -> x``: the rows the layer loop starts
      from; None: ``params["embed"][tokens]``.
    - ``chunk_walks(cfg) -> ((layers, rows_per_block, window), ...)``: the
      ``gqa_prefill_paged`` calls of ONE chunk program, a kind of attention
      layer each: how many layers walk, in row blocks of what, under which
      window (None: the whole context). The engine counts a chunk's walked
      and edge pages from it at the chunk's commit, on the host
      (``ops.flash_decode.chunk_walk_counts``: counters ``chunk_walk_pages``
      / ``chunk_walk_edge_pages``); None: the chunk walks no K/V pages.
    - ``walks(cfg) -> Walks``: for a family whose rows WALK THE WHOLE STACK
      of layers several times over the same weights, each walk with cache
      planes of its own: how often, and what happens at a walk's end (see
      ``Walks``). Walk t of layer l is row l of the parameter stacks and
      plane ``t * (the segments' layers) + l`` of the pool (``attention``
      is handed the PLANE as its ``layer``, ``ffn`` the layer); the walks
      are a ``fori_loop`` around the scan over layers, so the loop stays
      one compiled body. The rows ``_paged_layers`` returns are then
      ``Walks.rows`` of the walks' state, normed already: ``logits`` heads
      them as they are. None: one walk and nothing at its end, layer l is
      plane l."""
    name: str
    init_pool: Any
    segments: Any
    attention: Any = None
    period: Any = None
    norm: Any = None
    parallel: bool = False
    head: Any = None
    decode_speculate: Any = None
    counters: tuple = ()
    lacks: tuple = ()
    slot_ring: Any = None
    bind: Any = None
    slot_state: Any = None
    embed: Any = None
    chunk_walks: Any = None
    walks: Any = None

    # ``benchmark/tools/fit_paged.py`` reads the two shared programs off the
    # record; they are the module's functions, whatever the family.
    decode_multistep = property(lambda self: decode_multistep_paged)
    prefill_chunk = property(lambda self: prefill_chunk_paged)

    def logits(self, cfg, params, x, lin) -> jax.Array:
        """The head on ``x`` [R, D]: final norm (a family with ``walks``
        norms at every walk's end instead), then the family's head."""
        if self.walks is None:
            x = (self.norm or rmsnorm)(x, params["final_norm"], cfg.norm_eps)
        if self.head is not None:
            return self.head(cfg, params, x, lin)
        return lin(x, params["lm_head"], "lm_head").astype(jnp.float32)

    def embedded(self, cfg, params, tokens) -> jax.Array:
        """The rows ``tokens`` enter the layer loop as."""
        if self.embed is not None:
            return self.embed(cfg, params, tokens)
        return params["embed"][tokens].astype(cfg.dtype)


def require_config(cfg, kind: type, who: str) -> None:
    """``who`` is written against one family's pool and layer; any other
    config is refused by name rather than mis-run."""
    if not isinstance(cfg, kind):
        raise NotImplementedError(
            f"{who} serves {kind.__name__} models only; got "
            f"{type(cfg).__name__}")


def live_rows(kv_len: jax.Array, active: jax.Array | None) -> jax.Array:
    """[R] bool: the rows of a dispatch that count (not parked, not padding,
    not frozen mid-scan)."""
    return kv_len > 0 if active is None else jnp.logical_and(active,
                                                             kv_len > 0)


def gated_ffn(h: jax.Array, w_gate, w_up, w_down) -> jax.Array:
    """silu(h Wg) * (h Wu), then Wd: a dense FFN, or a shared expert."""
    return (jax.nn.silu((h @ w_gate).astype(jnp.float32)).astype(h.dtype)
            * (h @ w_up)) @ w_down


def swiglu_ffn(cfg, p, h: jax.Array, layer=None, active=None):
    """The dense FFN of a layer, as a ``PagedFamily`` segment's ``ffn``."""
    del cfg, layer, active
    return gated_ffn(h, p["w_gate"], p["w_up"], p["w_down"]), {}


def _gqa_segments(cfg: LlamaConfig, params: dict) -> list:
    return [(params["blocks"], 0, cfg.n_layers, swiglu_ffn)]


def _gqa_attention(cfg: LlamaConfig, p, h, layer, pool, block_table, pos,
                   kv_len, active, shared_table, lin, attn_io):
    """GQA over the K/V pool. Rows that each have their own block table
    (decode slots, speculative verify rows) are rows x heads of
    ``gqa_decode_paged``; rows that share one (``shared_table``: a prefill
    chunk) walk it together in ``gqa_prefill_paged``. The ``attn_io`` hook
    keeps the decode rows either way."""
    from triton_dist_tpu.ops.flash_decode import (gqa_decode_paged,
                                                  gqa_prefill_paged,
                                                  paged_kv_write)
    R = h.shape[0]
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    positions = pos[:, None].astype(jnp.int32)             # [R, 1]
    kp, vp = pool["k"], pool["v"]
    q = rope(lin(h, p["wq"], "wq").reshape(R, 1, Hq, Dh), positions,
             cfg.rope_theta)[:, 0]                         # [R, Hq, Dh]
    k = rope(lin(h, p["wk"], "wk").reshape(R, 1, Hkv, Dh), positions,
             cfg.rope_theta)[:, 0]                         # [R, Hkv, Dh]
    v = lin(h, p["wv"], "wv").reshape(R, 1, Hkv, Dh)[:, 0]
    if attn_io is None:
        kp, vp = paged_kv_write(kp, vp, k, v, block_table, pos,
                                active=active, layer=layer,
                                shared_table=shared_table)
        if shared_table:
            attn = gqa_prefill_paged(q, kp, vp, block_table[0], kv_len,
                                     layer=layer)
        else:
            attn, _lse = gqa_decode_paged(q, kp, vp, block_table, kv_len,
                                          layer=layer)
    else:
        attn, kl, vl = attn_io(q, k, v, kp[layer], vp[layer], block_table,
                               pos, kv_len, active)
        kp, vp = kp.at[layer].set(kl), vp.at[layer].set(vl)
    return lin(attn.reshape(R, Hq * Dh), p["wo"], "wo"), {"k": kp, "v": vp}


def _paged_layers(params: dict, x: jax.Array, pos: jax.Array,
                  kv_len: jax.Array, active: jax.Array | None,
                  cfg, pages: dict, block_table: jax.Array,
                  ffn, attn_io, linear, shared_table: bool = False
                  ) -> tuple[jax.Array, dict, tuple]:
    """The layer loop of both paged programs, for every family: ``x``
    [R, D] is R rows of decode (R batch slots, or a chunk's C tokens), row
    r at position ``pos[r]`` attending ``kv_len[r]`` keys through
    ``block_table[r]`` (``shared_table``: all rows have the same one).
    What a layer IS comes from ``cfg.paged`` (``PagedFamily``): its norm,
    its block form (sequential or parallel), per layer of a period its
    attention over its kind of pool, and per run of layers its FFN.
    Returns (x after the last block, updated pages, the family's counters
    summed over the layers, in ``counters``' order).

    The body that is scanned is ONE PERIOD of the family's layer pattern
    (``PagedFamily.period``, or the run's own; one layer where every layer
    is of one kind): its params are the run's stacked ``[n, ...]`` arrays
    seen as ``[n / period, period, ...]`` (a free view), or, where the kinds
    differ in shape, each kind's stack seen as ``[n / period, that kind's
    layers a period, ...]``; its layer index is the period's first. Where a
    run is SEVERAL periods of several layers the body takes the period's
    first index alone and indexes each layer in the stacks where they lie
    (``body_in_place``).

    The pool is the loop's CARRY, never its per-layer input or output:
    ``lax.scan`` cannot alias an ``xs`` to a ``ys``, so scanning over the
    pool copies all of it every call, and a per-layer pool sliced out of
    the stack is copied again for the kernel. Carried whole, written in
    place (``paged_kv_write(layer=)``) and read in place
    (``gqa_decode_paged(layer=)``), it is never copied at all.

    A family whose rows walk the stack SEVERAL TIMES (``PagedFamily.walks``)
    gets one ``fori_loop`` over the walks around those scans: the scanned
    body is still compiled once, walk t's attention is handed plane
    ``t * layers + i`` of the pool where its parameters are row i, the
    walk's end (the family's: a norm, a gate) runs once a trip, and ``x`` is
    what the walks' state says the head takes. Without ``walks`` nothing of
    this is traced: every other family's program is what it was.

    Any hook (``ffn`` / ``attn_io`` / ``linear``, see
    ``decode_step_paged``) unrolls the loop in Python over the same body.
    ``attn_io`` keeps its per-layer contract: it is handed ``K[i]``,
    ``V[i]`` and its result is put back at the static index."""
    fam = cfg.paged
    norm = fam.norm or rmsnorm
    family_period = fam.period(cfg) if fam.period else (fam.attention,)
    lin = linear or (lambda h, w, name: h @ w)
    hooked = not (ffn is None and attn_io is None and linear is None)

    def add(counts, new):
        return {**counts, **{k: counts[k] + v for k, v in new.items()}}

    def layer(carry, p, i, attention, seg_ffn, off=None):
        # ``off``: the walk's first plane (``PagedFamily.walks``); layer i's
        # cache is then plane off + i, its parameters row i all the same
        x, pool, counts = carry
        h = norm(x, p["attn_norm"], cfg.norm_eps)
        attn, pool, *new = attention(cfg, p, h, i if off is None else off + i,
                                     pool, block_table, pos, kv_len, active,
                                     shared_table, lin, attn_io)
        if new:
            counts = add(counts, new[0])
        if not fam.parallel:
            x = x + attn
            h = norm(x, p["mlp_norm"], cfg.norm_eps)
        if ffn is None:
            ff, new = seg_ffn(cfg, p, h, i, active)
            if new:
                counts = add(counts, new)
        else:
            ff = ffn(h, p)
        if fam.parallel:
            x = x + attn
        x = x + ff.astype(x.dtype)
        return x, pool, counts

    segments = fam.segments(cfg, params)

    def walk(carry, off):
        """Every run of layers once, in order."""
        for blocks, first, n, seg_ffn, *own in segments:
            period = own[0] if own else family_period
            P = len(period)
            assert n % P == 0, (
                f"{n} layers are no whole number of periods of {P}")
            # (attention, its stack, its rank among the period's layers of
            # that stack) of every layer of the period, and each stack's
            # layers a period: ONE stack (``None``) of P where the layers
            # share a shape
            plan, each = [], {}
            for entry in period:
                kind, attention = entry if isinstance(entry, tuple) \
                    else (None, entry)
                plan.append((attention, kind, each.get(kind, 0)))
                each[kind] = each.get(kind, 0) + 1
            stacks = {None: blocks} if None in each else blocks

            def body(carry, xs, seg_ffn=seg_ffn, plan=plan, whole=P == 1):
                p, i = xs   # a period's params {stack: [c, ...]}, its first
                for j, (attention, kind, rank) in enumerate(plan):
                    carry = layer(
                        carry,
                        p[kind] if whole else LayerParams(p[kind], rank),
                        i + j if j else i, attention, seg_ffn, off)
                return carry, None

            def body_in_place(carry, i, seg_ffn=seg_ffn, plan=plan,
                              stacks=stacks, each=each, first=first, P=P):
                # SEVERAL periods of several layers: each layer's params are
                # indexed where the stacks lie. As the scan's input a
                # period's slice of every stack was copied whole every trip
                # (100 MB of ``w_qkv`` [3, 2048, 8192] a period and
                # token-step on the v5e); one layer's slice is fetched as a
                # period-of-one's is.
                for j, (attention, kind, rank) in enumerate(plan):
                    at = (i - first) // P * each[kind] + rank
                    carry = layer(carry, LayerParams(stacks[kind], at),
                                  i + j if j else i, attention, seg_ffn, off)
                return carry, None

            if hooked:
                for j in range(n):
                    attention, kind, rank = plan[j % P]
                    carry = layer(carry, LayerParams(
                        stacks[kind], (j // P) * each[kind] + rank),
                        first + j, attention, seg_ffn, off)
            else:
                firsts = jnp.arange(first, first + n, dtype=jnp.int32)
                if P > 1 and n > P:
                    carry, _ = lax.scan(body_in_place, carry, firsts[::P])
                    continue
                if P > 1:
                    stacks = {kind: jax.tree.map(
                        lambda a, c=c: a.reshape((n // P, c) + a.shape[1:]),
                        stacks[kind]) for kind, c in each.items()}
                    firsts = firsts[::P]
                carry, _ = lax.scan(body, carry, (stacks, firsts))
        return carry

    carry = (x, pages, {name: jnp.int32(0) for name in fam.counters})
    if fam.walks is None:
        carry = walk(carry, None)
    else:
        # the stack walked ``w.n`` times: ONE loop around the same scans,
        # the walk's planes ``stride`` apart
        w = fam.walks(cfg)
        stride = sum(seg[2] for seg in segments)
        live = live_rows(kv_len, active)

        def whole_walk(t, c):
            x, pool, counts = walk(c[:3], t * stride)
            x, state, new = w.end(cfg, params, x, t, c[3], live)
            return x, pool, add(counts, new), state

        with jax.named_scope("looped_layers"):
            c = lax.fori_loop(0, w.n, whole_walk,
                              (*carry, w.start(cfg, params, x)))
        carry = (w.rows(c[3]), *c[1:3])
    x, pages, counts = carry
    return x, pages, tuple(counts[name] for name in fam.counters)


def decode_step_paged(params: dict, token: jax.Array, pos: jax.Array,
                      cfg, pages: dict,
                      block_table: jax.Array, ffn=None,
                      active: jax.Array | None = None,
                      sample: bool = False, attn_io=None,
                      linear=None, counters: bool = False
                      ) -> tuple[jax.Array, dict]:
    """One-token decode over the paged KV pool — the continuous-batching
    twin of ``decode_step``. Differences that make it a serving hot loop:

    - ``pos`` is PER-SLOT [B] int32 (every slot sits at its own depth —
      arrivals and finishes never force a shared position), vs
      ``decode_step``'s single scalar.
    - the cache is the page pool from ``init_page_pool`` plus a
      ``block_table`` [B, pages_per_seq] int32; the new (k, v) is
      scattered into page ``bt[b, pos_b // page_size]`` row
      ``pos_b % page_size`` and attention is ``gqa_decode_paged``.
    - inactive slots are driven by pointing their block-table row at a
      reserved scratch page (the serving engine reserves page 0): their
      writes land there, their reads mask out, and the batch shape never
      changes — one compiled step per token regardless of arrivals.

    Returns (logits [B, V] f32, updated pages). ``ffn(h, p) -> [B, D]``
    overrides the per-layer FFN exactly as in ``decode_step`` (MoE
    serving plugs ``moe_mlp_ep_overlap`` here); with a custom ``ffn`` the
    layer loop unrolls in Python for the same backend reasons.

    ``active`` [B] bool (optional) parks frozen rows' KV writes on the
    scratch page (``ops.flash_decode.paged_kv_write``) — the device-side
    slot mask the scanned multi-token loop uses for rows done mid-scan.
    ``sample=True`` fuses greedy sampling: the first return value is the
    on-device argmax ``next_token`` [B] int32 instead of the [B, vocab]
    logits, so a serving host only ever downloads a token slab.

    ``attn_io(q, k, v, kp, vp, bt, pos, kv_len, active) -> (attn, kp, vp)``
    overrides the KV-write + paged-attention pair (the SP serving path
    plugs ``ops.flash_decode.sp_paged_attend_write`` here — the pool
    arrays then stay sharded on their page dim). ``linear(h, w, name)``
    overrides every dense projection (wq/wk/wv/wo/lm_head — the TP
    serving path plugs ``ops.allgather_gemm.tp_column_linear``). Either
    hook unrolls the layer loop like ``ffn`` does.

    ``cfg`` is any config with a ``paged`` family (``PagedFamily``): the
    pool's kind, the attention and the FFNs are the family's.
    ``counters=True`` appends a third result, the family's counters of
    this step (a tuple of int32 scalars, ``cfg.paged.counters`` names
    them; rows masked off by ``active`` are not counted)."""
    lin = linear or (lambda h, w, name: h @ w)
    x = cfg.paged.embedded(cfg, params, token)            # [B, D]
    kv_len = (pos + 1).astype(jnp.int32)
    x, pages, counts = _paged_layers(params, x, pos, kv_len, active, cfg,
                                     pages, block_table, ffn, attn_io,
                                     linear)
    logits = cfg.paged.logits(cfg, params, x, lin)
    out = jnp.argmax(logits, -1).astype(jnp.int32) if sample else logits
    return (out, pages, counts) if counters else (out, pages)


def prefill_chunk_paged(params: dict, tokens: jax.Array, start: jax.Array,
                        prompt_len: jax.Array, cfg,
                        pages: dict, block_table: jax.Array,
                        ffn=None, attn_io=None,
                        linear=None) -> tuple[jax.Array, dict]:
    """Prefill one fixed-size chunk of a prompt DIRECTLY into the page
    pool — the admission half of the serving hot loop (ISSUE 5 tentpole).

    ``tokens`` [C] int32 is chunk ``[start, start + C)`` of the prompt,
    zero-padded past ``prompt_len``; ``start`` and ``prompt_len`` are
    runtime scalars, so ONE compiled program (keyed only by the chunk
    size C) serves every prompt length and every chunk position.
    ``block_table`` [pages_per_seq] int32 is the sequence's block-
    table row (fill entries past the owned pages are never dereferenced).

    The chunk rides the PAGED machinery end to end: its C tokens are C
    rows of the layer loop decode uses (``_paged_layers``), rows that all
    share ONE block-table row:

    - KV lands straight in the pool via ``paged_kv_write`` (pos = the
      absolute token position) — no temporary contiguous cache. The rows
      are ONE sequence's run, so they land a PAGE at a time
      (``shared_table=True``: at most ``C / page_size + 1`` pages a pool
      and layer are read, merged and written back where a row scatter
      paid for each of ``C x Hkv`` rows: PERF.md section 6, PR 47);
      ``active`` masks the padded tail, whose rows write NOTHING (the
      ``attn_io`` hook's rows of decode still park theirs on the scratch
      page).
    - attention is the family's paged walk with per-row
      ``kv_len = position + 1``: each query attends ALL pages filled so
      far — the pages of every previous chunk plus this chunk's own
      causal prefix (written just above). The chunk-boundary attention
      state therefore never crosses the host: it IS the pages, re-read
      through the same online-softmax walk decode uses, instead of an
      (m, l, acc) carry threaded between chunk calls. Because the rows
      share their table, they share the walk too: the K/V family runs
      ``ops.flash_decode.gqa_prefill_paged`` (a page leaves HBM once a
      row block, not once a row, and meets the MXU as a block's rows x
      its query heads; as C rows of ``gqa_decode_paged`` the walk was
      most of a Mistral-7B chunk's device time: PERF.md section 6,
      PR 27), the latent family ``mla_decode_paged(rows_per_block=)``
      (the same in-kernel loop over live pages as its decode rows, a row
      block's rows x heads as one operand: PR 34).
      Only the ``attn_io`` hook still takes the chunk as C rows of
      decode. Padded rows run with ``kv_len = 0`` (the empty-shard
      convention — zeros out, masked writes) and their residual-stream
      garbage is never read.

    Returns ``(tok [()], pages)``: ``tok`` is the on-device greedy argmax
    of the logits at row ``prompt_len - 1 - start`` (the first generated
    token, fused like ``decode_step_paged(sample=True)`` — the host never
    downloads logits or argmaxes them). It is meaningful only for the
    chunk that contains the prompt's last token; earlier chunks compute
    the same (cheap, one-row) head on a garbage row and the engine
    ignores it — the price of keeping every chunk the same program.

    ``ffn(h, p) -> [C, D]`` overrides the per-layer FFN exactly as in
    ``decode_step_paged`` (the MoE serving hook); with a custom ``ffn``
    the layer loop unrolls in Python for the same backend reasons.
    ``attn_io``/``linear`` hook the KV-write+attention pair and the dense
    projections exactly as in ``decode_step_paged`` (the chunk's C rows
    play the batch-row role; ``active`` is the padded-tail mask).
    """
    lin = linear or (lambda h, w, name: h @ w)
    C = tokens.shape[0]
    idx = start.astype(jnp.int32) + jnp.arange(C, dtype=jnp.int32)   # [C]
    valid = idx < prompt_len                                         # [C]
    # padded rows reach no live page: position 0 keeps the block-table
    # lookup in range, active=False masks the write
    pos = jnp.where(valid, idx, 0).astype(jnp.int32)
    kv_len = jnp.where(valid, idx + 1, 0).astype(jnp.int32)
    bt = jnp.broadcast_to(block_table[None, :], (C, block_table.shape[0]))
    x = cfg.paged.embedded(cfg, params, tokens)                      # [C, D]
    x, pages, _ = _paged_layers(params, x, pos, kv_len, valid, cfg, pages,
                                bt, ffn, attn_io, linear, shared_table=True)
    # one-row head: the prompt's last token sits at chunk row
    # prompt_len - 1 - start when this is the final chunk (clamped into
    # range otherwise — the result is then garbage the engine discards)
    last = jnp.clip(prompt_len - 1 - start, 0, C - 1).astype(jnp.int32)
    h_last = lax.dynamic_slice_in_dim(x, last, 1)                    # [1, D]
    logits = cfg.paged.logits(cfg, params, h_last, lin)
    tok = jnp.argmax(logits[0], -1).astype(jnp.int32)
    return tok, pages


def decode_multistep_paged(params: dict, token: jax.Array, pos: jax.Array,
                           cfg, pages: dict,
                           block_table: jax.Array, limit: jax.Array,
                           horizon: int, eos_id: int | None = None,
                           ffn=None, attn_io=None, linear=None
                           ) -> tuple[jax.Array, jax.Array, jax.Array, dict]:
    """Device-resident multi-token decode: ``horizon`` fused sampled steps
    (``decode_step_paged(..., sample=True)``) chained under one trace, so
    ONE host dispatch advances every slot up to ``horizon`` tokens. The
    serving hot loop (``serving.engine``) jits this once per engine — the
    horizon and ``eos_id`` are static trace constants; all per-step
    dynamism rides in ``limit``.

    ``limit`` [B] int32 is the per-slot step budget for THIS dispatch:
    ``min(horizon, tokens remaining, page capacity headroom)``, 0 for
    parked slots. A row freezes once its inner step index reaches its
    limit OR it has emitted ``eos_id`` — its token/pos stop advancing and
    its KV writes are parked on the scratch page via the ``active`` mask.
    The limit clamp is how the horizon auto-clamps so no slot can outgrow
    its pre-ensured pages mid-scan; the EOS freeze is the device half of
    the done-mask (the host reconciles finishes from the slab). Frozen
    rows keep computing harmlessly — the fixed-shape batch never changes.

    Returns ``(toks [horizon, B] int32, token' [B], pos' [B], pages)``:
    ``toks[i, b]`` is the token sampled by row ``b``'s step ``i`` (valid
    while the row was live); ``token'``/``pos'`` are the post-scan slot
    states (advanced exactly as many steps as the row was live) the
    engine keeps device-resident for the next dispatch. ``horizon=1``
    is exactly one fused ``decode_step_paged`` — today's per-token
    semantics.

    A family with ``counters`` (``cfg.paged.counters``) gets one more row
    of ``toks`` for each, after the ``horizon`` token rows: row
    ``horizon + j`` holds counter j summed over the layers and the inner
    steps of this dispatch (live rows only), in every column. They ride
    the slab the host downloads anyway: no further transfer."""
    assert horizon >= 1
    limit = limit.astype(jnp.int32)
    stopped0 = jnp.zeros(token.shape, jnp.bool_)
    counts0 = tuple(jnp.int32(0) for _ in cfg.paged.counters)

    def one(carry, i):
        tok, pos_c, stopped, pages_c, counts = carry
        act = jnp.logical_and(i < limit, ~stopped)         # [B] bool
        nxt, pages_c, new = decode_step_paged(
            params, tok, pos_c, cfg, pages_c, block_table, ffn=ffn,
            active=act, sample=True, attn_io=attn_io, linear=linear,
            counters=True)
        counts = tuple(a + b for a, b in zip(counts, new))
        tok = jnp.where(act, nxt, tok)
        pos_c = jnp.where(act, pos_c + 1, pos_c)
        if eos_id is not None:
            stopped = jnp.logical_or(stopped,
                                     jnp.logical_and(act, nxt == eos_id))
        return (tok, pos_c, stopped, pages_c, counts), nxt

    if ffn is None and attn_io is None and linear is None and horizon > 1:
        (token, pos, _, pages, counts), toks = lax.scan(
            one, (token, pos, stopped0, pages, counts0),
            jnp.arange(horizon, dtype=jnp.int32))
    else:
        # custom ffn may close over shard_map'd kernels that don't compose
        # with scan on every backend — unroll (same reason as the layer
        # loop above); horizon=1 skips the scan machinery entirely
        toks_l = []
        carry = (token, pos, stopped0, pages, counts0)
        for i in range(horizon):
            carry, nxt = one(carry, jnp.int32(i))
            toks_l.append(nxt)
        token, pos, _, pages, counts = carry
        toks = jnp.stack(toks_l)
    if counts:
        toks = jnp.concatenate([toks, jnp.broadcast_to(
            jnp.stack(counts)[:, None], (len(counts), toks.shape[1]))])
    return toks, token, pos, pages


def decode_speculate_paged(params: dict, token: jax.Array, pos: jax.Array,
                           cfg, pages: dict,
                           block_table: jax.Array, limit: jax.Array,
                           horizon: int, hist: jax.Array,
                           hist_len: jax.Array, eos_id: int | None = None,
                           ffn=None, attn_io=None, linear=None
                           ) -> tuple[jax.Array, jax.Array, jax.Array,
                                      jax.Array, jax.Array, jax.Array, dict]:
    """Draft-verify speculative decode: ONE dispatch commits up to
    ``horizon`` tokens per slot, bit-identical to ``horizon`` sequential
    greedy steps (ISSUE 20 tentpole). The spec twin of
    ``decode_multistep_paged`` — same signature family, same per-slot
    ``limit`` clamp / EOS freeze / scratch-page parking — but where the
    multistep scan runs K *sequential* fused steps, this runs K
    *positions in parallel* as K batch rows and accepts a prefix:

    - **draft**: ``serving.speculate.ngram_draft`` proposes K-1 tokens
      per slot from ``hist`` [B, H] (the device-resident recent-token
      window, newest at column H-1) — no host sync, no draft model.
    - **verify**: one ``decode_step_paged`` call over ``B*K`` rows —
      row (b, i) consumes token i of (last_token ‖ drafts) at position
      ``pos_b + i``. Per layer, ``paged_kv_write`` scatters ALL rows'
      KV before ``gqa_decode_paged`` reads, and row (b, i)'s
      ``kv_len = pos_b + i + 1`` masks everything deeper — exactly
      ``prefill_chunk_paged``'s write-then-read intra-call causality,
      so row i attends the KV rows 0..i-1 wrote THIS call. Rows past
      ``limit`` park on the scratch page (``active`` mask), same as a
      frozen multistep row.
    - **accept**: ``serving.speculate.spec_accept`` keeps the longest
      prefix where each row consumed the token the previous row
      argmaxed (exact-match greedy — a committed token is committed
      because a row fed the identical committed prefix produced it,
      which is the whole bitwise-trace argument), clamped by ``limit``
      and frozen after EOS so EOS is always the LAST committed token.

    Rejected rows' KV lands at positions ``>= pos'`` and is dead: the
    next dispatch re-writes those positions before any row's ``kv_len``
    admits them (writes precede reads per layer), and whole rejected
    pages are returned to the pool host-side via ``free_tail`` — no
    device-side unwind needed, which is why the accept path has no host
    sync.

    Returns ``(toks [K, B], accepted [B], token' [B], pos' [B],
    hist' [B, H], hist_len' [B], pages)``. ``toks[i, b]`` is row (b,i)'s
    verified argmax — the committed tokens are exactly
    ``toks[:accepted[b], b]``; ``token'``/``pos'`` advance by
    ``accepted`` (``accepted >= 1`` for every live row, since position 0
    consumes the authentic last token); ``hist'`` is ``hist`` rolled
    left by ``accepted`` with the committed tokens appended — the host
    mirrors the same roll, so history never re-uploads on the hot path.
    ``horizon=1`` drafts nothing and degenerates to one greedy step."""
    from triton_dist_tpu.serving.speculate import ngram_draft, spec_accept

    K = int(horizon)
    assert K >= 1
    B = token.shape[0]
    limit = limit.astype(jnp.int32)
    drafts = ngram_draft(hist, hist_len, K - 1)                # [B, K-1]
    inp = jnp.concatenate([token[:, None].astype(jnp.int32), drafts],
                          axis=1)                              # [B, K]
    offs = jnp.arange(K, dtype=jnp.int32)[None, :]             # [1, K]
    ract = offs < limit[:, None]                               # [B, K]
    rpos = jnp.where(ract, pos[:, None] + offs, 0).astype(jnp.int32)
    fl = lambda a: a.reshape((B * K,) + a.shape[2:])           # row-major
    fbt = jnp.repeat(block_table, K, axis=0)                   # [B*K, S]
    nxt_fl, pages = decode_step_paged(params, fl(inp), fl(rpos), cfg,
                                      pages, fbt, ffn=ffn,
                                      active=fl(ract), sample=True,
                                      attn_io=attn_io, linear=linear)
    nxt = nxt_fl.reshape(B, K)
    m = spec_accept(inp, nxt, ract, eos_id)                    # [B]
    tok2 = jnp.take_along_axis(nxt, jnp.maximum(m - 1, 0)[:, None],
                               axis=1)[:, 0]
    token2 = jnp.where(m > 0, tok2, token)
    pos2 = pos + m
    # roll history left by m and append the committed tokens — the last
    # H entries of (hist ‖ nxt[:, :m]); the zero-masked tail past m
    # never enters the gather window
    H = hist.shape[1]
    commit = offs < m[:, None]
    ext = jnp.concatenate([hist, jnp.where(commit, nxt, 0)], axis=1)
    cols = m[:, None] + jnp.arange(H, dtype=jnp.int32)[None, :]
    hist2 = jnp.take_along_axis(ext, cols, axis=1)
    hlen2 = jnp.minimum(hist_len + m, H).astype(jnp.int32)
    return nxt.T, m, token2, pos2, hist2, hlen2, pages


def decode_step_sp(ctx, params: dict, token: jax.Array, pos: jax.Array,
                   cfg: LlamaConfig, cache: dict,
                   axis: str | None = None,
                   ag_method: str = "fused",
                   ffn=None) -> tuple[jax.Array, dict]:
    """Sequence-parallel one-token decode: the KV cache is sharded on its
    sequence dim across ``axis`` and attention runs the distributed
    flash-decode (local split-KV + fused partial-AG + lse-merge) — the
    model-level serving loop over ``SpGQAFlashDecodeAttention`` (reference
    sp_flash_decode_layer.py:78-184; its README decode-scaling workload).
    The cache update for the new token's (k, v) is a global
    dynamic_update_slice — GSPMD routes it to the owning shard. Weights
    are replicated (compose TP separately).

    ``cache`` as from ``init_kv_cache`` with k/v sharded
    P(None, None, None, axis, None) ([layers, B, Hkv, S, D] on S).

    ``ffn(h, p) -> [B, D]`` overrides the per-layer FFN block (``h`` is the
    post-mlp_norm hidden, ``p`` the layer's params) — how
    ``models.moe.moe_decode_step_sp`` swaps in the expert-parallel MoE FFN
    without duplicating the attention/cache plumbing.
    """
    from triton_dist_tpu.ops.flash_decode import sp_gqa_flash_decode

    axis = axis or ctx.axis_names[0]
    B = token.shape[0]
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = params["embed"][token].astype(cfg.dtype)
    positions = jnp.full((B, 1), pos, jnp.int32)

    # python-unrolled layer loop (not lax.scan): the distributed decode
    # kernel's shard_map does not compose with scan under the SPMD
    # partitioner on every backend, and decode-step jaxprs are small
    ks_out, vs_out = [], []
    for i in range(cfg.n_layers):
        p = LayerParams(params["blocks"], i)
        ck, cv = cache["k"][i], cache["v"][i]
        h = rmsnorm(x, p["attn_norm"], cfg.norm_eps)
        q = rope((h @ p["wq"]).reshape(B, 1, Hq, Dh), positions,
                 cfg.rope_theta)[:, 0]
        k = rope((h @ p["wk"]).reshape(B, 1, Hkv, Dh), positions,
                 cfg.rope_theta)
        v = (h @ p["wv"]).reshape(B, 1, Hkv, Dh)
        ck = lax.dynamic_update_slice(ck, k.transpose(0, 2, 1, 3),
                                      (0, 0, pos, 0))
        cv = lax.dynamic_update_slice(cv, v.transpose(0, 2, 1, 3),
                                      (0, 0, pos, 0))
        kv_len = jnp.full((B,), pos + 1, jnp.int32)
        attn = sp_gqa_flash_decode(ctx, q, ck, cv, kv_len, axis=axis,
                                   ag_method=ag_method)
        x = x + attn.reshape(B, Hq * Dh).astype(x.dtype) @ p["wo"]
        h = rmsnorm(x, p["mlp_norm"], cfg.norm_eps)
        if ffn is None:
            ff = (jax.nn.silu((h @ p["w_gate"]).astype(jnp.float32)
                              ).astype(h.dtype) * (h @ p["w_up"])
                  ) @ p["w_down"]
        else:
            ff = ffn(h, p)
        x = x + ff.astype(x.dtype)
        ks_out.append(ck)
        vs_out.append(cv)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits, {"k": jnp.stack(ks_out), "v": jnp.stack(vs_out)}


def generate(params: dict, prompt: jax.Array, cfg: LlamaConfig,
             max_new_tokens: int, max_seq: int | None = None) -> jax.Array:
    """Greedy generation over the contiguous cache: prefill + scanned
    decode loop (batch decode, the reference's target regime, SURVEY.md
    §5.7). Returns [B, max_new_tokens]. The golden the serving tests hold
    the engine's tokens to: it shares no paged code with it.
    """
    B, S0 = prompt.shape
    max_seq = max_seq or cfg.max_seq_len
    assert S0 + max_new_tokens <= max_seq
    cache = init_kv_cache(cfg, B, max_seq)
    logits, cache = prefill(params, prompt, cfg, cache)
    tok0 = jnp.argmax(logits, -1).astype(jnp.int32)

    def step(carry, i):
        tok, cache = carry
        logits, cache = decode_step(params, tok, S0 + i, cfg, cache)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return (nxt, cache), tok

    (_, _), toks = lax.scan(step, (tok0, cache),
                            jnp.arange(max_new_tokens, dtype=jnp.int32))
    return toks.T                                          # [B, new]


# ---------------------------------------------------------------------------
# hand-overlapped TP forward (the reference's raison d'être)
# ---------------------------------------------------------------------------

def forward_tp_overlap(ctx: ShmemContext, params: dict, tokens: jax.Array,
                       cfg: LlamaConfig, axis: str | None = None) -> jax.Array:
    """TP forward where every Megatron linear pair runs through the Pallas
    overlap kernels: qkv/gate/up = AG-GEMM (activations sequence-sharded in,
    column-sharded weights), o/down = GEMM-RS (back to sequence-sharded) —
    the model-level composition of reference tutorials 07 (AG-GEMM) and 08
    (GEMM-RS). Layer loop is a Python loop (one pallas_call per linear);
    params may be replicated or TP-sharded on the mesh.

    tokens [B, S] with B*S divisible by (ranks * 128). Returns logits.
    """
    from triton_dist_tpu.ops.allgather_gemm import ag_gemm
    from triton_dist_tpu.ops.gemm import GemmConfig
    from triton_dist_tpu.ops.gemm_reduce_scatter import gemm_rs

    axis = axis or ctx.axis_names[0]
    nr = ctx.axis_size(axis)
    B, S = tokens.shape
    D = cfg.d_model
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = jnp.arange(S)[None, :].repeat(B, 0)
    blocks = params["blocks"]

    def tile(m, n):   # largest power-of-two tile ≤128 dividing the problem
        return GemmConfig(block_m=math.gcd(128, m), block_n=math.gcd(128, n))

    def col(x2d, w):
        return ag_gemm(ctx, x2d, w, axis=axis,
                       cfg=tile(x2d.shape[0] // nr, w.shape[1] // nr))

    def row(x2d, w):
        return gemm_rs(ctx, x2d, w, axis=axis,
                       cfg=tile(x2d.shape[0] // nr, w.shape[1]))

    T = B * S
    xs = x.reshape(T, D)  # sequence-major token rows, P(axis)-sharded by ops
    for l in range(cfg.n_layers):
        p = jax.tree.map(lambda a: a[l], blocks)
        h = rmsnorm(xs, p["attn_norm"], cfg.norm_eps)
        # fused qkv column-parallel AG-GEMM (one gather, one wide GEMM),
        # interleaved PER SHARD — a plain concat of the TP-sharded weights
        # would reshard them every layer (the gate‖up trick of
        # mlp_tp_overlap, with heterogeneous widths)
        qw, kw = Hq * Dh // nr, Hkv * Dh // nr
        wqkv = jnp.concatenate(
            [p["wq"].reshape(D, nr, qw), p["wk"].reshape(D, nr, kw),
             p["wv"].reshape(D, nr, kw)], axis=2).reshape(D, -1)
        qkv = col(h, wqkv).reshape(T, nr, qw + 2 * kw)
        q = qkv[..., :qw].reshape(T, Hq * Dh)
        k = qkv[..., qw:qw + kw].reshape(T, Hkv * Dh)
        v = qkv[..., qw + kw:].reshape(T, Hkv * Dh)
        q = rope(q.reshape(B, S, Hq, Dh), positions, cfg.rope_theta)
        k = rope(k.reshape(B, S, Hkv, Dh), positions, cfg.rope_theta)
        attn = _attention(q, k, v.reshape(B, S, Hkv, Dh),
                          1.0 / math.sqrt(Dh))
        xs = xs + row(attn.reshape(T, Hq * Dh), p["wo"])

        h = rmsnorm(xs, p["mlp_norm"], cfg.norm_eps)
        xs = xs + mlp_tp_overlap(ctx, h, p["w_gate"], p["w_up"],
                                 p["w_down"], axis=axis)

    x = rmsnorm(xs.reshape(B, S, D), params["final_norm"], cfg.norm_eps)
    return (x @ params["lm_head"]).astype(jnp.float32)


def plain_chunk_walks(layers: int) -> tuple:
    """``PagedFamily.chunk_walks`` of a family whose ``layers`` attention
    layers call ``gqa_prefill_paged`` as ``_gqa_attention`` does: the whole
    context, at the kernel's default block."""
    from triton_dist_tpu.ops.flash_decode import PREFILL_ROWS_PER_BLOCK
    return ((layers, PREFILL_ROWS_PER_BLOCK, None),)


GQA_DENSE = PagedFamily(
    name="gqa_dense", init_pool=init_page_pool, segments=_gqa_segments,
    attention=_gqa_attention, decode_speculate=decode_speculate_paged,
    chunk_walks=lambda cfg: plain_chunk_walks(cfg.n_layers))


__all__ = ["LlamaConfig", "LayerParams", "PagedFamily", "Walks", "GQA_DENSE",
           "plain_chunk_walks",
           "swiglu_ffn", "gated_ffn", "live_rows", "require_config", "init_params",
           "param_specs", "forward",
           "forward_tp_overlap", "mlp_tp_overlap", "rmsnorm", "rope",
           "block_apply", "init_kv_cache", "init_page_pool", "prefill",
           "decode_step", "decode_step_paged", "decode_multistep_paged",
           "decode_speculate_paged", "prefill_chunk_paged", "generate"]
