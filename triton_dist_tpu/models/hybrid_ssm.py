"""Decoders with a state-space (Mamba-2) mixer BESIDE attention in every block
(the Falcon-H1 class): both read the same normed rows and their outputs are
summed, each under its scalar multiplier.

What differs from ``models.llama`` reaches the paged programs as data
(``HYBRID_SSM``, a ``models.llama.PagedFamily``): the decode, multistep and
chunk programs, the layer loop, the engine, the scheduler and the page ledger
are the ones every family uses.

- **Two kinds of state in every layer.** The attention's K/V live in the
  ledger's pages under the engine's block table, are walked, and grow with
  the context. The mixer's state is a FIXED block a sequence that is
  REWRITTEN every token: ``ssm`` [L, slots + 1, H, N, P] float32 (the
  recurrence's state, [N, P] a head: ``ops.ssm``) and ``conv`` [L x (slots +
  1), (K - 1) x channels] (the last K - 1 rows of the un-convolved ``xBC``;
  two-dimensional, layer-major: with 65 rows a layer the three-dimensional
  form is tiled apart from its row view, and every gather and scatter of
  rows re-laid the leaf out, 2 % of a decode step on the v5e). They are the
  SLOT's, not the ledger's (``PagedFamily.slot_state``): slot s owns row ``1
  + s`` of every layer of both leaves (row 0 is scratch), which rides the
  last column of its block-table row, as a ring family's first ring page
  does.
- **Decode rows** map to slots; a row that is not live (parked, mid-prefill,
  frozen mid-scan) reads and writes NO state: its slot's state is the same to
  the bit afterwards (``ops.ssm.ssm_decode_update``).
- **A chunk's rows** are ONE slot's consecutive positions: they start from
  the slot's stored state, or from zero when the chunk starts a request
  (``pos[0] == 0``: a new tenant never sees the last one's state), run the
  chunked scan (``ops.ssm.ssd_chunk_scan``), and leave behind the state after
  the chunk's last LIVE row and the last K - 1 live rows of ``xBC``.
- **A state cannot be rewound or shared by reference**: a preempted request
  restarts, and prefix cache, speculation and page copy / export / import are
  refused by name (``lacks``, the engine's ``slot_state`` guards).

Every multiplier of the published config is computed where the source
computes it (none is folded into a weight). RoPE is half-split.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from triton_dist_tpu.models.llama import (PagedFamily, plain_chunk_walks,
                                          rope)

# The recurrent state's dtype: a running sum over the whole context. Not a
# config field: bfloat16 is a different result, not a faster one
# (``benchmark/tools/state_control.py`` reads how different).
STATE_DTYPE = jnp.float32


@dataclasses.dataclass(frozen=True)
class HybridSSMConfig:
    vocab_size: int = 261120
    d_model: int = 5120
    n_layers: int = 72
    n_heads: int = 20
    n_kv_heads: int = 4
    head_dim: int = 128
    d_ff: int = 21504
    ssm_heads: int = 32
    ssm_head_dim: int = 128
    ssm_state: int = 256
    ssm_groups: int = 2
    ssm_conv: int = 4
    ssm_chunk: int = 128               # tokens a block of the chunk's scan
    rope_theta: float = 1e11
    norm_eps: float = 1e-5
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)   # z, x, B, C, dt
    mlp_multipliers: tuple = (1.0, 1.0)                  # gate, down
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16
    # the engine whose slots own the states (``bind``)
    state_slots: int = 0

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def d_xbc(self) -> int:
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    @property
    def paged(self) -> PagedFamily:
        return HYBRID_SSM

    @classmethod
    def tiny(cls, n_layers: int = 2):
        """Test size: a query group of 5, two state groups, every multiplier
        away from 1."""
        return cls(vocab_size=256, d_model=64, n_layers=n_layers, n_heads=10,
                   n_kv_heads=2, head_dim=16, d_ff=128, ssm_heads=4,
                   ssm_head_dim=16, ssm_state=32, ssm_groups=2, ssm_chunk=8,
                   rope_theta=1e4, embedding_multiplier=2.0,
                   lm_head_multiplier=0.5, attention_in_multiplier=1.0,
                   attention_out_multiplier=0.5, key_multiplier=0.25,
                   ssm_in_multiplier=0.5, ssm_out_multiplier=0.4,
                   ssm_multipliers=(0.7, 0.5, 0.6, 0.8, 0.9),
                   mlp_multipliers=(0.6, 0.3), max_seq_len=256,
                   dtype=jnp.float32)


def bind(cfg: HybridSSMConfig, num_slots: int, prefill_chunk: int
         ) -> HybridSSMConfig:
    del prefill_chunk
    return dataclasses.replace(cfg, state_slots=num_slots)


def slot_state_bytes(cfg: HybridSSMConfig) -> int:
    """Bytes of state a slot owns over all layers (both leaves)."""
    ssm = cfg.ssm_heads * cfg.ssm_state * cfg.ssm_head_dim \
        * jnp.dtype(STATE_DTYPE).itemsize
    conv = (cfg.ssm_conv - 1) * cfg.d_xbc * jnp.dtype(cfg.dtype).itemsize
    return cfg.n_layers * (ssm + conv)


def xbc_multipliers(cfg: HybridSSMConfig) -> np.ndarray:
    """The multiplier of every column of ``xBC``: its x, B and C segments."""
    gn = cfg.ssm_groups * cfg.ssm_state
    return np.repeat(np.asarray(cfg.ssm_multipliers[1:4], np.float32),
                     [cfg.d_ssm, gn, gn])


# -- weights -------------------------------------------------------------------

def init_params(key: jax.Array, cfg: HybridSSMConfig) -> dict:
    """Seeded weights in the layout the programs take (the benchmark's
    reference draws its own in the same layout, at scales of its own)."""
    L, D, V, F = cfg.n_layers, cfg.d_model, cfg.vocab_size, cfg.d_ff
    qd, kvd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    H, K = cfg.ssm_heads, cfg.ssm_conv
    keys = iter(jax.random.split(key, 24))

    def w(*shape, scale=0.02, dtype=cfg.dtype):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    ones = lambda *shape: jnp.ones(shape, jnp.float32)      # noqa: E731
    blocks = {
        "attn_norm": ones(L, D),
        "w_z": w(L, D, cfg.d_ssm, scale=0.2),
        "w_xbc": w(L, D, cfg.d_xbc, scale=0.2), "w_dt": w(L, D, H, scale=0.2),
        "conv_w": w(L, K, cfg.d_xbc, scale=0.3, dtype=jnp.float32),
        "conv_b": w(L, cfg.d_xbc, scale=0.1, dtype=jnp.float32),
        "dt_bias": w(L, H, scale=1.0, dtype=jnp.float32),
        "A_log": w(L, H, scale=1.0, dtype=jnp.float32) - 2.0,
        "D": ones(L, H), "ssm_norm": ones(L, cfg.d_ssm),
        "w_out": w(L, cfg.d_ssm, D, scale=0.05),
        "wq": w(L, D, qd, scale=0.1), "wk": w(L, D, kvd, scale=0.1),
        "wv": w(L, D, kvd, scale=0.1), "wo": w(L, qd, D, scale=0.1),
        "mlp_norm": ones(L, D), "w_gate": w(L, D, F, scale=0.1),
        "w_up": w(L, D, F, scale=0.1), "w_down": w(L, F, D, scale=0.1)}
    return {"embed": w(V, D, scale=0.2), "blocks": blocks,
            "final_norm": ones(D), "lm_head": w(D, V, scale=0.5)}


# -- cache -----------------------------------------------------------------------

def init_pools(cfg: HybridSSMConfig, num_pages: int, page_size: int) -> dict:
    """``k`` / ``v`` [L, num_pages, Hkv, page, Dh]: the ledger's pages
    (``models.llama.init_page_pool``'s life: carried whole, written and read
    in place). ``ssm`` [L, slots + 1, H, N, P] and ``conv`` [L x (slots + 1),
    (K - 1) x channels]: a layer's scratch row and every slot's state."""
    assert page_size % 8 == 0, f"page_size {page_size} must be 8-aligned"
    assert cfg.state_slots > 0, "bind() the config to an engine first"
    L, S = cfg.n_layers, cfg.state_slots + 1
    kv = (L, num_pages, cfg.n_kv_heads, page_size, cfg.head_dim)
    return {"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
            "ssm": jnp.zeros((L, S, cfg.ssm_heads, cfg.ssm_state,
                              cfg.ssm_head_dim), STATE_DTYPE),
            "conv": jnp.zeros((L * S, (cfg.ssm_conv - 1) * cfg.d_xbc),
                              cfg.dtype)}


# -- the mixer -------------------------------------------------------------------

def chunk_starts_fresh(pos0: jax.Array) -> jax.Array:
    """Whether a chunk whose first row sits at position ``pos0`` starts a
    request (zero state) or continues one (the slot's stored state)."""
    return pos0 == 0


def _conv_silu(cfg, p, rows: jax.Array) -> jax.Array:
    """The causal depthwise conv over ``rows`` [T + K - 1, channels] (the K -
    1 rows before the first, then the T rows), its bias and silu: [T, .]."""
    K = cfg.ssm_conv
    T = rows.shape[0] - (K - 1)
    rows = rows.astype(jnp.float32)
    conv = sum(rows[k:k + T] * p["conv_w"][k] for k in range(K))
    return jax.nn.silu(conv + p["conv_b"]).astype(cfg.dtype)


def _gated_norm(cfg, p, y: jax.Array, gate: jax.Array) -> jax.Array:
    """rmsnorm over each of the G groups of ``(y silu(gate))``, weighted."""
    R, G = y.shape[0], cfg.ssm_groups
    y = (y * jax.nn.silu(gate.astype(jnp.float32))).reshape(R, G, -1)
    rms = lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
    return ((y * rms).reshape(R, -1) * p["ssm_norm"]).astype(cfg.dtype)


def mixer(cfg: HybridSSMConfig, p, u: jax.Array, layer, pool: dict,
          slot: jax.Array, pos: jax.Array, live: jax.Array,
          shared_table: bool, lin) -> tuple[jax.Array, dict]:
    """The Mamba-2 mixer of layer ``layer`` on normed rows u [R, D]. Decode
    rows: row r is one step of slot ``slot[r]``, if ``live[r]``. A chunk
    (``shared_table``): the rows are slot ``slot[0]``'s consecutive positions
    from ``pos[0]``, the live ones first. Returns (out [R, D], pool)."""
    from triton_dist_tpu.ops.ssm import ssd_chunk_scan, ssm_decode_update
    R = u.shape[0]
    H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    d, K = cfg.d_ssm, cfg.ssm_conv
    ssm, conv2d = pool["ssm"], pool["conv"]
    S = ssm.shape[1]
    # the in-projection as its three column blocks (a last dim that is no
    # multiple of 128 lanes makes the TPU hold the stack column-major and
    # the decode program re-lay out all of it every dispatch)
    u = u * jnp.asarray(cfg.ssm_in_multiplier, u.dtype)
    m_z, m_dt = cfg.ssm_multipliers[0], cfg.ssm_multipliers[4]
    gate = lin(u, p["w_z"], "w_z") * jnp.asarray(m_z, u.dtype)
    xbc = lin(u, p["w_xbc"], "w_xbc") * jnp.asarray(xbc_multipliers(cfg),
                                                    u.dtype)
    dt = jax.nn.softplus(
        (lin(u, p["w_dt"], "w_dt") * jnp.asarray(m_dt, u.dtype)
         ).astype(jnp.float32) + p["dt_bias"])               # [R, H]
    A = -jnp.exp(p["A_log"])
    f32 = lambda a: a.astype(jnp.float32)                   # noqa: E731
    split = lambda a: (f32(a[:, :d]).reshape(-1, H, P),     # noqa: E731
                       f32(a[:, d:d + G * N]).reshape(-1, G, N),
                       f32(a[:, d + G * N:]).reshape(-1, G, N))
    base = jnp.asarray(layer, jnp.int32) * S
    if shared_table:
        fresh = chunk_starts_fresh(pos[0])
        row = base + slot[0]
        before = jnp.where(fresh, 0, lax.dynamic_slice_in_dim(
            conv2d, row, 1)[0]).reshape(K - 1, -1)
        rows = jnp.concatenate([before, xbc])               # [K - 1 + R, .]
        x, b, c = split(_conv_silu(cfg, p, rows))
        at = (jnp.asarray(layer, jnp.int32), slot[0], 0, 0, 0)
        h0 = jnp.where(fresh, 0, f32(lax.dynamic_slice(
            ssm, at, (1, 1, H, N, P))[0, 0]))
        with jax.named_scope("ssm_scan"):
            y, hT = ssd_chunk_scan(x, jnp.where(live[:, None], dt, 0.0), A,
                                   b, c, h0, block=cfg.ssm_chunk)
        n_live = jnp.sum(live).astype(jnp.int32)
        ssm = lax.dynamic_update_slice(
            ssm, hT.astype(ssm.dtype)[None, None], at)
        # the K - 1 rows before row n_live of the chunk
        after = lax.dynamic_slice_in_dim(rows, n_live, K - 1)
        conv2d = lax.dynamic_update_slice(
            conv2d, after.reshape(1, -1).astype(conv2d.dtype), (row, 0))
    else:
        idx = base + jnp.where(live, slot, 0)
        before = conv2d.at[idx].get(mode="promise_in_bounds")
        rows = jnp.concatenate([before.reshape(R, K - 1, -1),
                                xbc[:, None]], axis=1)      # [R, K, .]
        conv_out = jnp.einsum("rkc,kc->rc", f32(rows), p["conv_w"])
        x, b, c = split(jax.nn.silu(conv_out + p["conv_b"]
                                    ).astype(cfg.dtype))
        y, ssm = ssm_decode_update(ssm, layer, slot, live,
                                   x * dt[:, :, None], jnp.exp(dt * A), b, c)
        # a row that is not live writes nothing (an index past the last row)
        conv2d = conv2d.at[jnp.where(live, idx, conv2d.shape[0])].set(
            rows[:, 1:].reshape(R, -1).astype(conv2d.dtype), mode="drop")
    y = (y + p["D"][:, None] * x).reshape(R, d)
    out = lin(_gated_norm(cfg, p, y, gate), p["w_out"], "w_out")
    return out, {**pool, "ssm": ssm, "conv": conv2d}


# -- the layer's two readers of the normed rows ------------------------------------

def _mixer_and_attention(cfg: HybridSSMConfig, p, h, layer, pool,
                         block_table, pos, kv_len, active, shared_table, lin,
                         attn_io):
    """``ssm_out_multiplier * mixer(h) + attention_out_multiplier * attn(
    attention_in_multiplier * h)``: what a block adds before its MLP. The
    block table's last column is the row of the slot's state; the columns
    before it are the sequence's pages."""
    from triton_dist_tpu.ops.flash_decode import (gqa_decode_paged,
                                                  gqa_prefill_paged,
                                                  paged_kv_write)
    assert attn_io is None, "the hybrid family has no attn_io hook"
    R = h.shape[0]
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    live = kv_len > 0 if active is None else jnp.logical_and(
        active, kv_len > 0)
    table, slot = block_table[:, :-1], block_table[:, -1]
    with jax.named_scope("ssm_mixer"):
        mix, pool = mixer(cfg, p, h, layer, pool, slot, pos, live,
                          shared_table, lin)
    counts = {"ssm_state_rows": jnp.int32(0) if shared_table
              else jnp.sum(live).astype(jnp.int32)}
    with jax.named_scope("full_attention"):
        a = h * jnp.asarray(cfg.attention_in_multiplier, h.dtype)
        positions = pos[:, None].astype(jnp.int32)
        q = rope(lin(a, p["wq"], "wq").reshape(R, 1, Hq, Dh), positions,
                 cfg.rope_theta)[:, 0]
        k = lin(a, p["wk"], "wk") * jnp.asarray(cfg.key_multiplier, h.dtype)
        k = rope(k.reshape(R, 1, Hkv, Dh), positions, cfg.rope_theta)[:, 0]
        v = lin(a, p["wv"], "wv").reshape(R, Hkv, Dh)
        kp, vp = paged_kv_write(pool["k"], pool["v"], k, v, table, pos,
                                active=active, layer=layer,
                                shared_table=shared_table)
        if shared_table:
            attn = gqa_prefill_paged(q, kp, vp, table[0], kv_len,
                                     layer=layer)
        else:
            attn, _lse = gqa_decode_paged(q, kp, vp, table, kv_len,
                                          layer=layer)
        attn = lin(attn.reshape(R, Hq * Dh), p["wo"], "wo")
    out = mix * jnp.asarray(cfg.ssm_out_multiplier, mix.dtype) \
        + attn * jnp.asarray(cfg.attention_out_multiplier, attn.dtype)
    return out, {**pool, "k": kp, "v": vp}, counts


def scaled_ffn(cfg: HybridSSMConfig, p, h: jax.Array, layer=None,
               active=None):
    """``down_multiplier * ((silu(gate_multiplier * (h Wg)) * (h Wu)) Wd)``."""
    del layer, active
    gm, dm = cfg.mlp_multipliers
    gate = (h @ p["w_gate"]).astype(jnp.float32) * gm
    ff = (jax.nn.silu(gate).astype(h.dtype) * (h @ p["w_up"])) @ p["w_down"]
    return ff * jnp.asarray(dm, ff.dtype), {}


def _segments(cfg: HybridSSMConfig, params: dict) -> list:
    return [(params["blocks"], 0, cfg.n_layers, scaled_ffn)]


def _embed(cfg: HybridSSMConfig, params: dict, tokens: jax.Array):
    x = params["embed"][tokens].astype(cfg.dtype)
    return x * jnp.asarray(cfg.embedding_multiplier, x.dtype)


def _head(cfg: HybridSSMConfig, params: dict, x: jax.Array, lin):
    logits = lin(x, params["lm_head"], "lm_head").astype(jnp.float32)
    return logits * cfg.lm_head_multiplier


HYBRID_SSM = PagedFamily(
    name="hybrid_ssm", init_pool=init_pools, segments=_segments,
    attention=_mixer_and_attention, embed=_embed, head=_head,
    counters=("ssm_state_rows",),
    # a state is the slot's and cannot be rewound, shared or copied by page
    lacks=("speculate", "prefix_cache", "hooks"),
    slot_state=slot_state_bytes, bind=bind,
    chunk_walks=lambda cfg: plain_chunk_walks(cfg.n_layers))


__all__ = ["HybridSSMConfig", "HYBRID_SSM", "init_params", "init_pools",
           "bind", "mixer", "scaled_ffn", "slot_state_bytes",
           "chunk_starts_fresh"]
