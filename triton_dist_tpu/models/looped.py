"""LOOPED dense decoders: one stack of layers that every token walks
``n_walks`` times over the SAME weights, each walk with cache planes of its
own, a norm and an exit gate at every walk's end (the Ouro / LoopLM class).

What differs from ``models.llama`` reaches the paged programs as data
(``LOOPED``, a ``models.llama.PagedFamily``): the decode, multistep and chunk
programs, the layer loop, the engine, the scheduler and the page ledger are
the ones every family uses.

- **The parameter row and the pool plane come apart.** Walk t of layer l is
  row l of the parameter stacks and plane ``t * n_layers + l`` of the pool
  (``PagedFamily.walks``: the layer loop hands ``attention`` the PLANE):
  ``k`` / ``v`` [n_walks x n_layers, page, Hkv, page_size, head_dim]. Walk t
  of layer l attends the keys and values that walk t of layer l wrote for
  the earlier tokens and no other walk's. The pages are plain K/V pages over
  more planes than layers: the ledger, the prefix cache and the engine's page
  copy / export / import (which map over the leaves) serve them as the dense
  family's.
- **Sandwich norms:** ``x + n(attn(n(x)))`` then ``x + n(ffn(n(x)))``, four
  norm weights a layer. The layer loop norms a branch's INPUT
  (``attn_norm`` / ``mlp_norm``); the branch's output is normed here, in the
  family's ``attention`` and segment ``ffn`` (``attn_out_norm`` /
  ``mlp_out_norm``).
- **Attention:** as many KV heads as query heads (a group of ONE), no bias,
  no q / k norm, RoPE (half-split) on the whole head at the token's position,
  the same in every walk.
- **A walk's end** (``walk_end``): the stream is normed by the model's final
  norm (after EVERY walk; the normed rows are the next walk's input) and an
  exit gate is read off them: ``lam_t = sigmoid(h_t . w_g + b_g)`` in float32,
  ``p_t = lam_t prod_{j<t} (1 - lam_j)`` (the last walk takes what is left),
  and the head takes the rows of the first walk whose running sum of ``p``
  reaches ``exit_threshold``, else the last walk's. EVERY walk is computed
  for every row whatever the gate says (a later token's cache needs them):
  at the published threshold 1.0 an earlier walk is picked only where a gate
  saturates, and ``loop_early_exit_rows`` counts the live rows where it did.
- **The head is untied**, on the picked rows as they are (normed already).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from triton_dist_tpu.models.llama import (PagedFamily, Walks, gated_ffn,
                                          live_rows, plain_chunk_walks,
                                          rmsnorm, rope)

COUNTERS = ("loop_plane_keys", "loop_row_calls", "loop_early_exit_rows")


@dataclasses.dataclass(frozen=True)
class LoopedConfig:
    vocab_size: int = 49152
    d_model: int = 2048
    n_layers: int = 48
    n_walks: int = 4                   # times a token walks the stack
    n_heads: int = 16
    n_kv_heads: int = 16
    head_dim: int = 128
    d_ff: int = 5632
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    exit_threshold: float = 1.0
    max_seq_len: int = 65536
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        assert self.n_walks >= 1 and self.n_heads % self.n_kv_heads == 0
        assert self.head_dim % 2 == 0

    @property
    def n_planes(self) -> int:
        """Cache planes: one a (walk, layer)."""
        return self.n_walks * self.n_layers

    @property
    def paged(self) -> PagedFamily:
        return LOOPED

    @classmethod
    def tiny(cls, **changes):
        """Test size, every mechanism kept: three layers walked three times,
        four heads for four KV heads."""
        return dataclasses.replace(cls(
            vocab_size=256, d_model=64, n_layers=3, n_walks=3, n_heads=4,
            n_kv_heads=4, head_dim=16, d_ff=128, rope_theta=1e4,
            max_seq_len=256, dtype=jnp.float32), **changes)


def kv_bytes_per_token(cfg: LoopedConfig) -> int:
    """Bytes a token holds over ALL planes: K and V of every KV head, a
    plane a (walk, layer)."""
    return (cfg.n_planes * 2 * cfg.n_kv_heads * cfg.head_dim
            * jnp.dtype(cfg.dtype).itemsize)


# -- weights -------------------------------------------------------------------

def init_params(key: jax.Array, cfg: LoopedConfig) -> dict:
    """Seeded weights in the layout the programs take (the benchmark's
    reference draws its own in the same layout, at scales of its own): ONE
    stack of ``n_layers`` whatever the walks; the norms' weights and the exit
    gate float32."""
    L, D, F, V = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(key, 16))

    def f32(*shape, scale=1.0):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def w(*shape, scale=0.1):
        return f32(*shape, scale=scale).astype(cfg.dtype)

    def gain(*shape):
        return 1.0 + f32(*shape, scale=0.1)

    return {
        "embed": w(V, D, scale=0.5),
        "blocks": {
            "attn_norm": gain(L, D), "attn_out_norm": gain(L, D),
            "mlp_norm": gain(L, D), "mlp_out_norm": gain(L, D),
            "wq": w(L, D, Hq * Dh), "wk": w(L, D, Hkv * Dh),
            "wv": w(L, D, Hkv * Dh), "wo": w(L, Hq * Dh, D),
            "w_gate": w(L, D, F), "w_up": w(L, D, F), "w_down": w(L, F, D)},
        "final_norm": gain(D),
        "exit_gate": f32(D, scale=0.02), "exit_bias": f32(),
        "lm_head": w(D, V),
    }


# -- cache -----------------------------------------------------------------------

def init_pools(cfg: LoopedConfig, num_pages: int, page_size: int) -> dict:
    """``k`` / ``v`` [n_walks x n_layers, num_pages, Hkv, page, head_dim]:
    the ledger's pages (``models.llama.init_page_pool``'s life: carried
    whole, written and read in place), a plane a (walk, layer)."""
    assert page_size % 8 == 0, f"page_size {page_size} must be 8-aligned"
    shape = (cfg.n_planes, num_pages, cfg.n_kv_heads, page_size, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


# -- a layer's two branches ---------------------------------------------------------
# (``n_walks``, ``write_plane``, ``read_plane``, ``rotary_positions``,
# ``branch_norm`` and ``walk_norm`` are functions of their own so that the
# benchmark's controls, ``benchmark/tools/loop_control.py``, can put ONE of
# them wrong at a time)

def n_walks(cfg: LoopedConfig) -> int:
    """Times a token walks the stack."""
    return cfg.n_walks


def write_plane(cfg: LoopedConfig, plane):
    """The pool plane a (walk, layer)'s keys and values are written to: its
    own, ``walk * n_layers + layer``, as the layer loop hands it over."""
    del cfg
    return plane


def read_plane(cfg: LoopedConfig, plane, shared_table: bool):
    """The pool plane a (walk, layer)'s queries walk: its own, for a
    chunk's rows (``shared_table``) as for decode rows."""
    del cfg, shared_table
    return plane


def rotary_positions(cfg: LoopedConfig, pos: jax.Array, plane,
                     of: str) -> jax.Array:
    """The rotary position of the queries' (``of`` "q") or keys' ("k") rows
    at ``pos``: the token's, in every walk."""
    del cfg, plane, of
    return pos


def branch_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """The norm on a branch's OUTPUT (the sandwich's second slice)."""
    return rmsnorm(x, w, eps)


def walk_norm(cfg: LoopedConfig, params: dict, x: jax.Array) -> jax.Array:
    """The model's final norm, at the end of every walk."""
    return rmsnorm(x, params["final_norm"], cfg.norm_eps)



def _attention(cfg: LoopedConfig, p, h, plane, pool, block_table, pos,
               kv_len, active, shared_table, lin, attn_io):
    """One (walk, layer)'s attention on normed rows h [R, D], over plane
    ``plane`` of the pool, and the norm on its output."""
    from triton_dist_tpu.ops.flash_decode import (gqa_decode_paged,
                                                  gqa_prefill_paged,
                                                  paged_kv_write)
    assert attn_io is None, "the looped family has no attn_io hook"
    R = h.shape[0]
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    plane = jnp.asarray(plane, jnp.int32)
    at = {of: rotary_positions(cfg, pos, plane, of)[:, None].astype(jnp.int32)
          for of in "qk"}
    q = rope(lin(h, p["wq"], "wq").reshape(R, 1, Hq, Dh), at["q"],
             cfg.rope_theta)[:, 0]
    k = rope(lin(h, p["wk"], "wk").reshape(R, 1, Hkv, Dh), at["k"],
             cfg.rope_theta)[:, 0]
    v = lin(h, p["wv"], "wv").reshape(R, Hkv, Dh)
    live = live_rows(kv_len, active)
    counts = {"loop_plane_keys": jnp.sum(jnp.where(live, kv_len, 0)
                                         ).astype(jnp.int32),
              "loop_row_calls": jnp.sum(live).astype(jnp.int32)}
    kp, vp = paged_kv_write(pool["k"], pool["v"], k, v, block_table, pos,
                            active=active, layer=write_plane(cfg, plane),
                            shared_table=shared_table)
    read = read_plane(cfg, plane, shared_table)
    if shared_table:
        attn = gqa_prefill_paged(q, kp, vp, block_table[0], kv_len,
                                 layer=read)
    else:
        attn, _lse = gqa_decode_paged(q, kp, vp, block_table, kv_len,
                                      layer=read)
    out = lin(attn.reshape(R, Hq * Dh), p["wo"], "wo")
    return (branch_norm(out, p["attn_out_norm"], cfg.norm_eps),
            {"k": kp, "v": vp}, counts)


def _ffn(cfg: LoopedConfig, p, h: jax.Array, layer=None, active=None):
    """The dense FFN of a layer and the norm on its output."""
    del layer, active
    out = gated_ffn(h, p["w_gate"], p["w_up"], p["w_down"])
    return branch_norm(out, p["mlp_out_norm"], cfg.norm_eps), {}


def _segments(cfg: LoopedConfig, params: dict) -> list:
    return [(params["blocks"], 0, cfg.n_layers, _ffn)]


# -- a walk's end ---------------------------------------------------------------------

def _walk_start(cfg: LoopedConfig, params: dict, x: jax.Array) -> dict:
    """Nothing picked yet: all of the exit distribution is left."""
    del cfg, params
    R = x.shape[0]
    return {"rows": jnp.zeros_like(x), "left": jnp.ones((R,), jnp.float32),
            "cum": jnp.zeros((R,), jnp.float32),
            "done": jnp.zeros((R,), jnp.bool_)}


def walk_end(cfg: LoopedConfig, params: dict, x: jax.Array, t, state: dict,
             live: jax.Array):
    """The end of walk ``t`` on the stream x [R, D]: norm, gate, and the
    exit rule's pick. Returns (the next walk's input, state, counts)."""
    last = t == n_walks(cfg) - 1
    with jax.named_scope("loop_step_end"):
        x = walk_norm(cfg, params, x)
        lam = jax.nn.sigmoid(
            jnp.sum(x.astype(jnp.float32) * params["exit_gate"], axis=-1)
            + params["exit_bias"])
        cum = state["cum"] + jnp.where(last, 1.0, lam) * state["left"]
        now = jnp.logical_and(~state["done"], jnp.logical_or(
            last, cum >= jnp.float32(cfg.exit_threshold)))
        state = {"rows": jnp.where(now[:, None], x, state["rows"]),
                 "left": state["left"] * (1.0 - lam), "cum": cum,
                 "done": jnp.logical_or(state["done"], now)}
        early = jnp.sum(now & live & ~last).astype(jnp.int32)
    return x, state, {"loop_early_exit_rows": early}


def _walks(cfg: LoopedConfig) -> Walks:
    return Walks(n=n_walks(cfg), start=_walk_start, end=walk_end,
                 rows=lambda state: state["rows"])


LOOPED = PagedFamily(
    name="looped", init_pool=init_pools, segments=_segments,
    attention=_attention, walks=_walks, counters=COUNTERS,
    # plain K/V pages over more planes than layers: the prefix cache and the
    # engine's page copy / export / import serve them as the dense family's
    lacks=("speculate", "hooks"),
    # every plane's layer walks the chunk's pages
    chunk_walks=lambda cfg: plain_chunk_walks(cfg.n_planes))


__all__ = ["LoopedConfig", "LOOPED", "COUNTERS", "init_params", "init_pools",
           "kv_bytes_per_token", "n_walks", "write_plane", "read_plane",
           "rotary_positions", "branch_norm", "walk_norm", "walk_end"]
