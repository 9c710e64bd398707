"""Plain reference for decoder-only language models (dense or sparse-expert FFN).

Straight ``jax.numpy`` in float32 under ``precision=HIGHEST``: RMSNorm, GQA
attention with half-split RoPE (the Hugging Face ``rotate_half`` convention of
the Mistral and Mixtral sources), SwiGLU, and for ``num_local_experts`` > 0 a
dropless top-k router whose weights are a softmax over the selected logits.
No kernels, no cache, no batching. It imports nothing of the program and is
handed nothing the program made: the weights are this module's own, drawn from
the seed in the layout the serving engines take as input.

Departures from the published models, each on purpose:
- ``sliding_window`` is not applied (the cells' contexts stay under it);
- the weights are random, ``std`` 0.02, with ``wo``/``w_down`` scaled by
  1/sqrt(2 L) so that depth does not blow up the residual.

``quant="fp8"`` is the control of the output check: the same mathematics with
the inputs of every matrix product rounded to float8 e4m3 (rows of the
activations and output channels of the weights scaled to the format's range),
the nearest precision below the configurations' bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


# -- sizes -----------------------------------------------------------------

def sizes(cfg: dict) -> dict:
    """The shape numbers the reference needs, by their published keys."""
    heads = cfg["num_attention_heads"]
    return {
        "L": cfg["num_hidden_layers"], "D": cfg["hidden_size"],
        "Hq": heads, "Hkv": cfg["num_key_value_heads"],
        "Dh": cfg.get("head_dim") or cfg["hidden_size"] // heads,
        "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
        "E": cfg.get("num_local_experts", 0),
        "k": cfg.get("num_experts_per_tok", 0),
        "theta": float(cfg["rope_theta"]), "eps": float(cfg["rms_norm_eps"]),
        "dtype": jnp.dtype(cfg["torch_dtype"]),
    }


# -- weights ---------------------------------------------------------------

def init_weights(key: jax.Array, cfg: dict) -> dict:
    """Weights from the seed, made on the device in the served dtype. Call it
    under ``jax.jit`` (with ``out_shardings`` on a mesh) so that nothing is
    drawn leaf by leaf or on the host. Layout: stacked per layer, the pytree
    the serving engines accept."""
    z = sizes(cfg)
    L, D, F, V, E = z["L"], z["D"], z["F"], z["V"], z["E"]
    qd, kvd = z["Hq"] * z["Dh"], z["Hkv"] * z["Dh"]
    dt, s = z["dtype"], 0.02
    keys = iter(jax.random.split(key, 16))

    def w(*shape, scale=s):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dt)

    def gain(*shape):
        return 1.0 + 0.05 * jax.random.normal(next(keys), shape, jnp.float32)

    down = s / math.sqrt(2 * L)
    blocks = {"attn_norm": gain(L, D), "wq": w(L, D, qd), "wk": w(L, D, kvd),
              "wv": w(L, D, kvd), "wo": w(L, qd, D, scale=down),
              "mlp_norm": gain(L, D)}
    if E:
        blocks["w_router"] = jax.random.normal(
            next(keys), (L, D, E), jnp.float32) * s
        blocks["we_gate"] = w(L, E, D, F)
        blocks["we_up"] = w(L, E, D, F)
        blocks["we_down"] = w(L, E, F, D, scale=down)
    else:
        blocks["w_gate"] = w(L, D, F)
        blocks["w_up"] = w(L, D, F)
        blocks["w_down"] = w(L, F, D, scale=down)
    return {"embed": w(V, D), "blocks": blocks, "final_norm": gain(D),
            "lm_head": w(D, V)}


# -- mathematics -----------------------------------------------------------

def _fq(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 and back, scaled along ``axis`` to its range."""
    x = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _mm(x: jax.Array, w: jax.Array, quant: str | None) -> jax.Array:
    """x [..., K] @ w [K, N] in float32; ``quant`` rounds both inputs."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fq(x, -1), _fq(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown precision {quant!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [T, H, Dh], positions 0..T-1; rotate_half convention."""
    T, _, Dh = x.shape
    half = Dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal GQA. q [T, Hq, Dh]; k, v [T, Hkv, Dh]."""
    T, Hq, Dh = q.shape
    Hkv = k.shape[1]
    q = q.reshape(T, Hkv, Hq // Hkv, Dh)
    s = jnp.einsum("shgd,thd->hgst", q, k, precision=HIGHEST) / math.sqrt(Dh)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hgst,thd->shgd", p, v,
                      precision=HIGHEST).reshape(T, Hq * Dh)


def _dense_ffn(h, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wd, quant)


def _moe_ffn(h, router, tables, layer, k, quant, e_local, rows=512):
    """Dropless top-k: every token's k experts, weights = softmax over the
    selected logits. Computed as every expert on every row, then weighted
    (zero for the experts a row did not choose): plain, and exact.

    Only to bound memory: rows go through in blocks, and the experts in
    ``e_local`` rounds (expert j of every group of ``e_local`` neighbours at
    a time), so that where the expert tables are split over chips each chip
    holds one expert's float32 copy at a time and no table ever moves.
    ``tables`` are the STACKED [L, E, ., .] gate, up and down tables: one
    expert of one layer is sliced out at a time, never a whole layer."""
    T, D = h.shape
    E = router.shape[-1]
    groups = E // e_local
    logits = jnp.matmul(h, router.astype(jnp.float32), precision=HIGHEST)
    top, ids = jax.lax.top_k(logits, k)
    gate = jax.nn.softmax(top, axis=-1)                          # [T, k]
    weight = jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32)
                     * gate[..., None], axis=1)                  # [T, E]
    if quant not in (None, "fp8"):
        raise ValueError(f"unknown precision {quant!r}")
    q = (lambda a, ax: _fq(a, ax)) if quant else (lambda a, ax: a)
    R = math.gcd(T, rows)
    hb = h.reshape(T // R, R, D)
    wr = weight.reshape(T // R, R, groups, e_local)

    def one_round(j, out):
        g_w, u_w, d_w = (
            q(jax.lax.dynamic_slice(
                t.reshape(t.shape[0], groups, e_local, *t.shape[2:]),
                (layer, 0, j, 0, 0), (1, groups, 1, *t.shape[2:])
            )[0, :, 0].astype(jnp.float32), 1) for t in tables)  # [G, ., .]
        wj = jax.lax.dynamic_index_in_dim(wr, j, 3, keepdims=False)

        def block(args):
            x, wb = args                                         # [R,D] [R,G]
            g = jnp.einsum("rd,gdf->grf", q(x, -1), g_w, precision=HIGHEST)
            u = jnp.einsum("rd,gdf->grf", q(x, -1), u_w, precision=HIGHEST)
            y = jnp.einsum("grf,gfd->grd", q(jax.nn.silu(g) * u, -1), d_w,
                           precision=HIGHEST)
            return jnp.einsum("grd,rg->rd", y, wb, precision=HIGHEST)

        return out + jax.lax.map(block, (hb, wj)).reshape(T, D)

    out = jax.lax.fori_loop(0, e_local, one_round, jnp.zeros_like(h))
    return out


@functools.partial(jax.jit, static_argnames=("z", "quant", "e_local"))
def _layer(x, blocks, layer, z, quant, e_local=1):
    """One block on x [T, D] (float32); ``z`` is ``sizes`` as a tuple."""
    z = dict(z)
    p = {n: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
         for n, a in blocks.items() if not n.startswith("we_")}
    T = x.shape[0]
    h = _rmsnorm(x, p["attn_norm"], z["eps"])
    q = _rope(_mm(h, p["wq"], quant).reshape(T, z["Hq"], z["Dh"]), z["theta"])
    k = _rope(_mm(h, p["wk"], quant).reshape(T, z["Hkv"], z["Dh"]),
              z["theta"])
    v = _mm(h, p["wv"], quant).reshape(T, z["Hkv"], z["Dh"])
    x = x + _mm(_attention(q, k, v), p["wo"], quant)
    h = _rmsnorm(x, p["mlp_norm"], z["eps"])
    if z["E"]:
        ff = _moe_ffn(h, p["w_router"], (blocks["we_gate"], blocks["we_up"],
                                         blocks["we_down"]), layer, z["k"],
                      quant, e_local)
    else:
        ff = _dense_ffn(h, p["w_gate"], p["w_up"], p["w_down"], quant)
    return x + ff


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, final_norm, lm_head, eps, quant):
    return _mm(_rmsnorm(x, final_norm, eps), lm_head, quant)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


def logits(weights: dict, tokens, cfg: dict,
           quant: str | None = None) -> jax.Array:
    """Full forward of one sequence: tokens [T] -> logits [T, V] (float32),
    layer by layer so that one layer's float32 working set is all that is
    live beside the weights. Padding at the end of ``tokens`` is harmless:
    attention is causal."""
    z = sizes(cfg)
    zt = tuple(sorted((k, v) for k, v in z.items() if k != "dtype"))
    x = _embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    e_local = 1
    if z["E"]:          # experts a chip holds, where the tables are split
        table = weights["blocks"]["we_gate"]
        e_local = table.sharding.shard_shape(table.shape)[1]
    for layer in range(z["L"]):
        x = _layer(x, weights["blocks"], jnp.int32(layer), zt, quant,
                   e_local)
    return _head(x, weights["final_norm"], weights["lm_head"], z["eps"],
                 quant)
