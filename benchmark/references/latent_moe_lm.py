"""Plain reference for latent-attention decoders with a shared expert beside
sigmoid-routed experts and leading dense layers (the DeepSeek-V3 / Kimi-K2
layer), handed ONE CHIP'S SHARE of the routed experts.

Straight ``jax.numpy`` in float32 under ``precision=HIGHEST``, one sequence, no
kernels, no cache, no batching, and nothing of the program: the weights are
this module's own, drawn from the seed in the layout the serving engine takes.
Per layer, on ``x`` [T, D] (keys of the configuration file in quotes):

- attention (MLA): ``h = RMSNorm(x)``; ``c_q = RMSNorm(h W_qa)``
  ("q_lora_rank"); ``q = c_q W_qb`` -> heads of (``q_nope``
  "qk_nope_head_dim", ``q_rope`` "qk_rope_head_dim"); ``[c_kv | k_r] = h
  W_kva``; ``c = RMSNorm(c_kv)`` ("kv_lora_rank"); ``k_rope = RoPE(k_r)``, one
  head shared by all query heads; ``q_rope = RoPE(q_rope)``; per head
  ``k_nope = c W_uk^T``, ``v = c W_uv`` ("v_head_dim"); score = (``q_nope .
  k_nope + q_rope . k_rope``) x ``s``, causal softmax, times ``v``;
  concatenate the heads and multiply by ``W_o``. ``s = (nope + rope)^-0.5 x
  m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``. This is the PLAIN form:
  every cached row is up-projected to per-head keys and values; nothing is
  absorbed into the query.
- RoPE is YaRN ("rope_scaling"): inverse frequencies ``theta^(-2i/d)`` blended
  with the same over "factor" by a linear ramp between the two correction
  indices (where a dimension turns "beta_fast" / "beta_slow" times over
  "original_max_position_embeddings"); cos and sin scaled by ``mscale /
  mscale_all_dim``.
- FFN: the first "first_k_dense_replace" layers are SwiGLU of width
  "intermediate_size". The others: ``g = sigmoid(h W_g)`` over all
  ``published.n_routed_experts`` experts; the "num_experts_per_tok" largest of
  ``g + b`` are chosen ("n_group" = "topk_group" = 1: no group step); weights
  = the chosen ``g`` (without ``b``) over their sum, times
  "routed_scaling_factor"; ``y = sum_e w_e SwiGLU_e(h)`` over the chosen
  experts THAT THIS SHARE HOLDS (ids ``share.first_expert`` + [0,
  "n_routed_experts")) ``+ SwiGLU_shared(h)``. What the absent experts would
  add is left out, and the partial result goes on to the next layer.

Departures from the published model, each on purpose:
- the rotary dims are laid out half-split (``rotate_half`` applied directly);
  the source de-interleaves pairs first, a fixed permutation of columns of
  ``W_qb`` / ``W_kva`` that random weights absorb;
- ``kv_b_proj`` is held as its per-head halves ``w_uk`` [H, nope, c] and
  ``w_uv`` [H, c, v] (the same numbers, regrouped);
- weights are random, std 0.02 (``wo`` and the down projections scaled by
  1/sqrt(2 L)); the router's correction bias ``b`` is drawn with std
  ``BIAS_STD``, small: at random router weights the experts' loads are even
  already, which is the state ``noaux_tc`` trains ``b`` to keep, and the
  picked sigmoid scores lie within a few hundredths of each other, so a draw
  of std 0.1 moved the held experts' load by +-50 % from seed to seed
  (PERF.md, PR 26, call 6); norm gains are 1 + 0.05 N(0, 1);
- only the held experts' tables exist: the router keeps its published width.

Only to bound memory at 8,960 tokens beside 11 GB of weights: attention runs
in blocks of query rows, the FFNs in blocks of rows, the experts one at a
time, each sliced out of the stacked tables where it is used.

``quant="fp8"`` is the control of the output check: the same mathematics with
the inputs of every weight product rounded to float8 e4m3 (rows of the
activations and output channels of the weights scaled to the format's range),
the nearest precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
BIAS_STD = 0.001
ATTN_ROWS = 128
FFN_ROWS = 256


def sizes(cfg: dict) -> dict:
    """The shape numbers the reference needs, by their published keys."""
    rs = cfg["rope_scaling"]
    return {
        "L": cfg["num_hidden_layers"], "Ld": cfg["first_k_dense_replace"],
        "D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
        "qr": cfg["q_lora_rank"], "c": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rp": cfg["qk_rope_head_dim"],
        "vd": cfg["v_head_dim"], "F": cfg["intermediate_size"],
        "Fe": cfg["moe_intermediate_size"],
        "Fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        "E": cfg["published"]["n_routed_experts"],
        "Eh": cfg["n_routed_experts"],
        "first": cfg["share"]["first_expert"],
        "k": cfg["num_experts_per_tok"],
        "route_scale": float(cfg["routed_scaling_factor"]),
        "V": cfg["vocab_size"], "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]), "factor": float(rs["factor"]),
        "orig": int(rs["original_max_position_embeddings"]),
        "beta_fast": float(rs["beta_fast"]),
        "beta_slow": float(rs["beta_slow"]), "mscale": float(rs["mscale"]),
        "mscale_all": float(rs["mscale_all_dim"]),
        "dtype": jnp.dtype(cfg["torch_dtype"]),
    }


# -- weights -----------------------------------------------------------------

def init_weights(key: jax.Array, cfg: dict) -> dict:
    """Weights from the seed, made on the device in the served dtype (call
    under ``jax.jit``). Layout: ``dense`` = the leading dense layers and
    ``blocks`` = the sparse ones, each stacked on a leading layer dim."""
    z = sizes(cfg)
    D, H, V, dt = z["D"], z["H"], z["V"], z["dtype"]
    keys = iter(jax.random.split(key, 48))
    s, down = 0.02, 0.02 / math.sqrt(2 * z["L"])

    def w(*shape, scale=s):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dt)

    def gain(*shape):
        return 1.0 + 0.05 * jax.random.normal(next(keys), shape, jnp.float32)

    def attn(n):
        return {"attn_norm": gain(n, D), "wq_a": w(n, D, z["qr"]),
                "q_norm": gain(n, z["qr"]),
                "wq_b": w(n, z["qr"], H * (z["nope"] + z["rp"])),
                "wkv_a": w(n, D, z["c"] + z["rp"]),
                "kv_norm": gain(n, z["c"]),
                "w_uk": w(n, H, z["nope"], z["c"]),
                "w_uv": w(n, H, z["c"], z["vd"]),
                "wo": w(n, H * z["vd"], D, scale=down),
                "mlp_norm": gain(n, D)}

    Ld, Lm = z["Ld"], z["L"] - z["Ld"]
    F, Fe, Fs, Eh = z["F"], z["Fe"], z["Fs"], z["Eh"]
    dense = {**attn(Ld), "w_gate": w(Ld, D, F), "w_up": w(Ld, D, F),
             "w_down": w(Ld, F, D, scale=down)}
    blocks = {**attn(Lm),
              "w_router": jax.random.normal(next(keys), (Lm, D, z["E"]),
                                            jnp.float32) * s,
              "router_bias": jax.random.normal(next(keys), (Lm, z["E"]),
                                               jnp.float32) * BIAS_STD,
              "we_gate": w(Lm, Eh, D, Fe), "we_up": w(Lm, Eh, D, Fe),
              "we_down": w(Lm, Eh, Fe, D, scale=down),
              "ws_gate": w(Lm, D, Fs), "ws_up": w(Lm, D, Fs),
              "ws_down": w(Lm, Fs, D, scale=down)}
    return {"embed": w(V, D), "dense": dense, "blocks": blocks,
            "final_norm": gain(D), "lm_head": w(D, V)}


# -- mathematics ---------------------------------------------------------------

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


def _by_rows(fn, x: jax.Array, rows: int) -> jax.Array:
    """``fn`` over blocks of rows of x [T, .]: bounds the working set."""
    T = x.shape[0]
    R = math.gcd(T, rows)
    out = jax.lax.map(fn, x.reshape(T // R, R, *x.shape[1:]))
    return out.reshape(T, *out.shape[2:])


def yarn_inv_freq(z: dict) -> np.ndarray:
    d, base = z["rp"], z["theta"]
    extra = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    inter = extra / z["factor"]

    def index(turns):        # the dimension that turns ``turns`` times
        return d * math.log(z["orig"] / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(index(z["beta_fast"])), 0)
    high = min(math.ceil(index(z["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _rope(x, z):
    """x [T, ..., d], positions 0..T-1 on the first dim; rotate_half."""
    T, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * yarn_inv_freq(z)
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (half,))
    scale = _mscale(z["factor"], z["mscale"]) \
        / _mscale(z["factor"], z["mscale_all"])
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q_nope, q_rope, k_nope, k_rope, v, scale):
    """Causal, per head. q_* [T, H, .]; k_nope, v [T, H, .]; k_rope [T, rp]
    (one head for all). Query rows in blocks of ``ATTN_ROWS``."""
    T, H, _ = q_nope.shape
    R = math.gcd(T, ATTN_ROWS)
    keys = jnp.arange(T)

    def block(args):
        qn, qr, first = args
        s = (jnp.einsum("rhd,thd->hrt", qn, k_nope, precision=HIGHEST)
             + jnp.einsum("rhe,te->hrt", qr, k_rope, precision=HIGHEST))
        seen = (first + jnp.arange(R))[:, None] >= keys[None, :]
        p = jax.nn.softmax(jnp.where(seen, s * scale, -jnp.inf), axis=-1)
        return jnp.einsum("hrt,thv->rhv", p, v,
                          precision=HIGHEST).reshape(R, -1)

    split = lambda a: a.reshape(T // R, R, *a.shape[1:])     # noqa: E731
    out = jax.lax.map(block, (split(q_nope), split(q_rope),
                              jnp.arange(T // R) * R))
    return out.reshape(T, -1)


def _swiglu(h, wg, wu, wd, quant):
    return _by_rows(lambda x: _mm(jax.nn.silu(_mm(x, wg, quant))
                                  * _mm(x, wu, quant), wd, quant),
                    h, FFN_ROWS)


def route(h, w_router, bias, z):
    """(ids [T, k], weights [T, k]) of the router, as in the docstring."""
    g = jax.nn.sigmoid(jnp.matmul(h, w_router.astype(jnp.float32),
                                  precision=HIGHEST))
    _, ids = jax.lax.top_k(g + bias, z["k"])
    w = jnp.take_along_axis(g, ids, axis=-1)
    return ids, w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * z["route_scale"]


def routed_part(h, p, tables, layer, z, quant):
    """The held experts' part of the routed sum on h [T, D]: every held
    expert on every row, weighted (zero where a row did not choose it).
    ``tables`` = the stacked [Lm, Eh, ., .] gate, up and down tables; one
    expert of one layer is sliced out at a time."""
    ids, w = route(h, p["w_router"], p["router_bias"], z)
    held = z["first"] + jnp.arange(z["Eh"])
    weight = jnp.sum((ids[..., None] == held) * w[..., None], axis=1)

    def one(e, out):
        wg, wu, wd = (jax.lax.dynamic_slice(
            t, (layer, e, 0, 0), (1, 1) + t.shape[2:])[0, 0] for t in tables)
        we = jax.lax.dynamic_slice_in_dim(weight, e, 1, axis=1)
        return out + _swiglu(h, wg, wu, wd, quant) * we

    return jax.lax.fori_loop(0, z["Eh"], one, jnp.zeros_like(h))


def shared_part(h, p, quant):
    return _swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"], quant)


@functools.partial(jax.jit, static_argnames=("z", "quant", "sparse"))
def _layer(x, group, layer, z, quant, sparse):
    """One block on x [T, D] (float32). ``group`` = the stacked params of the
    dense or of the sparse layers, ``layer`` the index within it; ``z`` is
    ``sizes`` as a tuple."""
    z = dict(z)
    p = {n: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
         for n, a in group.items() if not n.startswith("we_")}
    T, H, nope, c = x.shape[0], z["H"], z["nope"], z["c"]
    h = _rmsnorm(x, p["attn_norm"], z["eps"])
    c_q = _rmsnorm(_mm(h, p["wq_a"], quant), p["q_norm"], z["eps"])
    q = _mm(c_q, p["wq_b"], quant).reshape(T, H, nope + z["rp"])
    kv = _mm(h, p["wkv_a"], quant)
    ckv = _rmsnorm(kv[:, :c], p["kv_norm"], z["eps"])
    k_rope = _rope(kv[:, c:], z)
    q_rope = _rope(q[..., nope:], z)
    k_nope = _mm(ckv, p["w_uk"].reshape(H * nope, c).T,
                 quant).reshape(T, H, nope)
    v = _mm(ckv, p["w_uv"].transpose(1, 0, 2).reshape(c, H * z["vd"]),
            quant).reshape(T, H, z["vd"])
    m = _mscale(z["factor"], z["mscale_all"])
    scale = (nope + z["rp"]) ** -0.5 * m * m
    x = x + _mm(_attention(q[..., :nope], q_rope, k_nope, k_rope, v, scale),
                p["wo"], quant)
    h = _rmsnorm(x, p["mlp_norm"], z["eps"])
    if sparse:
        tables = tuple(group[n] for n in ("we_gate", "we_up", "we_down"))
        ff = routed_part(h, p, tables, layer, z, quant) \
            + shared_part(h, p, quant)
    else:
        ff = _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], quant)
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
    layer by layer. Padding at the end of ``tokens`` is harmless: attention
    is causal and every other operation is per row."""
    z = sizes(cfg)
    zt = tuple(sorted((k, v) for k, v in z.items() if k != "dtype"))
    x = _embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    for layer in range(z["L"]):
        sparse = layer >= z["Ld"]
        x = _layer(x, weights["blocks" if sparse else "dense"],
                   jnp.int32(layer - z["Ld"] if sparse else layer), zt,
                   quant, sparse)
    return _head(x, weights["final_norm"], weights["lm_head"], z["eps"],
                 quant)
