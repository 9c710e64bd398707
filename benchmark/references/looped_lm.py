"""Plain reference for LOOPED dense decoders: one stack of layers that every
token walks ``total_ut_steps`` times over the same weights, sandwich norms, a
norm and an exit gate at every walk's end (the ``ouro`` layer).

Straight ``jax.numpy`` in float32 under ``precision=HIGHEST``, one sequence, no
kernels, no cache, no batching, and nothing of the program: the weights are
this module's own, drawn from the seed in the layout the serving engine takes.
On ``x`` [T, D] (keys of the configuration file in quotes):

    n(x; w) = x / sqrt(mean(x^2) + "rms_norm_eps") * w              (plain weight)
    x = E[tokens]                                                    (no scale)
    for t in 0 .. "total_ut_steps" - 1:                              (a WALK)
      for l in 0 .. "num_hidden_layers" - 1:                         (the SAME weights every walk)
        u = n(x; w1_l)
        q, k, v = u W_q, u W_k, u W_v   ("num_attention_heads" = "num_key_value_heads" heads of
                  "head_dim"; no bias, no q / k norm); RoPE("rope_theta"), half-split, on the
                  whole head, at the token's position (the same in every walk);
        a = causal softmax(q k^T / sqrt(head)) v over THIS walk's k, v of the whole sequence
            (a full forward of walk t over its own keys is exactly a cache entry a (walk, layer));
        x = x + n(a W_o; w2_l)                                       (the sandwich's second slice)
        x = x + n((silu(n(x; w3_l) W_gate) * (n(x; w3_l) W_up)) W_down; w4_l)
      x = n(x; w_out);  h_t = x  (the next walk's input);  g_t = h_t . w_g + b_g
    lam_t = sigmoid(g_t) in float32;  p_t = lam_t prod_{j<t} (1 - lam_j) for t < last,
    p_last = prod_{j<last} (1 - lam_j);  c_t = sum_{j<=t} p_j;
    s = the first t with c_t >= "early_exit_threshold", else the last walk
    logits = h_s W_head                                              (untied)

At the published threshold 1.0, ``c_t`` reaches 1.0 before the last walk only
where a gate saturates in float32; ``Logits`` ASSERTS that the last walk was
picked on every row it is asked for, unless ``logits(..., early_ok=True)``
(the one test that draws a saturating gate).

The weights (the file's ``assumed.weights``), drawn so that every mechanism
MOVES the logits (the constants below):
- the embedding has unit rows; every projection of a normed row has std
  ``fan_in ** -0.5`` (its output of order one), ``W_q`` / ``W_k``
  ``QK_GAIN`` times that: q . k / sqrt(head) has a standard deviation near
  ``QK_GAIN`` ^ 2, so a head reads a few keys and not the mean of hundreds,
  and WHICH plane a walk reads moves its output;
- the input norms' weights ``w1`` / ``w3`` are 1 + ``NORM_W`` N(0, 1); the
  OUTPUT norms' ``w2`` / ``w4`` are ``OUT_GAIN`` (1 + ``OUT_SPREAD`` N(0, 1)):
  a branch adds about a third of a unit a channel whatever its projection's
  scale (which is of order one: dropping the norm triples the branch), with
  a spread over channels that is no part of the projection; 96 branches take
  a unit stream to about 3.5 by a walk's end;
- ``w_out`` is 1 + ``OUT_SPREAD`` N(0, 1): every walk starts from unit rows
  again, weighed a channel; without it the next walk's branches are a third
  of what they should be beside the stream;
- the gate is N(0, ``GATE_STD`` ^ 2) with bias 0: logits of about 0.9 on
  unit-RMS rows, ``lam`` between 0.2 and 0.8, never saturated;
- the head has std ``D ** -0.5``: logits of std about one.

``quant="fp8"`` is the control of the output check: the same mathematics with
the inputs of every weight product rounded to float8 e4m3 (rows of the
activations and output channels of the weights scaled to the format's range),
the nearest precision below the configuration's bfloat16. ``quant="bf16"``
rounds the activations entering every weight product to bfloat16 (the weights
are bfloat16 already): what the program's own precision costs, for finding the
draw's scales on the CPU before the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0

QK_GAIN = 1.5                # W_q and W_k over the unit scale
NORM_W = 0.1                 # std of an input norm's weight around 1
OUT_GAIN = 0.35              # mean of an output norm's weight
OUT_SPREAD = 0.3             # relative std of the output norms' and w_out
GATE_STD = 0.02              # of the exit gate's weight; its bias is 0


def sizes(cfg: dict) -> dict:
    """The shape numbers the reference needs, by their published keys."""
    L = cfg["num_hidden_layers"]
    assert cfg["model_type"] == "ouro" and cfg["hidden_act"] == "silu" \
        and not cfg["tie_word_embeddings"] and cfg["rope_scaling"] is None \
        and cfg["sliding_window"] is None and not cfg["use_sliding_window"] \
        and set(cfg["layer_types"][:L]) == {"full_attention"}, \
        "the reference has the published ouro layer only"
    return {
        "L": L, "walks": cfg["total_ut_steps"], "D": cfg["hidden_size"],
        "F": cfg["intermediate_size"], "Hq": cfg["num_attention_heads"],
        "Hkv": cfg["num_key_value_heads"], "Dh": cfg["head_dim"],
        "theta": float(cfg["rope_theta"]), "V": cfg["vocab_size"],
        "eps": float(cfg["rms_norm_eps"]),
        "threshold": float(cfg["early_exit_threshold"]),
        "dtype": jnp.dtype(cfg["torch_dtype"]),
    }


# -- weights -----------------------------------------------------------------

def init_weights(key: jax.Array, cfg: dict) -> dict:
    """Weights from the seed, made on the device in the served dtype (call
    under ``jax.jit``), in the layout the serving engine takes: ONE stack of
    ``num_hidden_layers`` whatever the walks. The norms' weights and the exit
    gate are float32."""
    z = sizes(cfg)
    L, D, F, V, dt = z["L"], z["D"], z["F"], z["V"], z["dtype"]
    qd, kvd = z["Hq"] * z["Dh"], z["Hkv"] * z["Dh"]
    keys = iter(jax.random.split(key, 20))

    def f32(*shape, scale=1.0):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def w(*shape, scale):
        return f32(*shape, scale=scale).astype(dt)

    def gain(*shape, mean=1.0, spread=NORM_W):
        return mean * (1.0 + f32(*shape, scale=spread))

    unit = D ** -0.5
    blocks = {
        "attn_norm": gain(L, D), "mlp_norm": gain(L, D),
        "attn_out_norm": gain(L, D, mean=OUT_GAIN, spread=OUT_SPREAD),
        "mlp_out_norm": gain(L, D, mean=OUT_GAIN, spread=OUT_SPREAD),
        "wq": w(L, D, qd, scale=QK_GAIN * unit),
        "wk": w(L, D, kvd, scale=QK_GAIN * unit),
        "wv": w(L, D, kvd, scale=unit), "wo": w(L, qd, D, scale=qd ** -0.5),
        "w_gate": w(L, D, F, scale=unit), "w_up": w(L, D, F, scale=unit),
        "w_down": w(L, F, D, scale=F ** -0.5)}
    return {"embed": w(V, D, scale=1.0), "blocks": blocks,
            "final_norm": gain(D, spread=OUT_SPREAD),
            "exit_gate": f32(D, scale=GATE_STD),
            "exit_bias": jnp.zeros((), jnp.float32),
            "lm_head": w(D, V, scale=unit)}


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
    elif quant == "bf16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif quant is not None:
        raise ValueError(f"unknown precision {quant!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, H, d] at positions 0 .. T - 1; rotate_half over all of d."""
    T, half = x.shape[0], x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(u, p, z, quant):
    """Causal attention on the normed rows u [T, D] of ONE walk over that
    walk's own keys and values -> [T, D]."""
    T, Hq, Hkv, Dh = u.shape[0], z["Hq"], z["Hkv"], z["Dh"]
    q = _rope(_mm(u, p["wq"], quant).reshape(T, Hq, Dh), z["theta"])
    k = _rope(_mm(u, p["wk"], quant).reshape(T, Hkv, Dh), z["theta"])
    v = _mm(u, p["wv"], quant).reshape(T, Hkv, Dh)
    q = q.reshape(T, Hkv, Hq // Hkv, Dh)
    s = jnp.einsum("thgd,shd->hgts", q, k, precision=HIGHEST) * Dh ** -0.5
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    a = jnp.einsum("hgts,shd->thgd", w, v, precision=HIGHEST)
    return _mm(a.reshape(T, Hq * Dh), p["wo"], quant)


def ffn(h, p, quant):
    return _mm(jax.nn.silu(_mm(h, p["w_gate"], quant))
               * _mm(h, p["w_up"], quant), p["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("z", "quant"))
def _layer(x, stack, at, z, quant):
    """One block on x [T, D] (float32): layer ``at`` of the ``stack``; ``z``
    is ``sizes`` as a tuple. Returns (x', norms [3]: the residual's and the
    two terms')."""
    z = dict(z)
    p = {n: jax.lax.dynamic_index_in_dim(a, at, 0, keepdims=False)
         for n, a in stack.items()}
    a = _norm(attention(_norm(x, p["attn_norm"], z["eps"]), p, z, quant),
              p["attn_out_norm"], z["eps"])
    y = x + a
    f = _norm(ffn(_norm(y, p["mlp_norm"], z["eps"]), p, quant),
              p["mlp_out_norm"], z["eps"])
    return y + f, jnp.stack([jnp.linalg.norm(t) for t in (x, a, f)])


@functools.partial(jax.jit, static_argnames=("eps",))
def _walk_end(x, final_norm, gate, bias, eps):
    """(h_t [T, D], g_t [T]) of the stream at a walk's end."""
    h = _norm(x, final_norm, eps)
    return h, jnp.sum(h * gate, axis=-1) + bias


@functools.partial(jax.jit, static_argnames=("threshold",))
def exit_walk(g, threshold):
    """The exit walk s [T] of gate logits g [walks, T] (float32), by the rule
    of the module docstring."""
    lam = jax.nn.sigmoid(g.astype(jnp.float32))
    left = jnp.concatenate([jnp.ones_like(lam[:1]),
                            jnp.cumprod(1.0 - lam[:-1], axis=0)])
    p = jnp.concatenate([lam[:-1] * left[:-1], left[-1:]])
    c = jnp.cumsum(p, axis=0)
    last = g.shape[0] - 1
    hit = (c >= jnp.float32(threshold)).at[last].set(True)
    return jnp.argmax(hit, axis=0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(h, lm_head, quant):
    return _mm(h, lm_head, quant)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


class Logits:
    """Logits [T, V] of one sequence, held as every walk's normed rows
    ``hidden`` [walks, T, D] and the exit walk ``picked`` [T]: ``self[rows]``
    computes the head on the picked walk's ``rows`` alone, ``np.asarray(self)``
    on all of them. ``norms`` [walks x L, 3]: see ``_layer``."""

    def __init__(self, hidden, picked, weights, quant, norms, early_ok):
        self.hidden, self.picked, self.weights, self.quant, self.norms = (
            hidden, picked, weights, quant, norms)
        self.early_ok = early_ok

    def __getitem__(self, rows):
        s = np.asarray(self.picked)[rows]
        last = self.hidden.shape[0] - 1
        assert self.early_ok or np.all(s == last), (
            f"the exit rule picked a walk before the last on "
            f"{int(np.sum(s != last))} checked rows: a gate saturated")
        at = np.arange(self.hidden.shape[1])[rows]
        return _head(self.hidden[s, at], self.weights["lm_head"], self.quant)

    def __array__(self, dtype=None, copy=None):
        out = np.asarray(self[:])
        return out if dtype is None else out.astype(dtype)


def logits(weights: dict, tokens, cfg: dict, quant: str | None = None,
           early_ok: bool = False) -> Logits:
    """Full forward of one sequence: tokens [T] -> logits [T, V] (float32),
    walk by walk and layer by layer (``Logits``: index it for the rows that
    are wanted). Padding at the end of ``tokens`` is harmless: attention is
    causal and every other operation is per row."""
    z = sizes(cfg)
    zt = tuple(sorted((k, v) for k, v in z.items() if k != "dtype"))
    x = _embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    hidden, gates, norms = [], [], []
    for _ in range(z["walks"]):
        for layer in range(z["L"]):
            x, n = _layer(x, weights["blocks"], jnp.int32(layer), zt, quant)
            norms.append(n)
        x, g = _walk_end(x, weights["final_norm"], weights["exit_gate"],
                         weights["exit_bias"], z["eps"])
        hidden.append(x)
        gates.append(g)
    picked = exit_walk(jnp.stack(gates), z["threshold"])
    return Logits(jnp.stack(hidden), picked, weights, quant,
                  jnp.stack(norms), early_ok)
