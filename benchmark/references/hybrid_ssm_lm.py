"""Plain reference for decoders with a Mamba-2 mixer BESIDE attention in every
block (the ``falcon_h1`` layer): both read the same normed rows and their
outputs are summed, under muP-style scalar multipliers.

Straight ``jax.numpy`` in float32 under ``precision=HIGHEST``, one sequence, no
kernels, no cache, no batching, and nothing of the program: the weights are
this module's own, drawn from the seed in the layout the serving engine takes.
Per layer, on ``x`` [T, D] (keys of the configuration file in quotes):

    u      = rmsnorm(x) g                                          ("rms_norm_eps")
    mixer  : zxBCdt = ("ssm_in_multiplier" u) W_in, times the mup vector
             ("ssm_multipliers"[0..4] on the z, x, B, C and dt segments)
             z [T, "mamba_d_ssm"], xBC [T, d_ssm + 2 "mamba_n_groups" "mamba_d_state"], dt [T, "mamba_n_heads"]
             xBC <- silu(causal depthwise conv1d(xBC, "mamba_d_conv" taps) + bias)
             x [T, H, P = "mamba_d_head"], B, C [T, G, N]   (head h uses group h // (H / G))
             dt <- softplus(dt + dt_bias); A = -exp(A_log)  (a head each)
             h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t       A PLAIN SCAN OVER TOKENS
             y_t = h_t C_t + D x_t
             y <- rmsnorm over each of G groups of d_ssm / G (y silu(z)) w   ("mamba_rms_norm", not "mamba_norm_before_gate")
             m = y W_out
    attn   : q, k, v = ("attention_in_multiplier" u) Wq, "key_multiplier" (. Wk), . Wv
             "num_attention_heads" / "num_key_value_heads" of "head_dim"; RoPE("rope_theta") over the whole
             head on q and k; dense causal softmax(q k^T / sqrt(head_dim)) v; a = . Wo
    x     <- x + "ssm_out_multiplier" m + "attention_out_multiplier" a
    x     <- x + "mlp_multipliers"[1] ((silu("mlp_multipliers"[0] (h Wg)) * (h Wu)) Wd),  h = rmsnorm(x) g'
    ends  : x0 = "embedding_multiplier" E[token]; logits = "lm_head_multiplier" (rmsnorm(x) g_f) W_head (untied)

Departures from the published model, each on purpose (the file's ``assumed``):
- the rotary dims are laid out half-split (``rotate_half`` applied directly),
  as the source does; the conv taps are held [tap, channel], tap 3 on the
  current row; ``W_in`` [D, 9248] is held as its three column blocks ``w_z``
  [D, 4096], ``w_xbc`` [D, 5120] and ``w_dt`` [D, 32] (the same product: a
  last dim that is no multiple of 128 makes the TPU hold the stack
  column-major and the decode program re-lay out all of it every dispatch);
- weights are random. Their scales (``STD``) are NOT one number: under the
  published multipliers a std of 0.02 everywhere leaves the attention's term
  at a hundredth of the mixer's, the MLP's at a twentieth and the logits flat
  (std 0.011), so a lost state or a dropped branch would pass the check. Each
  matrix is drawn so that what its multiplier scales is of order one, which
  is what the multipliers are for; ``A_log`` and ``dt_bias`` are drawn so that
  the heads' time constants ``1 / (dt |A|)`` are spread from 2 to 2,000 tokens
  in each norm group (a default Mamba-2 initialisation forgets within a
  dozen), with ``dt`` itself of order one so that what the state holds
  outweighs the skip term ``D x`` in the long-memory heads;
- the recurrent state is float32 (a running sum over the whole context).

Only to bound memory at 2,560 tokens beside 10.5 GB of bfloat16 weights: a
layer's matrices are upcast when the layer runs, and ``logits`` returns the
rows of the final hidden state: indexing it (``logits(...)[rows]``) computes
the head on those rows alone, a block of the vocabulary at a time.

``quant="fp8"`` is the control of the output check: the same mathematics with
the inputs of every weight product rounded to float8 e4m3 (rows of the
activations and output channels of the weights scaled to the format's range),
the nearest precision below the configuration's bfloat16. ``logits(...).norms``
[L, 4] holds the norms of the residual and of each branch's term (mixer,
attention, MLP) in every layer: the ratio of norms ``PERF.md`` reports.
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
VOCAB_BLOCKS = 8

# Stds of the seeded matrices (see the docstring) and the heads' memory.
STD = {"embed": 0.177, "w_in": 0.2, "w_out": 0.044, "wq": 0.1, "wk": 0.3,
       "wv": 0.1, "wo": 0.14, "w_gate": 0.08, "w_up": 0.02, "w_down": 0.35,
       "lm_head": 2.0, "conv_w": 0.3, "conv_b": 0.1}
TAU = (2.0, 2000.0)          # tokens: 1 / (dt0 |A|), log-spaced in a group
DT_BIAS = (-1.0, 1.0)        # uniform; dt0 = softplus(dt_bias): 0.31 .. 1.31


def sizes(cfg: dict) -> dict:
    """The shape numbers and scalars the reference needs, by their keys."""
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    assert H * P == cfg["mamba_d_ssm"] and H % G == 0, (H, P, G)
    assert cfg["mamba_rms_norm"] and not cfg["mamba_norm_before_gate"] \
        and cfg["mamba_conv_bias"] and not cfg["mamba_proj_bias"] \
        and not cfg["attention_bias"] and not cfg["mlp_bias"] \
        and not cfg["tie_word_embeddings"] and cfg["rope_scaling"] is None, \
        "the reference has the published falcon_h1 layer only"
    mup = tuple(float(m) for m in cfg["ssm_multipliers"])
    mlp = tuple(float(m) for m in cfg["mlp_multipliers"])
    return {
        "L": cfg["num_hidden_layers"], "D": cfg["hidden_size"],
        "Hq": cfg["num_attention_heads"], "Hkv": cfg["num_key_value_heads"],
        "Dh": cfg["head_dim"], "F": cfg["intermediate_size"],
        "V": cfg["vocab_size"], "H": H, "P": P, "G": G, "N": N,
        "K": cfg["mamba_d_conv"], "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]), "mup": mup, "mlp": mlp,
        "m_embed": float(cfg["embedding_multiplier"]),
        "m_head": float(cfg["lm_head_multiplier"]),
        "m_attn_in": float(cfg["attention_in_multiplier"]),
        "m_attn_out": float(cfg["attention_out_multiplier"]),
        "m_key": float(cfg["key_multiplier"]),
        "m_ssm_in": float(cfg["ssm_in_multiplier"]),
        "m_ssm_out": float(cfg["ssm_out_multiplier"]),
        "dtype": jnp.dtype(cfg["torch_dtype"]),
    }


def mup_vector(z: dict) -> np.ndarray:
    """The multiplier of every column of ``W_in``'s output: z, x, B, C, dt."""
    d, gn = z["H"] * z["P"], z["G"] * z["N"]
    return np.repeat(np.asarray(z["mup"], np.float32),
                     [d, d, gn, gn, z["H"]])


# -- weights -----------------------------------------------------------------

def init_weights(key: jax.Array, cfg: dict) -> dict:
    """Weights from the seed, made on the device in the served dtype (call
    under ``jax.jit``). ``blocks`` is stacked on a leading layer dim; the
    small per-head and per-channel vectors and the norm gains are float32."""
    z = sizes(cfg)
    L, D, V, dt = z["L"], z["D"], z["V"], z["dtype"]
    H, G, N, K = z["H"], z["G"], z["N"], z["K"]
    d_ssm, d_xbc = H * z["P"], H * z["P"] + 2 * G * N
    qd, kvd, F = z["Hq"] * z["Dh"], z["Hkv"] * z["Dh"], z["F"]
    keys = iter(jax.random.split(key, 32))

    def w(name, *shape, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * STD[name]).astype(dtype)

    def gain(*shape):
        return 1.0 + 0.05 * jax.random.normal(next(keys), shape, jnp.float32)

    # a head's memory: tau log-spaced over the heads of each norm group, so
    # that every group normalises short and long memories together
    hpg = H // G
    tau = TAU[0] * (TAU[1] / TAU[0]) ** (
        (jnp.arange(H) % hpg) / max(hpg - 1, 1))
    dt_bias = jax.random.uniform(next(keys), (L, H), jnp.float32, *DT_BIAS)
    a_log = -jnp.log(tau * jax.nn.softplus(dt_bias))
    blocks = {
        "attn_norm": gain(L, D), "w_z": w("w_in", L, D, d_ssm),
        "w_xbc": w("w_in", L, D, d_xbc), "w_dt": w("w_in", L, D, H),
        "conv_w": w("conv_w", L, K, d_xbc, dtype=jnp.float32),
        "conv_b": w("conv_b", L, d_xbc, dtype=jnp.float32),
        "dt_bias": dt_bias, "A_log": a_log, "D": gain(L, H),
        "ssm_norm": gain(L, d_ssm), "w_out": w("w_out", L, d_ssm, D),
        "wq": w("wq", L, D, qd), "wk": w("wk", L, D, kvd),
        "wv": w("wv", L, D, kvd), "wo": w("wo", L, qd, D),
        "mlp_norm": gain(L, D), "w_gate": w("w_gate", L, D, F),
        "w_up": w("w_up", L, D, F), "w_down": w("w_down", L, F, D)}
    return {"embed": w("embed", V, D), "blocks": blocks,
            "final_norm": gain(D), "lm_head": w("lm_head", D, V)}


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


def _rope(x, theta):
    """x [T, H, d] at positions 0 .. T - 1; rotate_half over all of d."""
    half = x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def mixer(u, p, z, quant):
    """The Mamba-2 mixer on normed rows u [T, D] -> [T, D]: the recurrence is
    a ``lax.scan`` over the T tokens, one [H, P, N] state."""
    T = u.shape[0]
    H, P, G, N, K = z["H"], z["P"], z["G"], z["N"], z["K"]
    d = H * P
    u = u * z["m_ssm_in"]
    zxbcdt = jnp.concatenate([_mm(u, p[n], quant) for n in (
        "w_z", "w_xbc", "w_dt")], axis=-1) * mup_vector(z)
    gate, xbc, dt = jnp.split(zxbcdt, [d, 2 * d + 2 * G * N], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = sum(padded[k:k + T] * p["conv_w"][k] for k in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"])
    x = xbc[:, :d].reshape(T, H, P)
    b, c = (jnp.repeat(a.reshape(T, G, N), H // G, axis=1)   # [T, H, N]
            for a in jnp.split(xbc[:, d:], 2, axis=-1))
    dt = jax.nn.softplus(dt + p["dt_bias"])                  # [T, H]
    decay = jnp.exp(dt * -jnp.exp(p["A_log"]))

    def token(h, t):
        x_t, b_t, c_t, dt_t, decay_t = t
        h = h * decay_t[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)      # [H, P]

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N)), (x, b, c, dt, decay))
    y = (y + p["D"][:, None] * x).reshape(T, d) * jax.nn.silu(gate)
    y = _rmsnorm(y.reshape(T, G, d // G), p["ssm_norm"].reshape(G, d // G),
                 z["eps"]).reshape(T, d)
    return _mm(y, p["w_out"], quant)


def attention(u, p, z, quant):
    """Dense causal GQA on normed rows u [T, D] -> [T, D]."""
    T, Hq, Hkv, Dh = u.shape[0], z["Hq"], z["Hkv"], z["Dh"]
    u = u * z["m_attn_in"]
    q = _rope(_mm(u, p["wq"], quant).reshape(T, Hq, Dh), z["theta"])
    k = _rope((_mm(u, p["wk"], quant) * z["m_key"]).reshape(T, Hkv, Dh),
              z["theta"])
    v = _mm(u, p["wv"], quant).reshape(T, Hkv, Dh)
    seen = jnp.tril(jnp.ones((T, T), jnp.bool_))

    def head(args):
        qh, kh, vh = args                       # [T, G, Dh], [T, Dh], [T, Dh]
        s = jnp.einsum("rgd,td->grt", qh, kh, precision=HIGHEST) * Dh ** -0.5
        w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("grt,td->rgd", w, vh, precision=HIGHEST)

    out = jax.lax.map(head, (q.reshape(T, Hkv, Hq // Hkv, Dh).swapaxes(0, 1),
                             k.swapaxes(0, 1), v.swapaxes(0, 1)))
    return _mm(out.swapaxes(0, 1).reshape(T, Hq * Dh), p["wo"], quant)


@functools.partial(jax.jit, static_argnames=("z", "quant"))
def _layer(x, blocks, layer, z, quant):
    """One block on x [T, D] (float32), ``layer`` its index in the stacked
    ``blocks``; ``z`` is ``sizes`` as a tuple. Returns (x', norms [4]: the
    residual's and the three terms')."""
    z = dict(z)
    p = {n: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
         for n, a in blocks.items()}
    u = _rmsnorm(x, p["attn_norm"], z["eps"])
    m = z["m_ssm_out"] * mixer(u, p, z, quant)
    a = z["m_attn_out"] * attention(u, p, z, quant)
    y = x + m + a
    h = _rmsnorm(y, p["mlp_norm"], z["eps"])
    f = z["mlp"][1] * _mm(
        jax.nn.silu(z["mlp"][0] * _mm(h, p["w_gate"], quant))
        * _mm(h, p["w_up"], quant), p["w_down"], quant)
    norms = jnp.stack([jnp.linalg.norm(t) for t in (x, m, a, f)])
    return y + f, norms


@functools.partial(jax.jit, static_argnames=("eps", "scale", "quant"))
def _head(x, final_norm, lm_head, eps, scale, quant):
    """The head on rows x, a block of the vocabulary at a time (the whole
    table in float32 would be twice the bfloat16 one)."""
    x = _rmsnorm(x, final_norm, eps)
    V = lm_head.shape[1]
    nb = math.gcd(V, VOCAB_BLOCKS)

    def block(i):
        w = jax.lax.dynamic_slice_in_dim(lm_head, i * (V // nb), V // nb, 1)
        return _mm(x, w, quant)

    out = jax.lax.map(block, jnp.arange(nb))                # [nb, R, V / nb]
    return out.swapaxes(0, 1).reshape(x.shape[0], V) * scale


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed(embed, tokens, scale):
    return embed[tokens].astype(jnp.float32) * scale


class Logits:
    """Logits [T, V] of one sequence, held as the final hidden state:
    ``self[rows]`` computes the head on ``rows`` alone, ``np.asarray(self)``
    on all of them. ``norms`` [L, 4]: see ``_layer``."""

    def __init__(self, hidden, weights, z, quant, norms):
        self.hidden, self.weights, self.z, self.quant, self.norms = (
            hidden, weights, z, quant, norms)

    def __getitem__(self, rows):
        return _head(self.hidden[rows], self.weights["final_norm"],
                     self.weights["lm_head"], self.z["eps"], self.z["m_head"],
                     self.quant)

    def __array__(self, dtype=None, copy=None):
        out = np.asarray(self[:])
        return out if dtype is None else out.astype(dtype)


def logits(weights: dict, tokens, cfg: dict,
           quant: str | None = None) -> Logits:
    """Full forward of one sequence: tokens [T] -> logits [T, V] (float32),
    layer by layer (``Logits``: index it for the rows that are wanted).
    Padding at the end of ``tokens`` is harmless: the mixer and the attention
    are causal and every other operation is per row."""
    z = sizes(cfg)
    zt = tuple(sorted((k, v) for k, v in z.items() if k != "dtype"))
    x = _embed(weights["embed"], jnp.asarray(tokens, jnp.int32), z["m_embed"])
    norms = []
    for layer in range(z["L"]):
        x, n = _layer(x, weights["blocks"], jnp.int32(layer), zt, quant)
        norms.append(n)
    return Logits(x, weights, z, quant, jnp.stack(norms))
