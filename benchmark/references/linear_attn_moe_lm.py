"""Plain reference for decoders of gated-delta-rule LINEAR-attention layers
with a gated full-attention layer closing every period, and a softmax-routed
expert FFN with a gated shared expert in every layer (the ``qwen3_next``
layer), handed ONE CHIP'S SHARE of the routed experts.

Straight ``jax.numpy`` in float32 under ``precision=HIGHEST``, one sequence, no
kernels, no cache, no batching, and nothing of the program: the weights are
this module's own, drawn from the seed in the layout the serving engine takes.
Layer i is *full* where ``(i + 1) % "full_attention_interval" == 0`` and
*linear* elsewhere. Per layer, on ``x`` [T, D] (keys of the configuration file
in quotes):

    n(x; w) = x / sqrt(mean(x^2) + "rms_norm_eps") * (1 + w)      (zero-centred)
    u      = n(x; w_attn)
    linear : [q; k; v] = u W_qkv  ("linear_num_key_heads" x "linear_key_head_dim" twice,
             "linear_num_value_heads" x "linear_value_head_dim"); z = u W_z; [b; a] = u W_ba
             [q; k; v] <- silu(causal depthwise conv1d(., "linear_conv_kernel_dim" taps, no bias))
             q, k <- q / sqrt(|q|^2 + 1e-6), likewise k, a head; q <- q / sqrt(key dim)
             beta = sigmoid(b); g = -exp(A_log) softplus(a + dt_bias); alpha = exp(g)   (a value head)
             value head h reads key head h // (value heads / key heads)
             S' = alpha_t S_{t-1}; u_t = beta_t (v_t - S'^T k_t); S_t = S' + k_t u_t^T     A PLAIN SCAN OVER TOKENS
             o_t = S_t^T q_t
             y = [ o / sqrt(mean(o^2) + eps) * w_n * silu(z) ] a head of the value dim; m = y W_out
             (the norm BEFORE the gate; w_n plain, not 1 + w)
    full   : [q; gate] = u W_q a head ("num_attention_heads" x 2 "head_dim"); k, v = u W_k, u W_v
             ("num_key_value_heads" of "head_dim"); q <- n(q; w_q), k <- n(k; w_k) a head;
             RoPE("rope_theta"), half-split, on the first "partial_rotary_factor" x head_dim dims;
             dense causal softmax(q k^T / sqrt(head_dim)) v; m = [attn * sigmoid(gate)] W_o
    x     <- x + m;  h = n(x; w_mlp)
    FFN   : p = softmax(h W_r) in float32 over all ``published.num_experts``; T = the
            "num_experts_per_tok" largest, w_e = p_e / sum_T p  ("norm_topk_prob")
            f = sum_{e in T, held here} w_e E_e(h) + sigmoid(w_s . h) E_shared(h)
            E a SiLU-gated MLP of "moe_intermediate_size" / "shared_expert_intermediate_size"
    x     <- x + f
    logits = n(x; w_f) W_head                                  ("tie_word_embeddings" false)

The share: experts ``share.first_expert`` + [0, "num_experts") are held; what
the others would add is left out, and the partial result goes on.

The weights (the file's ``assumed.weights``), drawn so that every mechanism
MOVES the logits (``STD`` and the constants below):
- every projection of a normed row has std ``D ** -0.5`` (its output of order
  one): q . k / sqrt(head_dim) of the full layers has a standard deviation of
  about one, so the softmax is not flat and RoPE on a quarter of the head
  moves it; ``b``, ``z`` and the gates sit around 0 with std about one, away
  from saturation; router logits have std about one;
- ``alpha``: the value heads' time constants ``1 / (1 - alpha0)`` are
  log-spaced from 1.7 to 2,000 tokens (alpha0 0.4 .. 0.9995) with
  ``dt_bias`` uniform in [-1, 1] and ``A_log = log(-log(alpha0) /
  softplus(dt_bias))``; ``a``'s projection at half the std, so a token moves
  its alpha around alpha0 without flattening the spread. Heads that forget
  within a few dozen tokens alone would let a state lost at a chunk boundary
  pass unseen;
- the zero-centred norm weights ``w`` are N(0, 0.1^2), the gated norm's plain
  weight 1 + 0.05 N(0, 1);
- the output projections are scaled so that a linear layer's mixer and an
  FFN each add about half of what the stream holds when they meet it, a
  full layer's mixer about as much as it holds, and of an FFN's term the
  held experts give about a third (``norms`` of ``logits`` reads it). At
  the first draw (every branch 1.5 times the stream, the held experts'
  down tables 8 times the shared expert's, because a token lands 0.6 of its
  10 experts here at weights near a tenth) ONE expert chosen the other way
  moved a logit by 1-5, and bfloat16 rounding at the router alone (512
  scores, the tenth and the eleventh 0.04 apart) chose one the other way in
  one token of three: sound runs read ``gap_mean`` 0.18 on the chip with 30 %
  of tokens not the reference's (PERF.md, PR 39). The embedding's std is
  under the layers' terms.
Layout: ``blocks["linear"]`` / ``blocks["full"]`` = the layers of each kind
stacked on their own, every layer's FFN leaves (norm, router, shared expert)
among them; ``blocks["we_*"]`` = the held experts' tables stacked over all
layers. ``W_qkvz`` / ``W_ba`` are held as lane-aligned column blocks ``w_qkv``
[D, 8192], ``w_z`` [D, 4096], ``w_ba`` [D, 64] (the checkpoint interleaves
them by key head: a fixed permutation that seeded weights absorb).

Only to bound memory at 5,120 tokens: a full layer runs in blocks of ``ROWS``
query rows against all keys, one KV head's group at a time, the experts one at
a time, and ``logits`` returns the rows of the final hidden state: indexing it
computes the head on those rows alone, a block of the vocabulary at a time.

``quant="fp8"`` is the control of the output check: the same mathematics with
the inputs of every weight product rounded to float8 e4m3 (rows of the
activations and output channels of the weights scaled to the format's range),
the nearest precision below the configuration's bfloat16. ``logits(...).norms``
[L, 3] holds the norms of the residual and of each branch's term (mixer, FFN).
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
ROWS = 256
VOCAB_BLOCKS = 8
L2_EPS = 1e-6

# Stds of the seeded matrices that are NOT ``D ** -0.5`` (see the docstring):
# what each output projection is scaled by, the embedding and the head.
STD = {"embed": 0.3, "w_out": 0.0042, "wo": 0.0525, "ws_down": 0.0175,
       "we_down": 0.028, "lm_head": 0.044, "conv_w": 0.5}
TAU = (1.0 / 0.6, 2000.0)    # tokens: 1 / (1 - alpha0), log-spaced over heads
DT_BIAS = (-1.0, 1.0)        # uniform; softplus(dt_bias): 0.31 .. 1.31
NORM_W = 0.1                 # std of the zero-centred norm weights


def sizes(cfg: dict) -> dict:
    """The shape numbers the reference needs, by their published keys."""
    L, every = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    assert L % every == 0 and cfg["decoder_sparse_step"] == 1 \
        and not cfg["mlp_only_layers"] and not cfg["tie_word_embeddings"] \
        and cfg["rope_scaling"] is None and cfg["norm_topk_prob"] \
        and cfg["hidden_act"] == "silu" and not cfg["use_sliding_window"], \
        "the reference has the published qwen3_next layer only"
    rot = int(cfg["partial_rotary_factor"] * cfg["head_dim"])
    return {
        "L": L, "every": every, "D": cfg["hidden_size"],
        "Hq": cfg["num_attention_heads"], "Hkv": cfg["num_key_value_heads"],
        "Dh": cfg["head_dim"], "rot": rot - rot % 2,
        "theta": float(cfg["rope_theta"]),
        "Hk": cfg["linear_num_key_heads"], "H": cfg["linear_num_value_heads"],
        "K": cfg["linear_key_head_dim"], "Vd": cfg["linear_value_head_dim"],
        "taps": cfg["linear_conv_kernel_dim"],
        "Fe": cfg["moe_intermediate_size"],
        "Fs": cfg["shared_expert_intermediate_size"],
        "E": cfg["published"]["num_experts"], "Eh": cfg["num_experts"],
        "first": cfg["share"]["first_expert"],
        "k": cfg["num_experts_per_tok"], "V": cfg["vocab_size"],
        "eps": float(cfg["rms_norm_eps"]),
        "dtype": jnp.dtype(cfg["torch_dtype"]),
    }


def kinds(z: dict) -> tuple:
    return tuple("full" if (i + 1) % z["every"] == 0 else "linear"
                 for i in range(z["L"]))


# -- weights -----------------------------------------------------------------

def init_weights(key: jax.Array, cfg: dict) -> dict:
    """Weights from the seed, made on the device in the served dtype (call
    under ``jax.jit``), in the layout of the module docstring. The small
    per-head and per-channel vectors, the norm weights and the router are
    float32."""
    z = sizes(cfg)
    L, D, V, dt = z["L"], z["D"], z["V"], z["dtype"]
    Hq, Hkv, Dh, H, Hk = z["Hq"], z["Hkv"], z["Dh"], z["H"], z["Hk"]
    d_qkv, d_lin = 2 * Hk * z["K"] + H * z["Vd"], H * z["Vd"]
    keys = iter(jax.random.split(key, 64))
    unit = D ** -0.5

    def f32(*shape, scale=1.0):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def w(*shape, scale=unit):
        return f32(*shape, scale=scale).astype(dt)

    def layers(n, kind):
        p = {"attn_norm": f32(n, D, scale=NORM_W),
             "mlp_norm": f32(n, D, scale=NORM_W),
             "w_router": f32(n, D, z["E"], scale=unit),
             "ws_gate": w(n, D, z["Fs"]), "ws_up": w(n, D, z["Fs"]),
             "ws_down": w(n, z["Fs"], D, scale=STD["ws_down"]),
             "w_shared_gate": f32(n, D, scale=unit)}
        if kind == "full":
            return {**p, "wq": w(n, D, Hq * 2 * Dh), "wk": w(n, D, Hkv * Dh),
                    "wv": w(n, D, Hkv * Dh),
                    "wo": w(n, Hq * Dh, D, scale=STD["wo"]),
                    "q_norm": f32(n, Dh, scale=NORM_W),
                    "k_norm": f32(n, Dh, scale=NORM_W)}
        # a head's memory: tau log-spaced over the value heads of a layer
        tau = TAU[0] * (TAU[1] / TAU[0]) ** (jnp.arange(H) / max(H - 1, 1))
        dt_bias = jax.random.uniform(next(keys), (n, H), jnp.float32,
                                     *DT_BIAS)
        a_log = jnp.log(-jnp.log1p(-1.0 / tau) / jax.nn.softplus(dt_bias))
        # [b; a]: b at the unit std, a at half of it
        ba = f32(n, D, 2 * H, scale=unit) * jnp.repeat(
            jnp.asarray([1.0, 0.5], jnp.float32), H)
        return {**p, "w_qkv": w(n, D, d_qkv), "w_z": w(n, D, d_lin),
                "w_ba": ba.astype(dt),
                "conv_w": f32(n, z["taps"], d_qkv, scale=STD["conv_w"]),
                "A_log": a_log, "dt_bias": dt_bias,
                "gdn_norm": 1.0 + 0.05 * f32(n, z["Vd"]),
                "w_out": w(n, d_lin, D, scale=STD["w_out"])}

    ks = kinds(z)
    blocks = {kind: layers(ks.count(kind), kind)
              for kind in ("linear", "full")}
    Eh, Fe = z["Eh"], z["Fe"]
    blocks.update(we_gate=w(L, Eh, D, Fe), we_up=w(L, Eh, D, Fe),
                  we_down=w(L, Eh, Fe, D, scale=STD["we_down"]))
    return {"embed": w(V, D, scale=STD["embed"]), "blocks": blocks,
            "final_norm": f32(D, scale=NORM_W),
            "lm_head": w(D, V, scale=STD["lm_head"])}


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


def _norm(x, w, eps):
    """The zero-centred RMS norm ``x_hat * (1 + w)``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, positions, theta, rot):
    """x [T, H, d] at ``positions`` [T]: rotate_half over the first ``rot``
    dims, the rest unchanged."""
    half = rot // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           -1)


def _swiglu(h, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wd, quant)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def route(h, w_router, z):
    """(ids [T, k], weights [T, k]): softmax over ALL experts in float32, the
    k largest, their probabilities over their sum."""
    p = jax.nn.softmax(jnp.matmul(h, w_router.astype(jnp.float32),
                                  precision=HIGHEST), axis=-1)
    top, ids = jax.lax.top_k(p, z["k"])
    return ids, top / jnp.sum(top, -1, keepdims=True)


def routed_part(h, w_router, tables, layer, z, quant):
    """The held experts' part of the routed sum on h [R, D]: every held
    expert on every row, weighted (zero where a row did not choose it).
    ``tables`` = the stacked [layers, Eh, ., .] gate, up and down tables; one
    expert of one layer is sliced out at a time."""
    ids, w = route(h, w_router, z)
    held = z["first"] + jnp.arange(z["Eh"])
    weight = jnp.sum((ids[..., None] == held) * w[..., None], axis=1)

    def one(e, out):
        wg, wu, wd = (jax.lax.dynamic_slice(
            t, (layer, e, 0, 0), (1, 1) + t.shape[2:])[0, 0] for t in tables)
        we = jax.lax.dynamic_slice_in_dim(weight, e, 1, axis=1)
        return out + _swiglu(h, wg, wu, wd, quant) * we

    return jax.lax.fori_loop(0, z["Eh"], one, jnp.zeros_like(h))


def ffn(h, p, tables, layer, z, quant):
    """The held experts' part plus the gated shared expert, on h [R, D]."""
    gate = jax.nn.sigmoid(jnp.sum(h * p["w_shared_gate"], -1, keepdims=True))
    shared = _swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"], quant)
    return routed_part(h, p["w_router"], tables, layer, z, quant) \
        + gate * shared


def delta_rule(q, k, v, alpha, beta):
    """The gated delta rule token by token: q, k [T, H, K], v [T, H, Vd],
    alpha, beta [T, H] -> o [T, H, Vd]; one [H, K, Vd] state from zero."""
    def token(s, t):
        q_t, k_t, v_t, a_t, b_t = t
        s = s * a_t[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t,
                                             precision=HIGHEST))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=HIGHEST)

    s0 = jnp.zeros((v.shape[1], k.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(token, s0, (q, k, v, alpha, beta))[1]


def linear_mixer(u, p, z, quant):
    """The gated-delta-rule mixer on normed rows u [T, D] -> [T, D]."""
    T = u.shape[0]
    H, Hk, K, Vd, taps = z["H"], z["Hk"], z["K"], z["Vd"], z["taps"]
    qkv = _mm(u, p["w_qkv"], quant)
    gate = _mm(u, p["w_z"], quant)
    ba = _mm(u, p["w_ba"], quant)
    padded = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1])), qkv])
    qkv = jax.nn.silu(sum(padded[t:t + T] * p["conv_w"][t]
                          for t in range(taps)))
    q, k, v = jnp.split(qkv, [Hk * K, 2 * Hk * K], axis=-1)
    rep = lambda a: jnp.repeat(a, H // Hk, axis=1)          # noqa: E731
    q = rep(_l2norm(q.reshape(T, Hk, K)) * K ** -0.5)
    k = rep(_l2norm(k.reshape(T, Hk, K)))
    beta = jax.nn.sigmoid(ba[:, :H])
    alpha = jnp.exp(-jnp.exp(p["A_log"])
                    * jax.nn.softplus(ba[:, H:] + p["dt_bias"]))
    o = delta_rule(q, k, v.reshape(T, H, Vd), alpha, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + z["eps"]) \
        * p["gdn_norm"]
    return _mm(o.reshape(T, H * Vd) * jax.nn.silu(gate), p["w_out"], quant)


def _attend(q, k, v, first, scale):
    """A block of query rows [R, Hq, Dh] at positions ``first ..`` against
    all keys k, v [T, Hkv, Dh], dense causal mask. One KV head's group of
    query heads at a time."""
    R, Hq, Dh = q.shape
    T, Hkv, _ = v.shape
    G = Hq // Hkv
    seen = jnp.arange(T)[None, :] <= (first + jnp.arange(R))[:, None]

    def head(args):
        qh, kh, vh = args                       # [R, G, Dh], [T, Dh], [T, Dh]
        s = jnp.einsum("rgd,td->grt", qh, kh, precision=HIGHEST) * scale
        w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("grt,td->rgd", w, vh, precision=HIGHEST)

    out = jax.lax.map(head, (q.reshape(R, Hkv, G, Dh).swapaxes(0, 1),
                             k.swapaxes(0, 1), v.swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(R, Hq, Dh)


def gated_attention(x, p, z, quant):
    """The gated full attention on the residual rows x [T, D] -> [T, D], a
    block of ``ROWS`` query rows at a time."""
    T, Hq, Hkv, Dh = x.shape[0], z["Hq"], z["Hkv"], z["Dh"]
    u_all = _norm(x, p["attn_norm"], z["eps"])
    at = jnp.arange(T)
    k = _rope(_norm(_mm(u_all, p["wk"], quant).reshape(T, Hkv, Dh),
                    p["k_norm"], z["eps"]), at, z["theta"], z["rot"])
    v = _mm(u_all, p["wv"], quant).reshape(T, Hkv, Dh)
    R = math.gcd(T, ROWS)

    def block(args):
        xb, first = args
        u = _norm(xb, p["attn_norm"], z["eps"])
        qg = _mm(u, p["wq"], quant).reshape(R, Hq, 2, Dh)
        q = _rope(_norm(qg[:, :, 0], p["q_norm"], z["eps"]),
                  first + jnp.arange(R), z["theta"], z["rot"])
        a = _attend(q, k, v, first, Dh ** -0.5) * jax.nn.sigmoid(qg[:, :, 1])
        return _mm(a.reshape(R, Hq * Dh), p["wo"], quant)

    out = jax.lax.map(block, (x.reshape(T // R, R, -1),
                              jnp.arange(T // R) * R))
    return out.reshape(T, -1)


@functools.partial(jax.jit, static_argnames=("z", "quant", "kind"))
def _layer(x, stack, tables, at, layer, z, quant, kind):
    """One block on x [T, D] (float32): layer ``at`` of its kind's ``stack``,
    layer ``layer`` of the expert ``tables``; ``z`` is ``sizes`` as a tuple.
    Returns (x', norms [3]: the residual's and the two terms')."""
    z = dict(z)
    p = {n: jax.lax.dynamic_index_in_dim(a, at, 0, keepdims=False)
         for n, a in stack.items()}
    if kind == "full":
        m = gated_attention(x, p, z, quant)
    else:
        m = linear_mixer(_norm(x, p["attn_norm"], z["eps"]), p, z, quant)
    y = x + m
    T = x.shape[0]
    R = math.gcd(T, ROWS)
    f = jax.lax.map(
        lambda yb: ffn(_norm(yb, p["mlp_norm"], z["eps"]), p, tables, layer,
                       z, quant), y.reshape(T // R, R, -1)).reshape(T, -1)
    norms = jnp.stack([jnp.linalg.norm(t) for t in (x, m, f)])
    return y + f, norms


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, final_norm, lm_head, eps, quant):
    """The head on rows x, a block of the vocabulary at a time."""
    x = _norm(x, final_norm, eps)
    V = lm_head.shape[1]
    nb = math.gcd(V, VOCAB_BLOCKS)

    def block(i):
        w = jax.lax.dynamic_slice_in_dim(lm_head, i * (V // nb), V // nb, 1)
        return _mm(x, w, quant)

    out = jax.lax.map(block, jnp.arange(nb))                # [nb, R, V / nb]
    return out.swapaxes(0, 1).reshape(x.shape[0], V)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


class Logits:
    """Logits [T, V] of one sequence, held as the final hidden state:
    ``self[rows]`` computes the head on ``rows`` alone, ``np.asarray(self)``
    on all of them. ``norms`` [L, 3]: see ``_layer``."""

    def __init__(self, hidden, weights, z, quant, norms):
        self.hidden, self.weights, self.z, self.quant, self.norms = (
            hidden, weights, z, quant, norms)

    def __getitem__(self, rows):
        return _head(self.hidden[rows], self.weights["final_norm"],
                     self.weights["lm_head"], self.z["eps"], self.quant)

    def __array__(self, dtype=None, copy=None):
        out = np.asarray(self[:])
        return out if dtype is None else out.astype(dtype)


def logits(weights: dict, tokens, cfg: dict,
           quant: str | None = None) -> Logits:
    """Full forward of one sequence: tokens [T] -> logits [T, V] (float32),
    layer by layer (``Logits``: index it for the rows that are wanted).
    Padding at the end of ``tokens`` is harmless: both mixers are causal and
    every other operation is per row."""
    z = sizes(cfg)
    zt = tuple(sorted((k, v) for k, v in z.items() if k != "dtype"))
    x = _embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    blocks = weights["blocks"]
    tables = tuple(blocks[n] for n in ("we_gate", "we_up", "we_down"))
    seen = {"linear": 0, "full": 0}
    norms = []
    for layer, kind in enumerate(kinds(z)):
        x, n = _layer(x, blocks[kind], tables, jnp.int32(seen[kind]),
                      jnp.int32(layer), zt, quant, kind)
        seen[kind] += 1
        norms.append(n)
    return Logits(x, weights, z, quant, jnp.stack(norms))
