"""Plain reference for decoders of doubly gated SHORT-CONVOLUTION layers with a
GQA layer of narrow heads every few layers, leading dense layers and a
bias-selected, sigmoid-routed expert FFN after them (the ``lfm2_moe`` layer),
every layer whole (all routed experts held: the share of one).

Straight ``jax.numpy`` in float32 under ``precision=HIGHEST``, one sequence, no
kernels, no cache, no batching, and nothing of the program: the weights are
this module's own, drawn from the seed in the layout the serving engine takes.
Layer i is of kind ``"layer_types"[i]`` (``conv`` or ``full_attention``) and
carries a dense FFN where ``i < "num_dense_layers"``, experts after. Per
layer, on ``x`` [T, D] (keys of the configuration file in quotes):

    n(x; w) = x / sqrt(mean(x^2) + "norm_eps") * w                  (plain weight)
    u      = n(x; w_op)
    conv   : [B; C; z] = u W_in  (three blocks of "hidden_size", in that order); g = B * z
             y_t = sum_{j < L} k[j] * g_{t - (L - 1) + j}   per channel, L = "conv_L_cache",
             g of negative time zero, no bias ("conv_bias" false): A TOKEN-BY-TOKEN SCAN
             over the last L - 1 rows of g;  m = (C * y) W_out
    full   : q = u W_q ("num_attention_heads" heads of hidden / heads), k, v = u W_k, u W_v
             ("num_key_value_heads"); q <- n(q; w_qn), k <- n(k; w_kn) a head;
             RoPE("rope_parameters.rope_theta"), half-split, on the whole head;
             dense causal softmax(q k^T / sqrt(head)) v;  m = attn W_o      (no bias)
    x     <- x + m;  h = n(x; w_ffn)
    dense  : f = (silu(h W_1) * (h W_3)) W_2                       "intermediate_size"
    sparse : s = sigmoid(h W_r) in float32 over all "num_experts"; T = the
             "num_experts_per_tok" largest of s + b ("use_expert_bias"; b float32);
             w_e = s_e / (sum_T s + 1e-6) * "routed_scaling_factor"  ("norm_topk_prob")
             f = sum_{e in T} w_e E_e(h), E the gated MLP above at "moe_intermediate_size";
             EVERY expert is computed on every row (a dense loop), weight zero
             where a row did not choose it; no shared expert
    x     <- x + f
    logits = n(x; w_out) E^T                       (tied: ``assumed.tie_word_embeddings``)

The weights (the file's ``assumed.weights``), drawn so that every mechanism
MOVES the logits (``STD`` and the constants below):
- every projection of a normed row has std ``D ** -0.5`` (its output of order
  one): the conv's two gates B and C and its input z are N(0, 1) a channel,
  neither gate near zero for most channels, so dropping C or losing the
  carried rows of ``B * z`` changes a term of order one; router logits have
  std about one;
- the conv's taps are N(0, ``STD["conv_w"]`` ^ 2), all three alike: the two
  carried rows weigh as much as the current one;
- the q / k norm weights are ``QK_GAIN`` + 0.1 N(0, 1): q . k / sqrt(head) has
  a standard deviation of about ``QK_GAIN`` ^ 2, so the softmax is peaked, a
  head reads a few keys and not the mean of thousands, and the norms and
  rotary move it; the layer norms' weights are 1 + 0.1 N(0, 1);
- the selection bias b is N(0, ``BIAS_STD`` ^ 2): the fourth and the fifth
  largest of 64 sigmoid scores lie about 0.02 apart, so b changes the chosen
  four for most tokens;
- output projections are scaled so that a mixer and a dense FFN each add
  about half a unit a channel (``logits(...).norms`` reads it) and the four
  chosen experts together an eighth. At the first draw (the experts' down
  tables at 0.0425: half a unit too, ONE expert's output a whole unit) sound
  runs read ``gap_mean`` 0.22-0.23 with 65 % of served tokens not the
  reference's (PERF.md, PR 43): bfloat16 rounding upstream of a 64-wide top-4
  router (the fourth and fifth score 0.02 apart) chooses one expert the
  other way in one token-layer of ten, and one swap moved a row by a sixth
  of what the stream holds. The embedding's std is ``STD["embed"]``, well
  under the branches': it is the head too, and at the layers' scale the
  head's own-token term makes greedy decoding repeat one token (PERF.md Open
  questions 9).
Layout: ``dense`` = the leading layers (all conv) stacked with their dense
FFN; ``blocks["conv"]`` / ``blocks["full"]`` = the later layers of each kind
stacked on their own, each layer's router and bias among them;
``blocks["we_*"]`` = the experts' tables stacked over the later layers.

Only to bound memory at 9,216 tokens beside 10.5 GB of weights: a full layer
runs in blocks of ``ROWS`` query rows against all keys, one KV head's group at
a time, the experts one at a time, and ``logits`` returns the rows of the final
hidden state: indexing it computes the head on those rows alone, a block of
the vocabulary at a time.

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
ROUTE_EPS = 1e-6

# Stds of the seeded matrices that are NOT ``D ** -0.5`` (see the docstring):
# what each output projection is scaled by, the embedding, the taps.
STD = {"embed": 0.01, "w_out": 0.013, "wo": 0.02, "w_down": 0.0077,
       "we_down": 0.0106, "conv_w": 0.5}
QK_GAIN = 1.5                # mean of the q / k norm weights
BIAS_STD = 0.05              # of the router's selection bias
NORM_W = 0.1                 # std of every norm weight around its mean


def sizes(cfg: dict) -> dict:
    """The shape numbers the reference needs, by their published keys."""
    L, nd = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    kinds = tuple(cfg["layer_types"][:L])
    assert len(kinds) == L and set(kinds) <= {"conv", "full_attention"} \
        and not cfg["conv_bias"] and cfg["norm_topk_prob"] \
        and cfg["use_expert_bias"] \
        and cfg["rope_parameters"]["rope_type"] == "default", \
        "the reference has the published lfm2_moe layer only"
    assert set(kinds[:nd]) <= {"conv"}, "the dense layers are conv layers"
    Hq = cfg["num_attention_heads"]
    return {
        "L": L, "nd": nd, "kinds": kinds, "D": cfg["hidden_size"],
        "F": cfg["intermediate_size"], "Hq": Hq,
        "Hkv": cfg["num_key_value_heads"], "Dh": cfg["hidden_size"] // Hq,
        "theta": float(cfg["rope_parameters"]["rope_theta"]),
        "taps": cfg["conv_L_cache"], "Fe": cfg["moe_intermediate_size"],
        "E": cfg["num_experts"], "k": cfg["num_experts_per_tok"],
        "scale": float(cfg["routed_scaling_factor"]),
        "V": cfg["vocab_size"], "eps": float(cfg["norm_eps"]),
        "dtype": jnp.dtype(cfg["torch_dtype"]),
    }


# -- weights -----------------------------------------------------------------

def init_weights(key: jax.Array, cfg: dict) -> dict:
    """Weights from the seed, made on the device in the served dtype (call
    under ``jax.jit``), in the layout of the module docstring. The norm
    weights, the taps, the router and its bias are float32."""
    z = sizes(cfg)
    D, V, F, dt = z["D"], z["V"], z["F"], z["dtype"]
    Hq, Hkv, Dh, E, Fe = z["Hq"], z["Hkv"], z["Dh"], z["E"], z["Fe"]
    later = z["kinds"][z["nd"]:]
    keys = iter(jax.random.split(key, 64))
    unit = D ** -0.5

    def f32(*shape, scale=1.0):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def w(*shape, scale=unit):
        return f32(*shape, scale=scale).astype(dt)

    def gain(*shape, mean=1.0):
        return mean + f32(*shape, scale=NORM_W)

    def layers(n, kind, sparse=True):
        p = {"attn_norm": gain(n, D), "mlp_norm": gain(n, D)}
        if kind == "full":
            p.update(wq=w(n, D, Hq * Dh), wk=w(n, D, Hkv * Dh),
                     wv=w(n, D, Hkv * Dh),
                     wo=w(n, Hq * Dh, D, scale=STD["wo"]),
                     q_norm=gain(n, Dh, mean=QK_GAIN),
                     k_norm=gain(n, Dh, mean=QK_GAIN))
        else:
            p.update(w_in=w(n, D, 3 * D),
                     conv_w=f32(n, z["taps"], D, scale=STD["conv_w"]),
                     w_out=w(n, D, D, scale=STD["w_out"]))
        if not sparse:
            return {**p, "w_gate": w(n, D, F), "w_up": w(n, D, F),
                    "w_down": w(n, F, D, scale=STD["w_down"])}
        return {**p, "w_router": f32(n, D, E, scale=unit),
                "router_bias": f32(n, E, scale=BIAS_STD)}

    blocks = {"conv": layers(later.count("conv"), "conv"),
              "full": layers(later.count("full_attention"), "full")}
    Lp = len(later)
    blocks.update(we_gate=w(Lp, E, D, Fe), we_up=w(Lp, E, D, Fe),
                  we_down=w(Lp, E, Fe, D, scale=STD["we_down"]))
    return {"embed": w(V, D, scale=STD["embed"]), "blocks": blocks,
            "dense": layers(z["nd"], "conv", sparse=False),
            "final_norm": gain(D)}


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
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, positions, theta):
    """x [T, H, d] at ``positions`` [T]; rotate_half over all of d."""
    half = x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(h, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wd, quant)


def route(h, w_router, bias, z):
    """(ids [T, k], weights [T, k]): sigmoid scores over ALL experts in
    float32, the k largest of score + bias, weighed by their scores over
    their sum + 1e-6, times the scaling factor."""
    s = jax.nn.sigmoid(jnp.matmul(h, w_router.astype(jnp.float32),
                                  precision=HIGHEST))
    _, ids = jax.lax.top_k(s + bias, z["k"])
    w = jnp.take_along_axis(s, ids, axis=-1)
    return ids, w / (jnp.sum(w, -1, keepdims=True) + ROUTE_EPS) * z["scale"]


def routed_sum(h, p, tables, layer, z, quant, first=0, held=None):
    """The routed sum on h [R, D]: every expert ``first`` + [0, ``held``)
    (default: all) on every row, weighted (zero where a row did not choose
    it). ``tables`` = the stacked [layers, E, ., .] gate, up and down tables;
    one expert of one layer is sliced out at a time."""
    ids, w = route(h, p["w_router"], p["router_bias"], z)
    weight = jnp.sum((ids[..., None] == jnp.arange(z["E"])) * w[..., None],
                     axis=1)                                    # [R, E]

    def one(e, out):
        wg, wu, wd = (jax.lax.dynamic_slice(
            t, (layer, e, 0, 0), (1, 1) + t.shape[2:])[0, 0] for t in tables)
        we = jax.lax.dynamic_slice_in_dim(weight, e, 1, axis=1)
        return out + _swiglu(h, wg, wu, wd, quant) * we

    held = z["E"] if held is None else held
    return jax.lax.fori_loop(first, first + held, one, jnp.zeros_like(h))


def conv_mixer(u, p, z, quant):
    """The doubly gated short convolution on normed rows u [T, D] -> [T, D]:
    a scan over tokens that carries the last ``taps - 1`` rows of ``B * z``."""
    D, taps = z["D"], z["taps"]
    bcz = _mm(u, p["w_in"], quant)
    g = bcz[:, :D] * bcz[:, 2 * D:]

    def token(before, g_t):
        rows = jnp.concatenate([before, g_t[None]])             # [taps, D]
        return rows[1:], jnp.sum(rows * p["conv_w"], axis=0)

    _, y = jax.lax.scan(token, jnp.zeros((taps - 1, D), jnp.float32), g)
    return _mm(bcz[:, D:2 * D] * y, p["w_out"], quant)


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
    return out.swapaxes(0, 1).reshape(R, Hq * Dh)


def full_attention(u_all, p, z, quant):
    """GQA on the normed rows u_all [T, D] -> [T, D], a block of ``ROWS``
    query rows at a time."""
    T, Hq, Hkv, Dh = u_all.shape[0], z["Hq"], z["Hkv"], z["Dh"]
    at = jnp.arange(T)
    k = _rope(_norm(_mm(u_all, p["wk"], quant).reshape(T, Hkv, Dh),
                    p["k_norm"], z["eps"]), at, z["theta"])
    v = _mm(u_all, p["wv"], quant).reshape(T, Hkv, Dh)
    R = math.gcd(T, ROWS)

    def block(args):
        u, first = args
        q = _rope(_norm(_mm(u, p["wq"], quant).reshape(R, Hq, Dh),
                        p["q_norm"], z["eps"]), first + jnp.arange(R),
                  z["theta"])
        return _mm(_attend(q, k, v, first, Dh ** -0.5), p["wo"], quant)

    out = jax.lax.map(block, (u_all.reshape(T // R, R, -1),
                              jnp.arange(T // R) * R))
    return out.reshape(T, -1)


@functools.partial(jax.jit, static_argnames=("z", "quant", "kind", "sparse"))
def _layer(x, stack, tables, at, layer, z, quant, kind, sparse):
    """One block on x [T, D] (float32): layer ``at`` of its ``stack``, layer
    ``layer`` of the expert ``tables`` (where ``sparse``); ``z`` is ``sizes``
    as a tuple. Returns (x', norms [3]: the residual's and the two terms')."""
    z = dict(z)
    p = {n: jax.lax.dynamic_index_in_dim(a, at, 0, keepdims=False)
         for n, a in stack.items()}
    u = _norm(x, p["attn_norm"], z["eps"])
    m = full_attention(u, p, z, quant) if kind == "full_attention" \
        else conv_mixer(u, p, z, quant)
    y = x + m
    T = x.shape[0]
    R = math.gcd(T, ROWS)

    def ffn(yb):
        h = _norm(yb, p["mlp_norm"], z["eps"])
        if sparse:
            return routed_sum(h, p, tables, layer, z, quant)
        return _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], quant)

    f = jax.lax.map(ffn, y.reshape(T // R, R, -1)).reshape(T, -1)
    norms = jnp.stack([jnp.linalg.norm(t) for t in (x, m, f)])
    return y + f, norms


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, final_norm, embed, eps, quant):
    """The tied head on rows x, a block of the vocabulary at a time."""
    x = _norm(x, final_norm, eps)
    V = embed.shape[0]
    nb = math.gcd(V, VOCAB_BLOCKS)

    def block(i):
        e = jax.lax.dynamic_slice_in_dim(embed, i * (V // nb), V // nb, 0)
        return _mm(x, e.T, quant)

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
                     self.weights["embed"], self.z["eps"], self.quant)

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
    seen = {"conv": 0, "full_attention": 0}
    norms = []
    for layer, kind in enumerate(z["kinds"]):
        if layer < z["nd"]:
            x, n = _layer(x, weights["dense"], None, jnp.int32(layer),
                          jnp.int32(0), zt, quant, kind, False)
        else:
            stack = blocks["conv" if kind == "conv" else "full"]
            x, n = _layer(x, stack, tables, jnp.int32(seen[kind]),
                          jnp.int32(layer - z["nd"]), zt, quant, kind, True)
            seen[kind] += 1
        norms.append(n)
    return Logits(x, weights, z, quant, jnp.stack(norms))
