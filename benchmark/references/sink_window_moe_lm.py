"""Plain reference for decoders that mix sliding-window layers with a learned
attention sink and full layers of ANOTHER SHAPE, behind a leading dense layer,
with bias-selected sigmoid-routed experts (the ``mimo_v2_flash`` layer), handed
ONE CHIP'S SHARE of the routed experts.

Straight ``jax.numpy`` in float32 under ``precision=HIGHEST``, one sequence, no
kernels, no cache, no batching, and nothing of the program: the weights are
this module's own, drawn from the seed in the layout the serving engine takes.
Layer i is *full* where "hybrid_layer_pattern"[i] is 0 and *window* where it is
1; its FFN is dense where "moe_layer_freq"[i] is 0 and sparse elsewhere. Per
layer, on ``x`` [T, D] (keys of the configuration file in quotes):

    h     = RMS(x) = x / sqrt(mean(x^2) + "layernorm_epsilon") * g
    q     = h Wq    "num_attention_heads" of "head_dim" (192)
    k     = h Wk    full: "num_key_value_heads" (4); window: "swa_num_key_value_heads" (8)
    v     = "attention_value_scale" * (h Wv)              of "v_head_dim" (128)
    q, k <- RoPE over the first int("partial_rotary_factor" * head_dim) = 64
            dims, half-split, at "rope_theta" (full) / "swa_rope_theta"
            (window); the other 128 dims pass through
    a_tj  = q_t k_j / sqrt(head_dim); full: every j <= t; window: t -
            "sliding_window" < j <= t
    window ("add_swa_attention_sink_bias"): one more logit s_h a query head
            joins the softmax and is DROPPED after it:
            p = softmax([a_t., s_h])[:-1]        (rows sum to less than one)
    x     = x + (p v).reshape(T, Hq * 128) Wo
    h     = RMS(x)
    dense : f = (silu(h Wg) * (h Wu)) Wd                  "intermediate_size"
    sparse: s = sigmoid(h Wr) over all ``published.n_routed_experts``; T = the
            "num_experts_per_tok" largest of s + b (b = the ``noaux_tc``
            selection bias; "n_group" 1: no group limit); w_e = s_e / sum_T s
            ("norm_topk_prob"; no scaling factor, no shared expert)
            f = sum_{e in T, held here} w_e E_e(h),  E of "moe_intermediate_size"
    x     = x + f
    logits = RMS_f(x) W_head                              ("tie_word_embeddings" false)

The share: experts ``share.first_expert`` + [0, "n_routed_experts") are held;
what the others would add is left out, and the partial result goes on.

The weights (the file's ``assumed.weights``), chosen so that every mechanism
MOVES the logits (at std 0.02 throughout a zero bias selects what no bias
selects, and a sink of 0 is one key among 128):
- ``Wq`` / ``Wk`` std ``D ** -0.5`` (1/64 at 4,096): scores of standard
  deviation ~1, so the softmax is not flat and RoPE, the window's edge and the
  two thetas each move it;
- sinks uniform in ln(W / 5) .. ln(2 W) (ln 25.6 .. ln 256 at a window of
  128): the sink holds roughly 10-55 % of a full window row's softmax mass;
- the selection bias: normal quantiles of std 0.03, so that about a quarter
  of the chosen experts differ from the unbiased choice, dealt so that EVERY
  share of ``n_routed_experts`` consecutive experts holds the same 32 values
  in an order of its own (drawn from the seed). ``noaux_tc`` trains the bias
  to keep the experts' loads even; a free draw of this size would move the
  held experts' load, and with it the work of a step, by tens of per cent
  from seed to seed (PERF.md, PR 26: +-50 % at std 0.1), and the seed would
  be changing the work. Dealt this way every share's expected load is 1/8
  whatever the seed. The router is N(0, (1.28 / sqrt(D))^2), 0.02 at 4,096,
  in float32;
- every other matrix std 0.02, ``wo`` and the down projections scaled by
  1 / sqrt(2 L); norm gains 1 + 0.05 N(0, 1); only the held experts' tables
  exist: the router keeps its width.
Layout: ``dense`` = the leading dense layers (full attention) stacked;
``blocks["window"]`` / ``blocks["full"]`` = the sparse layers of each kind
stacked on their own (their ``wk`` / ``wv`` differ in shape); ``blocks["we_*"]``
= the held experts' tables stacked over all sparse layers.

Only to bound memory at 13,824 tokens beside 11.7 GB of weights: a layer runs
in blocks of ``ROWS`` query rows against all keys, one KV head's group of query
heads at a time, the experts one at a time, and ``logits`` returns the rows of
the final hidden state: indexing it computes the head on those rows alone.

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
BIAS_STD = 0.03
ROWS = 256


def sizes(cfg: dict) -> dict:
    """The shape numbers the reference needs, by their published keys."""
    L = cfg["num_hidden_layers"]
    kinds = tuple("window" if x else "full"
                  for x in cfg["hybrid_layer_pattern"][:L])
    sparse = tuple(bool(x) for x in cfg["moe_layer_freq"][:L])
    assert len(kinds) == L == len(sparse)
    n_dense = sparse.index(True) if True in sparse else L
    assert all(sparse[n_dense:]) and set(kinds[:n_dense]) <= {"full"}, (
        "dense layers lead, and are full-attention layers")
    assert cfg["head_dim"] == cfg["swa_head_dim"] \
        and cfg["v_head_dim"] == cfg["swa_v_head_dim"] \
        and cfg["num_attention_heads"] == cfg["swa_num_attention_heads"]
    rot = int(cfg["partial_rotary_factor"] * cfg["head_dim"])
    return {
        "L": L, "kinds": kinds, "n_dense": n_dense, "D": cfg["hidden_size"],
        "Hq": cfg["num_attention_heads"],
        "Hkv_full": cfg["num_key_value_heads"],
        "Hkv_window": cfg["swa_num_key_value_heads"],
        "Dk": cfg["head_dim"], "Dv": cfg["v_head_dim"], "rot": rot - rot % 2,
        "W": cfg["sliding_window"],
        "theta_full": float(cfg["rope_theta"]),
        "theta_window": float(cfg["swa_rope_theta"]),
        "sink_full": bool(cfg["add_full_attention_sink_bias"]),
        "sink_window": bool(cfg["add_swa_attention_sink_bias"]),
        "v_scale": float(cfg["attention_value_scale"]),
        "F": cfg["intermediate_size"], "Fe": cfg["moe_intermediate_size"],
        "E": cfg["published"]["n_routed_experts"],
        "Eh": cfg["n_routed_experts"], "first": cfg["share"]["first_expert"],
        "k": cfg["num_experts_per_tok"], "V": cfg["vocab_size"],
        "eps": float(cfg["layernorm_epsilon"]),
        "dtype": jnp.dtype(cfg["torch_dtype"]),
    }


def layers_of(z: dict, kind: str) -> int:
    """Sparse layers of ``kind``: the length of its stack."""
    return z["kinds"][z["n_dense"]:].count(kind)


# -- weights -----------------------------------------------------------------

def dealt_bias(key, n: int, E: int, share: int) -> jax.Array:
    """[n, E] float32: every run of ``share`` experts holds the ``share``
    normal quantiles of std ``BIAS_STD``, each in its own seeded order."""
    assert E % share == 0, (E, share)
    vals = BIAS_STD * jax.scipy.special.ndtri(
        (jnp.arange(share, dtype=jnp.float32) + 0.5) / share)
    deal = jax.vmap(lambda k: jax.random.permutation(k, vals))
    return deal(jax.random.split(key, n * E // share)).reshape(n, E)


def init_weights(key: jax.Array, cfg: dict) -> dict:
    """Weights from the seed, made on the device in the served dtype (call
    under ``jax.jit``), in the layout of the module docstring."""
    z = sizes(cfg)
    L, D, V, dt = z["L"], z["D"], z["V"], z["dtype"]
    Hq, Dk, Dv, E, Eh = z["Hq"], z["Dk"], z["Dv"], z["E"], z["Eh"]
    keys = iter(jax.random.split(key, 64))
    s, down, qk = 0.02, 0.02 / math.sqrt(2 * L), D ** -0.5

    def f32(*shape, scale=1.0):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def w(*shape, scale=s):
        return f32(*shape, scale=scale).astype(dt)

    def gain(*shape):
        return 1.0 + 0.05 * f32(*shape)

    def layers(n, kind, sparse=True):
        Hkv = z["Hkv_" + kind]
        p = {"attn_norm": gain(n, D), "mlp_norm": gain(n, D),
             "wq": w(n, D, Hq * Dk, scale=qk),
             "wk": w(n, D, Hkv * Dk, scale=qk), "wv": w(n, D, Hkv * Dv),
             "wo": w(n, Hq * Dv, D, scale=down)}
        if z["sink_" + kind]:
            p["sinks"] = jax.random.uniform(
                next(keys), (n, Hq), jnp.float32, math.log(z["W"] / 5),
                math.log(2 * z["W"]))
        if not sparse:
            return {**p, "w_gate": w(n, D, z["F"]), "w_up": w(n, D, z["F"]),
                    "w_down": w(n, z["F"], D, scale=down)}
        return {**p, "w_router": f32(n, D, E, scale=1.28 * D ** -0.5),
                "router_bias": dealt_bias(next(keys), n, E, Eh)}

    Ls, Fe = L - z["n_dense"], z["Fe"]
    blocks = {kind: layers(layers_of(z, kind), kind)
              for kind in ("window", "full")}
    blocks.update(we_gate=w(Ls, Eh, D, Fe), we_up=w(Ls, Eh, D, Fe),
                  we_down=w(Ls, Eh, Fe, D, scale=down))
    return {"embed": w(V, D), "dense": layers(z["n_dense"], "full", False),
            "blocks": blocks, "final_norm": gain(D), "lm_head": w(D, V)}


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


def route(h, w_router, bias, z):
    """(ids [T, k], weights [T, k], scores [T, E]): the k largest of score +
    bias, weighed by the scores alone over their sum."""
    g = jax.nn.sigmoid(jnp.matmul(h, w_router.astype(jnp.float32),
                                  precision=HIGHEST))
    _, ids = jax.lax.top_k(g + bias, z["k"])
    w = jnp.take_along_axis(g, ids, axis=-1)
    return ids, w / (jnp.sum(w, -1, keepdims=True) + 1e-20), g


def routed_part(h, w_router, bias, tables, layer, z, quant):
    """The held experts' part of the routed sum on h [R, D]: every held
    expert on every row, weighted (zero where a row did not choose it).
    ``tables`` = the stacked [sparse layers, Eh, ., .] gate, up and down
    tables; one expert of one layer is sliced out at a time."""
    ids, w, _ = route(h, w_router, bias, z)
    held = z["first"] + jnp.arange(z["Eh"])
    weight = jnp.sum((ids[..., None] == held) * w[..., None], axis=1)

    def one(e, out):
        wg, wu, wd = (jax.lax.dynamic_slice(
            t, (layer, e, 0, 0), (1, 1) + t.shape[2:])[0, 0] for t in tables)
        we = jax.lax.dynamic_slice_in_dim(weight, e, 1, axis=1)
        return out + _swiglu(h, wg, wu, wd, quant) * we

    return jax.lax.fori_loop(0, z["Eh"], one, jnp.zeros_like(h))


def attention_probs(s, seen, sink):
    """Softmax of the scores ``s`` [G, R, T] over the keys ``seen`` [R, T]
    allows, with ``sink`` [G] (or None) as one more logit a head, concatenated
    and dropped: [G, R, T]."""
    s = jnp.where(seen[None], s, -jnp.inf)
    if sink is None:
        return jax.nn.softmax(s, axis=-1)
    col = jnp.broadcast_to(sink[:, None, None], s.shape[:2] + (1,))
    return jax.nn.softmax(jnp.concatenate([s, col], -1), axis=-1)[..., :-1]


def _attend(q, k, v, first, window, sinks, scale):
    """A block of query rows [R, Hq, Dk] at positions ``first ..`` against
    all keys k [T, Hkv, Dk], v [T, Hkv, Dv]: a dense causal mask, and the
    window as a band of it. One KV head's group of query heads at a time."""
    R, Hq, Dk = q.shape
    T, Hkv, Dv = v.shape
    G = Hq // Hkv
    rows = (first + jnp.arange(R))[:, None]
    keys = jnp.arange(T)[None, :]
    seen = keys <= rows
    if window is not None:
        seen = jnp.logical_and(seen, keys > rows - window)

    def head(args):
        qh, kh, vh, *sink = args              # [R, G, Dk], [T, Dk], [T, Dv]
        s = jnp.einsum("rgd,td->grt", qh, kh, precision=HIGHEST) * scale
        p = attention_probs(s, seen, sink[0] if sink else None)
        return jnp.einsum("grt,td->rgd", p, vh, precision=HIGHEST)

    xs = (q.reshape(R, Hkv, G, Dk).swapaxes(0, 1), k.swapaxes(0, 1),
          v.swapaxes(0, 1))
    if sinks is not None:
        xs += (sinks.reshape(Hkv, G),)
    return jax.lax.map(head, xs).swapaxes(0, 1).reshape(R, Hq * Dv)


@functools.partial(jax.jit, static_argnames=("z", "quant", "kind", "sparse"))
def _layer(x, stack, tables, at, expert_layer, z, quant, kind, sparse):
    """One block on x [T, D] (float32): layer ``at`` of ``stack`` (its kind's,
    or the dense run's); ``expert_layer`` its index in the expert ``tables``;
    ``z`` is ``sizes`` as a tuple."""
    z = dict(z)
    p = {n: jax.lax.dynamic_index_in_dim(a, at, 0, keepdims=False)
         for n, a in stack.items()}
    T, Hq, Hkv = x.shape[0], z["Hq"], z["Hkv_" + kind]
    Dk, Dv, theta = z["Dk"], z["Dv"], z["theta_" + kind]
    window = z["W"] if kind == "window" else None
    h_all = _rmsnorm(x, p["attn_norm"], z["eps"])
    k = _rope(_mm(h_all, p["wk"], quant).reshape(T, Hkv, Dk), jnp.arange(T),
              theta, z["rot"])
    v = z["v_scale"] * _mm(h_all, p["wv"], quant).reshape(T, Hkv, Dv)
    R = math.gcd(T, ROWS)

    def block(args):
        xb, first = args
        h = _rmsnorm(xb, p["attn_norm"], z["eps"])
        q = _rope(_mm(h, p["wq"], quant).reshape(R, Hq, Dk),
                  first + jnp.arange(R), theta, z["rot"])
        a = _attend(q, k, v, first, window, p.get("sinks"), Dk ** -0.5)
        xb = xb + _mm(a, p["wo"], quant)
        h = _rmsnorm(xb, p["mlp_norm"], z["eps"])
        if sparse:
            return xb + routed_part(h, p["w_router"], p["router_bias"],
                                    tables, expert_layer, z, quant)
        return xb + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], quant)

    out = jax.lax.map(block, (x.reshape(T // R, R, -1),
                              jnp.arange(T // R) * R))
    return out.reshape(T, -1)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, final_norm, lm_head, eps, quant):
    return _mm(_rmsnorm(x, final_norm, eps), lm_head, quant)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(jnp.float32)


class Logits:
    """Logits [T, V] of one sequence, held as the final hidden state:
    ``self[rows]`` computes the head on ``rows`` alone, ``np.asarray(self)``
    on all of them."""

    def __init__(self, hidden, weights, z, quant):
        self.hidden, self.weights, self.z, self.quant = (hidden, weights, z,
                                                         quant)

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
    Padding at the end of ``tokens`` is harmless: attention is causal and
    every other operation is per row."""
    z = sizes(cfg)
    zt = tuple(sorted((k, v) for k, v in z.items() if k != "dtype"))
    x = _embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    blocks = weights["blocks"]
    tables = tuple(blocks[n] for n in ("we_gate", "we_up", "we_down"))
    seen = {"window": 0, "full": 0}
    for layer, kind in enumerate(z["kinds"]):
        sparse = layer >= z["n_dense"]
        if sparse:
            stack, at = blocks[kind], seen[kind]
            seen[kind] += 1
        else:
            stack, at = weights["dense"], layer
        x = _layer(x, stack, tables, jnp.int32(at),
                   jnp.int32(layer - z["n_dense"]), zt, quant, kind, sparse)
    return Logits(x, weights, z, quant)
