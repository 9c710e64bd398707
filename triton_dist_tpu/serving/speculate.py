"""Model-free speculative decoding primitives (ISSUE 20).

The serving hot loop earns one committed token per active slot per
dispatch — decode throughput is bounded by sequential sampling, not the
hardware. Draft-verify breaks the bound WITHOUT a draft model:

- **draft** (``ngram_draft``): propose K-1 continuation tokens per slot by
  prompt-lookup over the token history the engine already keeps device-
  resident — find the most recent earlier occurrence of the current
  bigram and replay what followed it. Pure jnp over an int32 ``[B, H]``
  ring of recent tokens: no host sync, no extra model, no new weights.
- **verify** (``models.llama.decode_speculate_paged``): ONE paged-
  attention pass scores all K positions as K batch rows (each at its
  own ``kv_len``, as a prefill chunk's rows are), greedy-argmaxes each,
  and ``spec_accept`` keeps the longest prefix where draft == argmax.
- **rewind**: rejected positions' KV is already past the accepted
  cursor; the engine frees whole rejected pages via the existing
  ``KVPagePool.free_tail`` and the next dispatch overwrites in-page
  remainders before any read (the same argument that makes in-page
  padding tails safe).

Acceptance is EXACT-MATCH against the greedy argmax, which is what keeps
the bitwise trace contract: a committed token is committed because the
verify row — fed the identical committed prefix — produced it, so the
sequence is bit-identical to ``speculate=off``; only the dispatch count
shrinks. A bad drafter can only cost speed, never change a token.

This module is deliberately free of model/engine imports (``llama.py``
imports it function-locally at trace time) so the drafter and the accept
rule stay unit-testable host-side — the EOS/limit edge cases ride plain
int arrays here instead of a 50-request engine run.
"""

from __future__ import annotations

import jax.numpy as jnp

from triton_dist_tpu.aot.registry import TunedKey, get_default_registry

SPEC_K_DEFAULT = 4


def ngram_draft(hist: jnp.ndarray, hist_len: jnp.ndarray,
                n: int) -> jnp.ndarray:
    """Propose ``n`` draft tokens per row by bigram prompt-lookup.

    ``hist`` [B, H] int32 is the right-aligned recent-token window
    (newest token at column H-1, zero left-padding); ``hist_len`` [B]
    int32 counts the valid suffix. For each row, find the MOST RECENT
    earlier position whose (previous, current) token pair equals the
    window's final bigram and return the ``n`` tokens that followed it;
    fall back to a unigram match on the final token, then to repeating
    the final token (a deliberately wrong draft the verify pass simply
    rejects — drafting can never affect correctness, only speed).

    Pure jnp, shape-static in (B, H, n): traces into the one compiled
    decode program. Most-recent-match (not first) because generation
    loops — n-gram cycles in the generated suffix — are exactly the
    repetitive structure speculation wins on.
    """
    B, H = hist.shape
    if n <= 0:
        return jnp.zeros((B, 0), jnp.int32)
    idx = jnp.arange(H, dtype=jnp.int32)[None, :]             # [1, H]
    lo = (H - hist_len)[:, None].astype(jnp.int32)            # [B, 1]
    last = hist[:, -1][:, None]                               # [B, 1]
    prev = jnp.concatenate([jnp.zeros((B, 1), hist.dtype),
                            hist[:, :-1]], axis=1)            # [B, H]
    second = prev[:, -1][:, None]                             # hist[:, -2]
    # candidates strictly before the newest position, inside the valid
    # window (the bigram additionally needs its PREVIOUS position valid)
    in_win = jnp.logical_and(idx >= lo, idx < H - 1)
    m1 = jnp.logical_and(hist == last, in_win)
    m2 = jnp.logical_and(m1, jnp.logical_and(prev == second,
                                             idx - 1 >= lo))
    j2 = jnp.max(jnp.where(m2, idx, -1), axis=1)              # [B]
    j1 = jnp.max(jnp.where(m1, idx, -1), axis=1)
    j = jnp.where(j2 >= 0, j2, j1)                            # [B]
    cols = j[:, None] + 1 + jnp.arange(n, dtype=jnp.int32)[None, :]
    cols = jnp.clip(cols, 0, H - 1)
    out = jnp.take_along_axis(hist, cols, axis=1)
    return jnp.where((j >= 0)[:, None], out, last).astype(jnp.int32)


def spec_accept(inp: jnp.ndarray, nxt: jnp.ndarray, ract: jnp.ndarray,
                eos_id: int | None = None) -> jnp.ndarray:
    """Accepted-count per row for one draft-verify dispatch.

    ``inp`` [B, K] are the tokens the verify rows CONSUMED (column 0 the
    real last token, columns 1..K-1 the drafts); ``nxt`` [B, K] the
    greedy argmax each row PRODUCED; ``ract`` [B, K] the per-row
    ``limit`` mask. Returns ``m`` [B] int32, the number of committed
    tokens ``nxt[:, :m]`` — the longest prefix where:

    - position 0 always commits on an active row (``inp[:, 0]`` is the
      authentic last token, so ``nxt[:, 0]`` IS the greedy next token);
    - position i > 0 commits iff position i-1 committed AND the draft
      matched its verified argmax (``inp[:, i] == nxt[:, i-1]`` — the
      row consumed the token greedy decoding would have) AND the limit
      admits it AND position i-1 did not emit EOS.

    The EOS clause freezes AFTER the emitting position, mirroring
    ``decode_multistep_paged``'s stopped-mask: EOS, when present, is
    always the LAST committed token — never inside the accepted prefix —
    so the host can append all ``m`` tokens and finish the request
    without mid-slab divergence. ``m <= limit`` composes the
    ``max_new_tokens``/page-headroom clamp: an accept burst can never
    overshoot the budget or write KV past a frozen row.
    """
    B, K = inp.shape
    m = jnp.zeros((B,), jnp.int32)
    alive = jnp.ones((B,), jnp.bool_)
    for i in range(K):
        can = jnp.logical_and(alive, ract[:, i])
        if i > 0:
            can = jnp.logical_and(can, inp[:, i] == nxt[:, i - 1])
        m = m + can.astype(jnp.int32)
        if eos_id is not None:
            can = jnp.logical_and(can, nxt[:, i] != eos_id)
        alive = can
    return m


def resolve_spec_k(speculate, mesh_shape=(), dtype: str = "float32",
                   bucket: int = 0, default: int = SPEC_K_DEFAULT) -> int:
    """Resolve the draft length K: explicit int → PR 15 registry →
    default — the ``serving_overlap_mb`` resolution ladder (sharded.py)
    applied to the speculation knob. ``"auto"`` consults the default
    tuned-config registry under ``TunedKey("serving_spec_k", mesh_shape,
    dtype, ((bucket,),))`` where ``bucket`` is the workload-
    repetitiveness bucket (``workload.spec_bucket_of``): the best K is a
    property of the traffic (how repetitive) and the mesh (how much a
    wasted verify row costs), not of the model. Mesh-keyed entries enter
    the registry only through the sigcheck gate
    (``aot.registry.GATE_RUNNERS["serving_spec_k"]``) because K scales
    the decode program's EP A2A row count."""
    if isinstance(speculate, bool):
        raise TypeError("speculate must be an int K or 'auto', not bool")
    if isinstance(speculate, int):
        assert speculate >= 1, f"speculate K must be >= 1, got {speculate}"
        return speculate
    assert speculate == "auto", (
        f"speculate must be an int K or 'auto', got {speculate!r}")
    reg = get_default_registry()
    if reg is not None:
        k = reg.get(TunedKey("serving_spec_k",
                             mesh_shape=tuple(int(d) for d in mesh_shape),
                             dtype=str(dtype),
                             shape_bucket=((int(bucket),),)))
        if k is not None:
            return int(k)
    return default


__all__ = ["ngram_draft", "spec_accept", "resolve_spec_k",
           "SPEC_K_DEFAULT"]
