"""The output check: what the timed path served, held against the plain reference.

Once the window has closed, a sample of the requests it finished is drawn from
the seed, the longest among them. The reference runs once over each prompt with
the tokens the system served (teacher forcing), and at every served position
reads the gap by which the served token's reference logit lies below the
reference's best. Greedy decoding at the configuration's precision keeps that
gap within rounding; a path computed in a lower precision, or a token altered
where it is produced, does not. Two numbers are compared, each with a limit of
its own from the configuration's ``check.limits``:

- ``gap_max``: the widest gap over all checked positions;
- ``gap_mean``: the mean gap (steadier from seed to seed).

Also held, exactly: every finished request has the number of tokens it asked
for, every token is a valid id, and nothing compiled inside the window.
"""

from __future__ import annotations

import importlib

import numpy as np


def load_reference(name: str):
    return importlib.import_module(f"benchmark.references.{name}")


def pick_sample(recs, n: int, seed: int) -> list:
    """The longest finished window request and n-1 others, drawn from the
    seed."""
    done = [r for r in recs if r.counted and r.done and not r.failed]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + r.n_out, -r.idx))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 0x636b])
    take = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(take)]


def positions(prompt_len: int, n_out: int):
    """Rows of the reference's logits that predict the served tokens."""
    return np.arange(prompt_len - 1, prompt_len - 1 + n_out)


def gaps_of(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """ref_logits [n, V] at the predicting rows; tokens [n] served."""
    best = ref_logits.max(axis=-1)
    return best - ref_logits[np.arange(len(tokens)), tokens]


def reference_rows(ref, weights, cfg, prompt, served, pad_to: int,
                   quant=None) -> np.ndarray:
    """Reference logits [n_out, V] at the positions that predict ``served``."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    padded = np.zeros(max(pad_to, len(seq)), np.int32)
    padded[:len(seq)] = seq
    rows = positions(len(prompt), len(served))
    return np.asarray(ref.logits(weights, padded, cfg, quant=quant)[rows])


def _stats(gaps, prefix="") -> dict:
    g = np.concatenate(gaps) if gaps else np.zeros(0)
    nan = float("nan")
    return {prefix + "gap_max": float(g.max()) if g.size else nan,
            prefix + "gap_mean": float(g.mean()) if g.size else nan,
            prefix + "flipped_share": float((g > 0).mean()) if g.size else nan}


def compare(sample, prompts, served, ref, weights, cfg, pad_to: int,
            control: str | None = None) -> dict:
    """Gap statistics of the sampled requests (lists aligned with sample).
    ``control`` names a lower precision: the reference is then also computed
    in it over the same prompts and tokens, and the gap of the token IT puts
    first at each position is read the same way (``control_*``). A benchmark
    run never asks for it; ``tools/sweep.py`` and the tests do."""
    gaps, cgaps = [], []
    for p, s in zip(prompts, served):
        rows = reference_rows(ref, weights, cfg, p, s, pad_to)
        gaps.append(gaps_of(rows, np.asarray(s)))
        if control:
            low = reference_rows(ref, weights, cfg, p, s, pad_to,
                                 quant=control)
            cgaps.append(gaps_of(rows, low.argmax(axis=-1)))
    out = {"positions": int(sum(len(g) for g in gaps)),
           "requests": len(sample), **_stats(gaps)}
    if control:
        out.update(_stats(cgaps, "control_"))
    return out


def verdict(numbers: dict, limits: dict, exact: dict) -> tuple[bool, list]:
    """(correct, printed lines): each number beside its limit."""
    ok, lines = True, []
    for name, limit in limits.items():
        v = numbers.get(name)
        good = v is not None and np.isfinite(v) and v <= limit
        ok &= bool(good)
        lines.append({"check": name, "value": v, "limit": limit,
                      "ok": bool(good)})
    for name, (v, want) in exact.items():
        good = v == want
        ok &= bool(good)
        lines.append({"check": name, "value": v, "limit": want,
                      "ok": bool(good)})
    return ok, lines
