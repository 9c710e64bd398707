"""Cases whose results are pinned to the tree BEFORE window attention (PR 30):
the two paged GQA kernels with no window, and the decode and chunk programs of
the dense and the latent tiny presets. ``python tests/fixtures/parent_pins.py``
writes ``parent_pins.npz`` beside this file; it was run on the parent commit
(6bf7760), and ``tests/test_window_moe.py`` holds the present tree to it.

Bitwise equality is asked for where the machine computes as the one that took
the pins did (``canary``: a chain of float operations this repo does not
own); on another machine the same arrays are held to 1e-5.
"""
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
FILE = os.path.join(HERE, "parent_pins.npz")


def canary():
    import jax
    import jax.numpy as jnp
    x = jax.random.normal(jax.random.PRNGKey(7), (64, 64), jnp.float32)
    y = jnp.exp(jnp.tanh(x @ x.T) / 8.0) @ x
    return {"canary": np.asarray(jax.nn.softmax(y, axis=-1))}


def kernels():
    import jax
    import jax.numpy as jnp
    from triton_dist_tpu.ops.flash_decode import (gqa_decode_paged,
                                                  gqa_prefill_paged)
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    Hq, Hkv, D, page, P = 4, 2, 64, 8, 12
    kp = jax.random.normal(ks[0], (P, Hkv, page, D), jnp.float32)
    vp = jax.random.normal(ks[1], (P, Hkv, page, D), jnp.float32)
    q = jax.random.normal(ks[2], (4, Hq, D), jnp.float32)
    bt = jnp.asarray([[1, 2, 3, 4, 5], [6, 7, 8, 9, 10], [11, 3, 5, 7, 9],
                      [2, 4, 6, 8, 10]], jnp.int32)
    out, lse = gqa_decode_paged(q, kp, vp, bt, jnp.asarray([0, 5, 17, 40]))
    qc = jax.random.normal(ks[3], (16, Hq, D), jnp.float32)
    kv_len = jnp.where(jnp.arange(16) < 13, 20 + jnp.arange(16) + 1, 0)
    pre = gqa_prefill_paged(qc, kp, vp, bt[2], kv_len, rows_per_block=8)
    return {"decode_out": np.asarray(out), "decode_lse": np.asarray(lse),
            "prefill_out": np.asarray(pre)}


def programs(name):
    """One prompt through ``jit_chunk`` twice, then ``jit_step`` (K = 3) over
    four slots of which two are live: tokens, positions and the whole pool."""
    import jax
    import jax.numpy as jnp
    from triton_dist_tpu.models import llama, mla
    if name == "dense":
        cfg = llama.LlamaConfig.tiny(2)
        params = llama.init_params(jax.random.PRNGKey(3), cfg)
    else:
        cfg = mla.LatentMoEConfig.tiny(3, held=8, first=4)
        params = mla.init_params(jax.random.PRNGKey(3), cfg)
    page, pps, C, K = 8, 6, 16, 3
    pool = cfg.paged.init_pool(cfg, 14, page)
    chunk = jax.jit(lambda p, t, s, n, pages, bt: llama.prefill_chunk_paged(
        p, t, s, n, cfg, pages, bt))
    step = jax.jit(lambda p, t, pos, pages, bt, lim:
                   llama.decode_multistep_paged(p, t, pos, cfg, pages, bt,
                                                lim, horizon=K))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, n) for n in (21, 9)]
    rows = [np.asarray([1, 2, 3, 4, 5, 6], np.int32),
            np.asarray([7, 8, 9, 10, 11, 12], np.int32)]
    first = []
    for prompt, row in zip(prompts, rows):
        for start in range(0, len(prompt), C):
            toks = np.zeros(C, np.int32)
            part = prompt[start:start + C]
            toks[:len(part)] = part
            tok, pool = chunk(params, jnp.asarray(toks), jnp.int32(start),
                              jnp.int32(len(prompt)), pool, jnp.asarray(row))
        first.append(int(tok))
    bt = np.zeros((4, pps), np.int32)
    bt[0], bt[2] = rows
    token = jnp.asarray([first[0], 0, first[1], 0], jnp.int32)
    pos = jnp.asarray([21, 0, 9, 0], jnp.int32)
    toks, token, pos, pool = step(params, token, pos, pool, jnp.asarray(bt),
                                  jnp.asarray([3, 0, 2, 0], jnp.int32))
    out = {f"{name}_first": np.asarray(first), f"{name}_toks": np.asarray(toks),
           f"{name}_pos": np.asarray(pos)}
    for leaf, a in pool.items():
        out[f"{name}_pool_{leaf}"] = np.asarray(a.astype(jnp.float32))
    return out


CASES = {"canary": canary, "kernels": kernels,
         "dense": lambda: programs("dense"),
         "latent": lambda: programs("latent")}


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    pins = {}
    for case in CASES.values():
        pins.update(case())
    np.savez_compressed(FILE, **pins)
    print({k: v.shape for k, v in pins.items()})
