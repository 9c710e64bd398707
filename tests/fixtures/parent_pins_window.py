"""The two paged GQA kernels WITH a window, at one width of keys and values and
no sink, pinned to the tree before they took other widths and sinks (PR 37):
``python tests/fixtures/parent_pins_window.py`` writes
``parent_pins_window.npz`` beside this file; it was run on the parent commit
(cf11396), and ``tests/test_sink_window_moe.py`` holds the present tree to it
(``parent_pins.py`` holds the unwindowed kernels and the programs; its
``canary`` says whether this machine computes as the pinning one did)."""
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
FILE = os.path.join(HERE, "parent_pins_window.npz")


def windowed():
    import jax
    import jax.numpy as jnp
    from triton_dist_tpu.ops.flash_decode import (gqa_decode_paged,
                                                  gqa_prefill_paged)
    ks = jax.random.split(jax.random.PRNGKey(37), 4)
    Hq, Hkv, D, page, P, W = 8, 2, 32, 8, 13, 20
    kp = jax.random.normal(ks[0], (P, Hkv, page, D), jnp.float32)
    vp = jax.random.normal(ks[1], (P, Hkv, page, D), jnp.float32)
    q = jax.random.normal(ks[2], (4, Hq, D), jnp.float32)
    bt = jnp.asarray([[1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12],
                      [11, 3, 5, 7, 9, 1], [2, 4, 6, 8, 10, 12]], jnp.int32)
    out, lse = gqa_decode_paged(q, kp, vp, bt, jnp.asarray([0, 5, 21, 113]),
                                window=W)
    qc = jax.random.normal(ks[3], (16, Hq, D), jnp.float32)
    kv_len = jnp.where(jnp.arange(16) < 13, 50 + jnp.arange(16) + 1, 0)
    pre = gqa_prefill_paged(qc, kp, vp, bt[2], kv_len, rows_per_block=8,
                            window=W)
    return {"window_decode_out": np.asarray(out),
            "window_decode_lse": np.asarray(lse),
            "window_prefill_out": np.asarray(pre)}


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    np.savez(FILE, **windowed())
    print("wrote", FILE)
