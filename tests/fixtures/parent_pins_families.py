"""Tokens the OTHER model families serve, pinned to the tree before the
linear-attention family (PR 39): the latent, the window, the sink-window and
the mixer-beside-attention families share the layer loop, ``expert_share``,
the GQA kernels and the in-place state loop with it. ``python
tests/fixtures/parent_pins_families.py`` writes ``parent_pins_families.npz``
beside this file; it was run on the parent commit (140cd65), and
``tests/test_linear_attn_moe.py`` holds the present tree to it. Tokens are
integers: equality is asked for on every machine."""
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
FILE = os.path.join(HERE, "parent_pins_families.npz")
FAMILIES = ("latent", "window", "sink_window", "hybrid")


def engine(name):
    """A ``ServingEngine`` of two slots, chunks of 16, K = 3, for the family's
    tiny preset and seeded weights."""
    import jax
    from triton_dist_tpu.models import hybrid_ssm, mla, window_moe
    from triton_dist_tpu.serving import ServingEngine
    cfg, init = {
        "latent": (mla.LatentMoEConfig.tiny(3, held=8, first=4),
                   mla.init_params),
        "window": (window_moe.WindowMoEConfig.tiny(4, held=8, first=4),
                   window_moe.init_params),
        "sink_window": (window_moe.WindowMoEConfig.tiny_sink(held=8, first=4),
                        window_moe.init_params),
        "hybrid": (hybrid_ssm.HybridSSMConfig.tiny(), hybrid_ssm.init_params),
    }[name]
    params = init(jax.random.PRNGKey(3), cfg)
    return ServingEngine(params, cfg, num_slots=2, page_size=8, num_pages=24,
                         pages_per_seq=8, prefill_chunk=16, decode_horizon=3)


def programs(name):
    """Two requests through that engine (the first prompt spans two chunks):
    every request's tokens."""
    eng = engine(name)
    cfg = eng.cfg
    rng = np.random.default_rng(5)
    rids = [eng.submit(rng.integers(1, cfg.vocab_size, n), 5)
            for n in (21, 9)]
    while eng.step():
        pass
    done = {r.rid: r.generated for r in eng._finished}
    return {f"{name}_tokens": np.asarray([done[rid] for rid in rids],
                                         np.int32)}


if __name__ == "__main__":
    sys.path.insert(0, os.getcwd())
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    pins = {}
    for family in FAMILIES:
        pins.update(programs(family))
    np.savez(FILE, **pins)
    print({k: v.tolist() for k, v in pins.items()})
