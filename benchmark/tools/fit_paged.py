"""``fit.py`` for any model family the engine serves: compile the decode and
chunk programs of a one-chip configuration for a DESCRIBED TPU v5e (no chip
needed; ``JAX_PLATFORMS=cpu`` stays set) at the file's own sizes and print the
compiler's memory analysis.

    python3 benchmark/tools/fit_paged.py --config <name> [--layers 7,8]

``fit.py`` builds the llama programs by name; this one asks the configuration's
adapter for the program config and that config's family (``cfg.paged``) for the
pool and the two programs, the way ``ServingEngine`` does (same horizon, same
donation of the pool). A program's need on the chip is arguments + temporaries
+ outputs that alias no argument; the engine holds the weights and the pool
anyway, so ``beside_gb`` is what a program adds to them at its peak. A compile
that passes is not a chip run.
"""
import argparse
import importlib
import json
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")
os.environ["TDT_FORCE_COMPILED"] = "1"      # Mosaic kernels, not interpret

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmark import check as ck, manifest as mf  # noqa: E402


def analyse(cfg: dict, chip) -> dict:
    ad = importlib.import_module(
        f"benchmark.adapters.{cfg['adapter']}").Adapter(cfg)
    if ad.mesh_shape is not None:
        raise SystemExit("fit_paged.py compiles one-chip configurations only")
    pc, e = ad._program_config(), ad.eng_cfg
    fam = pc.paged
    on = lambda t: jax.tree_util.tree_map(            # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), t)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)  # noqa: E731
    ref = ck.load_reference(cfg["reference"])
    params = on(jax.eval_shape(lambda k: ref.init_weights(k, cfg),
                               jax.random.PRNGKey(0)))
    pool = on(jax.eval_shape(
        lambda: fam.init_pool(pc, e["num_pages"] + 1, e["page_size"])))
    B, K, C = e["num_slots"], ad.decode_horizon, e["prefill_chunk"]

    def step(p, t, pos, pages, bt, lim):
        return fam.decode_multistep(p, t, pos, pc, pages, bt, lim,
                                    horizon=K, eos_id=None)

    def chunk(p, t, s, n, pages, bt):
        return fam.prefill_chunk(p, t, s, n, pc, pages, bt)

    progs = {
        "weights": jax.jit(lambda k: ref.init_weights(k, cfg)).lower(
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)),
        "decode": jax.jit(step, donate_argnums=(3,)).lower(
            params, i32(B), i32(B), pool, i32(B, e["pages_per_seq"]), i32(B)),
        "chunk": jax.jit(chunk, donate_argnums=(4,)).lower(
            params, i32(C), i32(), i32(), pool, i32(e["pages_per_seq"]))}
    gb = lambda n: round(n / 1e9, 3)                   # noqa: E731
    size = lambda t: sum(a.size * a.dtype.itemsize     # noqa: E731
                         for a in jax.tree_util.tree_leaves(t))
    out = {"family": fam.name, "layers": cfg["num_hidden_layers"],
           "weights_gb": gb(size(params)), "pool_gb": gb(size(pool))}
    for name, lowered in progs.items():
        try:
            m = lowered.compile().memory_analysis()
        except jax.errors.JaxRuntimeError as err:      # the chip's refusal
            out[name] = {"refused": re.search(
                r"Used \S+ of \S+ hbm|$", str(err)).group(0) or str(err)[:300]}
            continue
        beside = (m.temp_size_in_bytes + m.output_size_in_bytes
                  - m.alias_size_in_bytes)
        out[name] = {"arguments_gb": gb(m.argument_size_in_bytes),
                     "temp_gb": gb(m.temp_size_in_bytes),
                     "beside_gb": gb(beside),
                     "total_gb": gb(m.argument_size_in_bytes + beside)}
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--layers", default=None,
                   help="depths to try instead of the file's, e.g. 7,8")
    a = p.parse_args()
    m = mf.load()
    paths = [os.path.join(ROOT, d, "configs", a.config + ".json")
             for d in m["paths"]]
    with open(next(f for f in paths if os.path.isfile(f))) as f:
        cfg = json.load(f)
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    depths = [int(x) for x in a.layers.split(",")] if a.layers \
        else [cfg["num_hidden_layers"]]
    for depth in depths:
        print(json.dumps(analyse(dict(cfg, num_hidden_layers=depth), chip)),
              flush=True)


if __name__ == "__main__":
    main()
