"""Whether a change leaves the benchmark's programs as they were, without a
chip: compile the decode and chunk programs of one-chip configurations for a
DESCRIBED v5e at their cells' sizes (as ``benchmark/tools/fit_paged.py``
does) and write, a program, its optimised HLO and every Mosaic kernel's MLIR,
both WITHOUT source locations (a moved line is no change):

    python scripts/programs_hlo.py --root <checkout> --out <dir> [--configs a,b]
    python scripts/programs_hlo.py --same <dir of one tree> <dir of another>

Run it once in each of two checkouts (the process imports ``--root``'s
package), then ``--same`` says which files differ. PR 37 held the four
configurations it shares code with to the parent this way: every program and
kernel identical."""
import argparse
import base64
import filecmp
import importlib
import json
import os
import re
import sys

CONFIGS = ("mistral-7b-v5e1", "kimi-k2-ep32-v5e1", "command-a-plus-ep8-v5e1",
           "falcon-h1-34b-v5e1")
TABLES = re.compile(r"^\d+ |^(FileNames|FunctionNames|FileLocations|"
                    r"StackFrames)")
BODY = re.compile(r'"body":"([^"]*)"')


def dump(root: str, out: str, configs) -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")
    os.environ["TDT_FORCE_COMPILED"] = "1"
    sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from benchmark import check as ck
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    on = lambda t: jax.tree_util.tree_map(            # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), t)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)  # noqa: E731
    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True

    def kernel(match):
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return '"body":' + json.dumps(module.operation.get_asm(
                enable_debug_info=False))

    os.makedirs(out, exist_ok=True)
    for name in configs:
        with open(os.path.join(root, "benchmark", "configs",
                               name + ".json")) as f:
            cfg = json.load(f)
        ad = importlib.import_module(
            f"benchmark.adapters.{cfg['adapter']}").Adapter(cfg)
        pc, e = ad._program_config(), ad.eng_cfg
        fam = pc.paged
        if fam.bind is not None:
            pc = fam.bind(pc, e["num_slots"], e["prefill_chunk"])
        ref = ck.load_reference(cfg["reference"])
        params = on(jax.eval_shape(lambda k: ref.init_weights(k, cfg),
                                   jax.random.PRNGKey(0)))
        pool = on(jax.eval_shape(lambda: fam.init_pool(
            pc, e["num_pages"] + 1, e["page_size"])))
        B, K, C = e["num_slots"], ad.decode_horizon, e["prefill_chunk"]
        W = e["pages_per_seq"] + bool(fam.slot_ring or fam.slot_state)
        lowered = {
            "decode": jax.jit(
                lambda p, t, pos, pages, bt, lim: fam.decode_multistep(
                    p, t, pos, pc, pages, bt, lim, horizon=K, eos_id=None),
                donate_argnums=(3,)).lower(params, i32(B), i32(B), pool,
                                           i32(B, W), i32(B)),
            "chunk": jax.jit(
                lambda p, t, s, n, pages, bt: fam.prefill_chunk(
                    p, t, s, n, pc, pages, bt),
                donate_argnums=(4,)).lower(params, i32(C), i32(), i32(), pool,
                                           i32(W))}
        for prog, low in lowered.items():
            text = low.compile().as_text()
            text = re.sub(r", metadata=\{[^}]*\}", "", text)
            text = re.sub(r",? ?stack_frame_id=\d+", "", text)
            lines = [BODY.sub(kernel, line) for line in text.splitlines()
                     if not TABLES.match(line)]
            with open(os.path.join(out, f"{name}.{prog}.hlo"), "w") as f:
                f.write("\n".join(lines) + "\n")
            print(json.dumps({"wrote": f"{name}.{prog}.hlo",
                              "lines": len(lines)}), flush=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    p.add_argument("--out")
    p.add_argument("--configs", default=",".join(CONFIGS))
    p.add_argument("--same", nargs=2, metavar="DIR")
    a = p.parse_args()
    if a.same:
        names = sorted(os.listdir(a.same[0]))
        same = {n: filecmp.cmp(os.path.join(a.same[0], n),
                               os.path.join(a.same[1], n), shallow=False)
                for n in names}
        print(json.dumps(same, indent=1))
        return 0 if all(same.values()) else 1
    dump(a.root, a.out, a.configs.split(","))
    return 0


if __name__ == "__main__":
    sys.exit(main())
