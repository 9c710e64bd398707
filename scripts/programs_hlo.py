"""Whether a change leaves the benchmark's programs as they were, without a
chip: compile the decode and chunk programs of one-chip configurations for a
DESCRIBED v5e at their cells' sizes (as ``benchmark/tools/fit_paged.py``
does) and write, a program, its optimised HLO and every Mosaic kernel's MLIR,
both WITHOUT source locations (a moved line is no change):

    python scripts/programs_hlo.py --root <checkout> --out <dir> [--configs a,b]
    python scripts/programs_hlo.py --same <dir of one tree> <dir of another>
    python scripts/programs_hlo.py --kernels-same <dir> <dir>
    python scripts/programs_hlo.py --entry-copies [--configs a,b]

The programs are compiled the way the ENGINE compiles them: through
``serving.layouts.held_layout_programs`` (the decode program chooses the
layout of every parameter leaf, the chunk program is compiled against what it
chose), or with plain ``jax.jit`` where ``--root``'s package is older than that
module. A line a configuration says which parameter leaves are held in another
layout than the device's default (leaf, shape, from, to, bytes); a line a
program its temporaries (``memory_analysis()``) and the instructions of its
ENTRY computation that re-lay out a parameter (none, through the helper; an
older checkout's are the copies it made every dispatch). ``--entry-copies``
prints those lines and writes no file.

Run it once in each of two checkouts (the process imports ``--root``'s
package), then ``--same`` says which files differ; ``--kernels-same`` compares
the Mosaic kernels' MLIR alone (a change of the programs around them that must
leave every kernel as it was). PR 37 held the four configurations it shares
code with to the parent this way: every program and kernel identical; PR 39
(whose layer loop indexes the layers of SEVERAL periods in place) all five."""
import argparse
import base64
import filecmp
import importlib
import importlib.util
import json
import os
import re
import sys

CONFIGS = ("mistral-7b-v5e1", "kimi-k2-ep32-v5e1", "command-a-plus-ep8-v5e1",
           "falcon-h1-34b-v5e1", "mimo-v2-flash-ep8-v5e1",
           "qwen3-next-80b-ep16-v5e1", "lfm2-24b-a2b-pp4-v5e1",
           "ouro-2.6b-v5e1")
TABLES = re.compile(r"^\d+ |^(FileNames|FunctionNames|FileLocations|"
                    r"StackFrames)")
BODY = re.compile(r'"body":"([^"]*)"')


def dump(root: str, out: str | None, configs) -> None:
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

    try:
        from triton_dist_tpu.serving import layouts
        through_helper = True
    except ImportError:
        # a checkout from before the engine held any layout: its programs
        # are plain jits, read with this checkout's reader of ENTRY copies
        spec = importlib.util.spec_from_file_location(
            "_layouts", os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "triton_dist_tpu", "serving",
                "layouts.py"))
        layouts = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(layouts)
        through_helper = False
    if out:
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
        step = lambda p, t, pos, pages, bt, lim: fam.decode_multistep(  # noqa: E731
            p, t, pos, pc, pages, bt, lim, horizon=K, eos_id=None)
        chunk = lambda p, t, s, n, pages, bt: fam.prefill_chunk(  # noqa: E731
            p, t, s, n, pc, pages, bt)
        step_rest = (i32(B), i32(B), pool, i32(B, W), i32(B))
        chunk_rest = (i32(C), i32(), i32(), pool, i32(W))
        held = []
        if not through_helper:
            compiled = {
                "decode": jax.jit(step, donate_argnums=(3,)).lower(
                    params, *step_rest).compile(),
                "chunk": jax.jit(chunk, donate_argnums=(4,)).lower(
                    params, *chunk_rest).compile()}
        else:
            decode, chunk_jit, formats = layouts.held_layout_programs(
                step, chunk, params, step_rest)
            compiled = {"decode": decode, "chunk": chunk_jit.lower(
                params, *chunk_rest).compile()}
            held = layouts.relaid(params, formats)
        print(json.dumps({"config": name, "through_helper": through_helper,
                          "held_relaid_bytes": sum(h["bytes"] for h in held),
                          "held_relaid": held}), flush=True)
        for prog, exe in compiled.items():
            text = exe.as_text()
            line = {"program": f"{name}.{prog}",
                    "temporaries": exe.memory_analysis().temp_size_in_bytes,
                    "entry_copies": layouts.entry_copies(text, params)}
            if out:
                text = re.sub(r", metadata=\{[^}]*\}", "", text)
                text = re.sub(r",? ?stack_frame_id=\d+", "", text)
                lines = [BODY.sub(kernel, ln) for ln in text.splitlines()
                         if not TABLES.match(ln)]
                with open(os.path.join(out, f"{name}.{prog}.hlo"), "w") as f:
                    f.write("\n".join(lines) + "\n")
                line.update(wrote=f"{name}.{prog}.hlo", lines=len(lines))
            print(json.dumps(line), flush=True)


def kernels(path: str) -> list[tuple[str, str]]:
    """The Mosaic kernels of a written program, in the program's order: the
    instruction's name (no number) and its MLIR (not what XLA plans around
    it: the scoped memory's offsets move with the program)."""
    found = []
    with open(path) as f:
        for ln in f:
            at = ln.find('"body":')
            if at >= 0:
                name = re.match(r"\s*(?:ROOT )?(%[\w\-]+)", ln).group(1)
                found.append((name, json.JSONDecoder().raw_decode(
                    ln, at + len('"body":'))[0]))
    return found


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    p.add_argument("--out")
    p.add_argument("--configs", default=",".join(CONFIGS))
    p.add_argument("--same", nargs=2, metavar="DIR")
    p.add_argument("--kernels-same", nargs=2, metavar="DIR")
    p.add_argument("--entry-copies", action="store_true")
    a = p.parse_args()
    pair = a.same or a.kernels_same
    if pair:
        paths = lambda n: [os.path.join(d, n) for d in pair]    # noqa: E731
        same = {n: (filecmp.cmp(*paths(n), shallow=False) if a.same
                    else kernels(paths(n)[0]) == kernels(paths(n)[1]))
                for n in sorted(os.listdir(pair[0]))}
        print(json.dumps(same, indent=1))
        return 0 if all(same.values()) else 1
    if not a.out and not a.entry_copies:
        p.error("--out, or --entry-copies for the listing alone")
    dump(a.root, a.out, a.configs.split(","))
    return 0


if __name__ == "__main__":
    sys.exit(main())
