"""The controls that show the output check SEES each mechanism of the
sink-window family: run a cell with the PROGRAM wrong in one thing (the
reference keeps the file's), one line a control and seed:

    python3 benchmark/tools/sink_control.py --workload <cell> --seeds 1,2 \
        [--controls sink-dropped,window-page-wider] [--seconds 20]

``CONTROLS`` maps a name to what is altered for the program alone, its config
or its weights; everything else is ``run.py``'s path: the
same engine, traffic and check against the unchanged reference and weights. A
check that holds a mechanism reads ``correct: false`` under its control by at
least one of its limits; one it cannot resolve is written down, with its
readings, in the configuration's ``check.set_from``. ``--controls none`` is a
sound run through the same tool."""
import argparse
import dataclasses
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import run as R  # noqa: E402


def _as_eight_kv_heads(pc, weights):
    """The full layers run with the window layers' KV-head count: query head
    h reads KV head (h // 8) % 4, the 8-head grouping over the 4 heads tiled,
    where it should read h // 16. No shape changes: the query heads (columns
    of ``wq``, rows of ``wo``) are permuted so that the program's group of 16
    around KV head j holds the heads the wrong grouping sends there."""
    import numpy as np
    Hq, Hkv, wrong = pc.n_heads, pc.kv_heads("full"), pc.kv_heads("window")
    G, Gw = Hq // Hkv, Hq // wrong
    want = (np.arange(Hq) // Gw) % Hkv        # the KV head each head reads
    order = np.argsort(want, kind="stable")   # slot s <- head order[s]
    assert (want[order] == np.arange(Hq) // G).all()

    def heads(stack):
        L, D = stack["wq"].shape[:2]
        wq = stack["wq"].reshape(L, D, Hq, -1)[:, :, order].reshape(
            stack["wq"].shape)
        wo = stack["wo"].reshape(L, Hq, -1, D)[:, order].reshape(
            stack["wo"].shape)
        return {**stack, "wq": wq, "wo": wo}

    blocks = {**weights["blocks"], "full": heads(weights["blocks"]["full"])}
    return {**weights, "dense": heads(weights["dense"]), "blocks": blocks}


def _config(**changes):
    return (lambda pc, page: dataclasses.replace(pc, **changes), None)


# name -> (program config, page size -> program config | None,
#          program config, weights -> the program's weights | None)
CONTROLS = {
    "sink-dropped": _config(sinks=False),
    "window-page-wider": (lambda pc, page: dataclasses.replace(
        pc, window=pc.window + page), None),
    "value-scale-dropped": _config(value_scale=1.0),
    "rope-whole-head": _config(rope_dims=0),
    "one-theta": (lambda pc, page: dataclasses.replace(
        pc, full_rope_theta=pc.rope_theta), None),
    "bias-ignored": _config(selection_bias=False),
    "full-as-eight-kv-heads": (None, _as_eight_kv_heads),
    "none": (None, None),
}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls",
                   default=",".join(n for n in CONTROLS if n != "none"))
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--rehearsal", action="store_true",
                   help="CPU walk-through of this tool (tests only)")
    p.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
                   help=argparse.SUPPRESS)
    a = p.parse_args()
    if a.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    c = R.load_cell(ROOT, a.workload, a.manifest)
    R.place_compile_cache(ROOT)
    R.device_doc(c["cell"]["chips"], a.rehearsal)
    adapters = importlib.import_module(
        f"benchmark.adapters.{c['cfg']['adapter']}")
    built, build = adapters.Adapter._program_config, adapters.Adapter.build
    page = c["cfg"]["engine"]["page_size"]
    for name in a.controls.split(","):
        alter, reweigh = CONTROLS[name]
        # the file's ring is checked against the file's window (``built``);
        # the program then runs the altered config on the altered weights,
        # the reference the seeded ones
        adapters.Adapter._program_config = (
            lambda self, f=alter: f(built(self), page)) if alter else built
        adapters.Adapter.build = (
            lambda self, weights, f=reweigh: build(
                self, f(built(self), weights))) if reweigh else build
        for seed in (int(s) for s in a.seeds.split(",")):
            args = argparse.Namespace(seed=seed, seconds=a.seconds, trace=0,
                                      rehearsal=a.rehearsal)
            res = R.run_cell(args, c)
            run = res.pop("_run")
            print(json.dumps({
                "sink_control": a.workload, "control": name, "seed": seed,
                "correct": res["correct"], "attempted": res["attempted"],
                "failed": res["failed"], **run["values"], **run["numbers"]}),
                flush=True)


if __name__ == "__main__":
    main()
