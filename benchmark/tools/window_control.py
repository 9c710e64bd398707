"""The control that shows the output check SEES the window: run a cell of a
window-attention configuration with the program's window off by some pages
(the reference keeps the file's), one line a seed:

    python3 benchmark/tools/window_control.py --workload <cell> --seeds 1,2 \
        [--pages-off 1] [--seconds 20]

The adapter's program config is rebuilt with ``window + pages_off x page_size``
(rings sized for it); everything else is ``run.py``'s path: the same engine,
traffic and check against the unchanged reference. A check that holds the
mechanism reads ``correct: false`` here by at least one of its limits; the
readings beside the sound ones go into the configuration's ``check.set_from``.
``--pages-off 0`` is a sound run through the same tool."""
import argparse
import dataclasses
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import run as R  # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--pages-off", type=int, default=1)
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
    built = adapters.Adapter._program_config
    off = a.pages_off * c["cfg"]["engine"]["page_size"]

    def widened(self):
        # the file's ring is checked against the file's window (``built``);
        # the program then runs another
        pc = built(self)
        return dataclasses.replace(pc, window=pc.window + off)

    adapters.Adapter._program_config = widened
    for seed in (int(s) for s in a.seeds.split(",")):
        args = argparse.Namespace(seed=seed, seconds=a.seconds, trace=0,
                                  rehearsal=a.rehearsal)
        res = R.run_cell(args, c)
        run = res.pop("_run")
        print(json.dumps({
            "window_control": a.workload, "pages_off": a.pages_off,
            "seed": seed, "correct": res["correct"],
            "attempted": res["attempted"], "failed": res["failed"],
            **run["values"], **run["numbers"]}), flush=True)


if __name__ == "__main__":
    main()
