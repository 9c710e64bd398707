"""Read the output check's numbers for a cell, sound runs beside the float8
control, one line a seed:

    python3 benchmark/tools/check_limits.py --workload <cell> --seeds 1,2,3 \
        [--seconds 20]

The method of ``tools/sweep.py`` (the same ``run_cell`` with ``control="fp8"``),
but every seed builds and closes a system of its own: ``sweep.py`` keeps one
engine alive between its rates, and a configuration that fills the chip (the
latent one: 14 GB of 16.9) leaves the reference no room beside the engine's
pool. A limit of the check goes above the largest sound reading and below the
smallest control reading of the same name (PERF.md gives both). ``setup_s`` of
these runs means nothing (one process, many systems)."""
import argparse
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
    for seed in (int(s) for s in a.seeds.split(",")):
        args = argparse.Namespace(seed=seed, seconds=a.seconds, trace=0,
                                  rehearsal=a.rehearsal)
        res = R.run_cell(args, c, control="fp8")
        run = res.pop("_run")
        print(json.dumps({
            "limits_of": a.workload, "seed": seed, "correct": res["correct"],
            "attempted": res["attempted"], "failed": res["failed"],
            **run["values"], **run["numbers"],
            "memory_peak_bytes": res["device"]["memory_peak_bytes"]}),
            flush=True)


if __name__ == "__main__":
    main()
