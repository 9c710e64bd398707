"""Find a cell's knee and read the output check's numbers, at several fixed
rates in ONE process (one set-up; every rate gets a seed, weights and tokens
of its own).

    python3 benchmark/tools/sweep.py --workload <cell> --rates 2,2.5,3 \
        [--seconds 20] [--seed 7]

For each rate the cell's mix runs with ``rate_rps`` overridden: ramp, window,
drain, then the output check with its control: the reference in float32 and in
float8 over the same sampled prompts and served tokens. One JSON line per rate.

The knee is the highest rate at which completions keep pace with arrivals to
the end of the window: the queue at window close is no deeper than at its
opening (give or take a request or two) and every window request finishes well
inside the drain cap. The mix file then takes 0.8 x that rate.

``gap_*`` are the program's readings, ``control_gap_*`` those of the token the
lower precision puts first. A limit of the check goes above the largest sound
reading and below the smallest control reading (PERF.md gives both); to read
more seeds at the cell's own rate, repeat it: ``--rates 2,2,2``."""
import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import run as R  # noqa: E402
from benchmark import check as ck  # noqa: E402
from benchmark.e2e import percentile, ttft_samples_ms  # noqa: E402


def build(c, seed, rehearsal=False):
    """(adapter, weights, reference module) of a built, warm system."""
    sut, weights = R.build_system(c, seed, rehearsal)
    return sut, weights, ck.load_reference(c["cfg"]["reference"])


def drain(sut):
    """Finish whatever a run left in the engine."""
    while sut.step():
        pass


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=7)
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
    sut, weights, ref = build(c, a.seed, a.rehearsal)
    for i, rate in enumerate(float(x) for x in a.rates.split(",")):
        seed = a.seed + i
        if i:
            weights = None
            sut.set_weights(None)            # two sets do not fit a chip
            weights = R.make_weights(ref, c["cfg"], seed,
                                     sut.weight_shardings())
            sut.set_weights(weights)
        ci = dict(c, spec=copy.deepcopy(c["spec"]))
        ci["spec"]["arrival"]["rate_rps"] = rate
        args = argparse.Namespace(seed=seed, seconds=a.seconds, trace=0,
                                  rehearsal=a.rehearsal)
        res = R.run_cell(args, ci, sut, weights, control="fp8")
        run = res["_run"]
        recs = [r for r in run["recs"] if r.counted]
        ttft = ttft_samples_ms(run["recs"])
        print(json.dumps({
            "sweep": a.workload, "rate_rps": rate, "seed": seed,
            "window_s": a.seconds, "arrived": len(recs),
            "finished_in_window": run["finished_in_window"],
            "failed": sum(1 for r in recs if r.failed or not r.done),
            "queue_at_open": run["queue_open"],
            "queue_at_close": run["queue_close"],
            "ttft_p50_ms": percentile(ttft, 50),
            "ttft_p95_ms": percentile(ttft, 95), **run["values"],
            "correct": res["correct"], **run["numbers"]}),
            flush=True)
        drain(sut)


if __name__ == "__main__":
    main()
