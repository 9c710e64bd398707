"""The controls that show the output check SEES each mechanism of the looped
family: run a cell with the PROGRAM wrong in one thing (the reference keeps the
file's mathematics and the seeded weights), one line a control and seed:

    python3 benchmark/tools/loop_control.py --workload <cell> --seeds 1,2 \\
        [--controls three-walks,one-cache] [--seconds 12] [--engine '{...}']

``patches`` maps a name to the functions of ``models.looped`` that are replaced
for the program alone (``mock.patch.multiple``: put back before the next
control). Everything else is ``run.py``'s path: the same engine, traffic and
check. A check that holds a mechanism reads ``correct: false`` under its
control by at least one of its limits; the readings beside the sound ones go
into the configuration's ``check.set_from``. ``--controls none`` is a sound
run through the same tool; ``--engine`` lays keys over the file's ``engine``
block (a sound run at another number of slots). ``setup_s`` of these runs
means nothing (one process, many systems).

- ``three-walks``: a token walks the stack once fewer than the file says (the
  head takes the rows of the walk before the last);
- ``one-cache``: every walk writes and reads walk 0's planes (one cache entry
  a layer, whatever the walk);
- ``decode-reads-last``: decode rows walk the LAST walk's planes in every
  walk (the published approximation the configuration does NOT take); a
  chunk's rows are served soundly;
- ``walk-norm-dropped``: the stream goes on into the next walk, to the gate
  and to the head as the last layer left it;
- ``out-norms-dropped``: a branch's output joins the stream as projected (no
  second slice of the sandwich);
- ``rope-by-walk``: rotary positions advanced by the walk's number, queries
  AND keys. Rotary is relative and a plane holds ONE walk's keys, so this is
  the same mathematics: it must read SOUND (a control of the controls);
- ``rope-queries-by-walk``: the queries' positions alone advanced by the
  walk's number."""
import argparse
import contextlib
import importlib
import json
import os
import sys
from unittest import mock

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import run as R  # noqa: E402


def patches(lp) -> dict:
    """name -> {attribute of ``models.looped``: its wrong value}. (A parameter
    leaf the altered program no longer READS would be pruned from it, and the
    engine's layout commit wants every leaf: a leaf that is ignored is read
    times zero, which XLA does not fold.)"""
    def walk_of(cfg, plane):
        return plane // cfg.n_layers

    return {
        "three-walks": {"n_walks": lambda cfg: cfg.n_walks - 1},
        "one-cache": {
            "write_plane": lambda cfg, plane: plane % cfg.n_layers,
            "read_plane": lambda cfg, plane, shared: plane % cfg.n_layers},
        "decode-reads-last": {
            "read_plane": lambda cfg, plane, shared: plane if shared else (
                plane % cfg.n_layers + (cfg.n_walks - 1) * cfg.n_layers)},
        "walk-norm-dropped": {
            "walk_norm": lambda cfg, params, x: x * (
                params["final_norm"] * 0.0 + 1.0).astype(x.dtype)},
        "out-norms-dropped": {
            "branch_norm": lambda x, w, eps: x * (w * 0.0 + 1.0).astype(
                x.dtype)},
        "rope-by-walk": {
            "rotary_positions": lambda cfg, pos, plane, of:
                pos + walk_of(cfg, plane)},
        "rope-queries-by-walk": {
            "rotary_positions": lambda cfg, pos, plane, of:
                pos + walk_of(cfg, plane) * (of == "q")},
        "none": {},
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", default=None,
                   help="comma-separated; default: every control but none")
    p.add_argument("--seconds", type=float, default=12.0)
    p.add_argument("--engine", default=None,
                   help="JSON laid over the file's engine block")
    p.add_argument("--rehearsal", action="store_true",
                   help="CPU walk-through of this tool (tests only)")
    p.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
                   help=argparse.SUPPRESS)
    a = p.parse_args()
    if a.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    c = R.load_cell(ROOT, a.workload, a.manifest)
    if a.engine:
        c["cfg"]["engine"].update(json.loads(a.engine))
    R.place_compile_cache(ROOT)
    R.device_doc(c["cell"]["chips"], a.rehearsal)
    # the adapter imports the family: a program without it fails here
    importlib.import_module(f"benchmark.adapters.{c['cfg']['adapter']}")
    from triton_dist_tpu.models import looped as lp
    from triton_dist_tpu.serving import programs
    table = patches(lp)
    for control in (a.controls.split(",") if a.controls
                    else [n for n in table if n != "none"]):
        for seed in (int(s) for s in a.seeds.split(",")):
            args = argparse.Namespace(seed=seed, seconds=a.seconds, trace=0,
                                      rehearsal=a.rehearsal)
            # the programs are memoised on the config: an altered function is
            # no part of the key, so every control traces its own
            programs._MEMO.clear()
            wrong = table[control]
            with mock.patch.multiple(lp, **wrong) if wrong \
                    else contextlib.nullcontext():
                res = R.run_cell(args, c)
            run = res.pop("_run")
            print(json.dumps({
                "loop_control": a.workload, "control": control,
                "seed": seed, "correct": res["correct"],
                "attempted": res["attempted"], "failed": res["failed"],
                **run["values"], **run["numbers"],
                "memory_peak_bytes": res["device"]["memory_peak_bytes"],
                "loop_early_exit_rows": run["counters_window"].get(
                    "loop_early_exit_rows")}), flush=True)


if __name__ == "__main__":
    main()
