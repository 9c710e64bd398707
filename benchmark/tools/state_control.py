"""The controls that show the output check SEES the recurrent state: run a
cell of a state-space configuration with the PROGRAM altered (the reference
keeps the file's mathematics), one line a seed:

    python3 benchmark/tools/state_control.py --workload <cell> --seeds 1,2 \
        --control mixer-zeroed|state-reset|bf16-state|none [--seconds 20]

- ``mixer-zeroed``: the mixer's branch adds nothing (the program's
  ``ssm_out_multiplier`` is 0): a block is attention and MLP alone;
- ``state-reset``: every prefill chunk starts from a zero state (and zero
  conv rows), as if the slot's state were lost between chunks; a prompt of one
  chunk is served soundly, so only requests whose prompts span chunks differ;
- ``bf16-state``: the recurrent state is kept in bfloat16 between tokens
  (updated in float32, rounded on the way back);
- ``none``: a sound run through the same tool.

Everything else is ``run.py``'s path: the same engine, traffic and check
against the unchanged reference. A check that holds the mechanism reads
``correct: false`` by at least one of its limits; the readings beside the
sound ones go into the configuration's ``check.set_from``. ``setup_s`` of
these runs means nothing (one process, many systems)."""
import argparse
import dataclasses
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import run as R  # noqa: E402

CONTROLS = ("mixer-zeroed", "state-reset", "bf16-state", "none")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", required=True, choices=CONTROLS)
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
    import jax.numpy as jnp
    from triton_dist_tpu.models import hybrid_ssm
    if a.control == "state-reset":
        hybrid_ssm.chunk_starts_fresh = lambda pos0: jnp.bool_(True)

    def altered(self):
        # the file's state is checked against the file's (``built``); the
        # program then runs another
        hybrid_ssm.STATE_DTYPE = jnp.float32
        pc = built(self)
        if a.control == "mixer-zeroed":
            return dataclasses.replace(pc, ssm_out_multiplier=0.0)
        if a.control == "bf16-state":
            hybrid_ssm.STATE_DTYPE = jnp.bfloat16
        return pc

    adapters.Adapter._program_config = altered
    for seed in (int(s) for s in a.seeds.split(",")):
        args = argparse.Namespace(seed=seed, seconds=a.seconds, trace=0,
                                  rehearsal=a.rehearsal)
        res = R.run_cell(args, c)
        run = res.pop("_run")
        print(json.dumps({
            "state_control": a.workload, "control": a.control, "seed": seed,
            "correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], **run["values"], **run["numbers"]}),
            flush=True)


if __name__ == "__main__":
    main()
