"""The controls that show the output check SEES each mechanism of the
linear-attention family: run a cell with the PROGRAM wrong in one thing (the
reference keeps the file's mathematics and the seeded weights), one line a
control and seed:

    python3 benchmark/tools/gdn_control.py --workload <cell> --seeds 1,2 \
        [--controls state-reset,delta-dropped] [--seconds 12]

``CONTROLS`` maps a name to what is altered for the program alone: its config,
its weights, or a function of ``models.linear_attn_moe`` (put back before the
next control). Everything else is ``run.py``'s path: the same engine, traffic
and check. A check that holds a mechanism reads ``correct: false`` under its
control by at least one of its limits; the readings beside the sound ones go
into the configuration's ``check.set_from``. ``--controls none`` is a sound run
through the same tool. ``setup_s`` of these runs means nothing (one process,
many systems).

- ``mixer-zeroed``: the linear layers' mixer adds nothing (``w_out`` zero);
- ``state-reset``: every prefill chunk starts from a zero state, as if the
  slot's state were lost between chunks (a prompt of one chunk is served
  soundly);
- ``bf16-state``: the recurrent state is kept in bfloat16 between tokens;
- ``no-decay``: ``alpha = 1``; ``beta-one``: ``beta = 1``;
- ``delta-dropped``: ``u = beta v``, the state never read before the write;
- ``gate-dropped``: the full layers' output gate left out;
- ``rope-whole-head``: rotary on all of the head, not its first quarter;
- ``norm-plain-weight``: every zero-centred norm's ``1 + w`` read as ``w``;
- ``shared-gate-dropped``: the shared expert ungated;
- ``sigmoid-routing``: sigmoid scores over their sum in softmax's place."""
import argparse
import dataclasses
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import run as R  # noqa: E402


def _zero_w_out(pc, weights):
    import jax.numpy as jnp
    lin = weights["blocks"]["linear"]
    blocks = {**weights["blocks"],
              "linear": {**lin, "w_out": jnp.zeros_like(lin["w_out"])}}
    return {**weights, "blocks": blocks}


def _patches(lm) -> dict:
    """name -> {attribute of ``models.linear_attn_moe``: its wrong value}."""
    import jax
    import jax.numpy as jnp
    from triton_dist_tpu.models.expert_share import sigmoid_route
    sound, gate = lm.decay_and_beta, lm.shared_gate

    def plain_weight(x, w, eps):
        x32 = x.astype(jnp.float32)
        rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
        return ((x32 * rms) * w).astype(x.dtype)

    # (a parameter leaf the altered program no longer READS would be pruned
    # from it, and the engine's layout commit wants every leaf: a wrong value
    # is therefore the sound one times zero, which XLA does not fold)
    def no_decay(p, a, b):
        g, beta = sound(p, a, b)
        return g * 0.0, beta

    def beta_one(p, a, b):
        g, beta = sound(p, a, b)
        return g, jnp.ones_like(beta)

    return {
        "state-reset": {"chunk_starts_fresh": lambda pos0: jnp.bool_(True)},
        "bf16-state": {"STATE_DTYPE": jnp.bfloat16},
        "no-decay": {"decay_and_beta": no_decay},
        "beta-one": {"decay_and_beta": beta_one},
        "gate-dropped": {"output_gate": lambda attn, gate: attn},
        "norm-plain-weight": {
            "zc_rmsnorm": plain_weight,
            "LINEAR_ATTN_MOE": dataclasses.replace(lm.LINEAR_ATTN_MOE,
                                                   norm=plain_weight)},
        "shared-gate-dropped": {
            "shared_gate": lambda p, h: gate(p, h) * 0.0 + 1.0},
        "sigmoid-routing": {"softmax_route": sigmoid_route},
    }


# name -> (program config -> program config | None,
#          program config, weights -> the program's weights | None)
CONTROLS = {
    "mixer-zeroed": (None, _zero_w_out),
    "state-reset": (None, None),
    "bf16-state": (None, None),
    "no-decay": (None, None),
    "beta-one": (None, None),
    "delta-dropped": (lambda pc: dataclasses.replace(pc, delta_rule=False),
                      None),
    "gate-dropped": (None, None),
    "rope-whole-head": (lambda pc: dataclasses.replace(
        pc, rope_dims=pc.head_dim), None),
    "norm-plain-weight": (None, None),
    "shared-gate-dropped": (None, None),
    "sigmoid-routing": (None, None),
    "none": (None, None),
}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls",
                   default=",".join(n for n in CONTROLS if n != "none"))
    p.add_argument("--seconds", type=float, default=12.0)
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
    from triton_dist_tpu.models import linear_attn_moe as lm
    built, build = adapters.Adapter._program_config, adapters.Adapter.build
    patches = _patches(lm)
    sound = {name: getattr(lm, name)
             for wrong in patches.values() for name in wrong}
    for control in a.controls.split(","):
        alter, reweigh = CONTROLS[control]

        def altered(self, control=control, alter=alter):
            # the file's state is checked against the SOUND program's
            # (``built``); the program then runs wrong in one thing
            for name, value in sound.items():
                setattr(lm, name, value)
            pc = built(self)
            for name, value in patches.get(control, {}).items():
                setattr(lm, name, value)
            return alter(pc) if alter else pc

        adapters.Adapter._program_config = altered
        adapters.Adapter.build = (
            lambda self, weights, f=reweigh: build(
                self, f(built(self), weights))) if reweigh else build
        for seed in (int(s) for s in a.seeds.split(",")):
            args = argparse.Namespace(seed=seed, seconds=a.seconds, trace=0,
                                      rehearsal=a.rehearsal)
            res = R.run_cell(args, c)
            run = res.pop("_run")
            print(json.dumps({
                "gdn_control": a.workload, "control": control, "seed": seed,
                "correct": res["correct"], "attempted": res["attempted"],
                "failed": res["failed"], **run["values"], **run["numbers"]}),
                flush=True)


if __name__ == "__main__":
    main()
