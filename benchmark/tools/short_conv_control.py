"""The controls that show the output check SEES each mechanism of the
short-convolution family: run a cell with the PROGRAM wrong in one thing (the
reference keeps the file's mathematics and the seeded weights), one line a
control and seed:

    python3 benchmark/tools/short_conv_control.py --workload <cell> \
        --seeds 1,2 [--controls state-reset,taps-reversed] [--seconds 12]

``patches`` maps a name to the functions of ``models.short_conv_moe`` that are
replaced for the program alone (``mock.patch.multiple``: put back before the
next control). Everything
else is ``run.py``'s path: the same engine, traffic and check. A check that
holds a mechanism reads ``correct: false`` under its control by at least one
of its limits; the readings beside the sound ones go into the configuration's
``check.set_from``. ``--controls none`` is a sound run through the same tool.
``setup_s`` of these runs means nothing (one process, many systems).

- ``state-reset``: every prefill chunk starts from zero rows, as if the
  slot's carried rows were lost between chunks (a prompt of one chunk is
  served soundly);
- ``taps-reversed``: the conv's taps in the other order;
- ``c-gate-dropped``: ``out = y W_out``, the output gate C left out;
- ``bias-ignored``: the four experts chosen by score alone;
- ``bias-in-weights``: the chosen experts weighed by score + bias;
- ``qk-norm-dropped``: q and k go to rotary as projected;
- ``rope-half-head``: rotary on the first 32 of a head's 64 dims;
- ``renorm-dropped``: the chosen experts weighed by their raw scores."""
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


def patches(sc) -> dict:
    """name -> {attribute of ``models.short_conv_moe``: its wrong value}.
    (A parameter leaf the altered program no longer READS would be pruned
    from it, and the engine's layout commit wants every leaf: a leaf that is
    ignored is read times zero, which XLA does not fold.)"""
    import jax
    import jax.numpy as jnp
    from triton_dist_tpu.models.llama import rope

    def scores(cfg, p, h, pick_by_bias=True):
        s = jax.nn.sigmoid(h.astype(jnp.float32) @ p["w_router"])
        b = p["router_bias"] if pick_by_bias else p["router_bias"] * 0.0
        _, ids = jax.lax.top_k(s + b, cfg.topk)
        return ids, jnp.take_along_axis(s, ids, -1), \
            jnp.take_along_axis(s + p["router_bias"], ids, -1)

    def over_sum(w):
        return w / (jnp.sum(w, -1, keepdims=True) + sc.ROUTE_EPS)

    def bias_ignored(cfg, p, h):
        ids, s, _ = scores(cfg, p, h, pick_by_bias=False)
        return ids, over_sum(s)

    def bias_in_weights(cfg, p, h):
        ids, _, sb = scores(cfg, p, h)
        return ids, over_sum(sb)

    def renorm_dropped(cfg, p, h):
        ids, s, _ = scores(cfg, p, h)
        return ids, s

    def rope_half(cfg, x, positions):
        half = x.shape[-1] // 2
        return jnp.concatenate(
            [rope(x[:, None, :, :half], positions, cfg.rope_theta)[:, 0],
             x[..., half:]], -1)

    return {
        "state-reset": {"chunk_starts_fresh": lambda pos0: jnp.bool_(True)},
        "taps-reversed": {"taps_of": lambda p: p["conv_w"][::-1]},
        "c-gate-dropped": {"output_gate": lambda y, c: y},
        "bias-ignored": {"route": bias_ignored},
        "bias-in-weights": {"route": bias_in_weights},
        "qk-norm-dropped": {
            "normed_heads": lambda x, w, eps: x * (w * 0.0 + 1.0).astype(
                x.dtype)},
        "rope-half-head": {"rotated": rope_half},
        "renorm-dropped": {"route": renorm_dropped},
        "none": {},
    }


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", default=None,
                   help="comma-separated; default: every control but none")
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
    # the adapter imports the family: a program without it fails here
    importlib.import_module(f"benchmark.adapters.{c['cfg']['adapter']}")
    from triton_dist_tpu.models import short_conv_moe as sc
    from triton_dist_tpu.serving import programs
    table = patches(sc)
    for control in (a.controls.split(",") if a.controls
                    else [n for n in table if n != "none"]):
        for seed in (int(s) for s in a.seeds.split(",")):
            args = argparse.Namespace(seed=seed, seconds=a.seconds, trace=0,
                                      rehearsal=a.rehearsal)
            # the programs are memoised on the config: an altered function is
            # no part of the key, so every control traces its own
            programs._MEMO.clear()
            wrong = table[control]
            with mock.patch.multiple(sc, **wrong) if wrong \
                    else contextlib.nullcontext():
                res = R.run_cell(args, c)
            run = res.pop("_run")
            print(json.dumps({
                "short_conv_control": a.workload, "control": control,
                "seed": seed, "correct": res["correct"],
                "attempted": res["attempted"], "failed": res["failed"],
                **run["values"], **run["numbers"]}), flush=True)


if __name__ == "__main__":
    main()
