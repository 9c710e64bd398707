"""One plain run of a cell through ``run.py``'s own ``run_cell``, and one more
line: the program's own split of a step over the window, without a trace
(decode wait = ``step_device_s``, chunk wait = ``prefill_stall_s``, the host's
remainder) and, where the family counts them, the routed assignments and the
held experts touched a decode token-step. Same arguments as ``run.py``:

    python3 benchmark/tools/step_split.py --workload <cell> --seed <n> \
        --seconds 51 --trace 0

For telling where run-to-run spread of a step comes from: PR 26 found the
seed moving the held experts' load with it (PERF.md section 6)."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import run as R  # noqa: E402


def main():
    a = R.parse(sys.argv[1:])
    if a.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    c = R.load_cell(ROOT, a.workload, a.manifest)
    R.place_compile_cache(ROOT)
    R.device_doc(c["cell"]["chips"], a.rehearsal)
    res = R.run_cell(a, c)
    w = res.pop("_run")["counters_window"]
    n, steps = w["dispatches"], max(w["decode_steps"], 1)
    chunks = max(w["prefill_stall_s.count"], 1)
    ms = lambda total, per: 1e3 * total / per          # noqa: E731
    print(json.dumps({
        "step_split": a.workload, "seed": a.seed, "correct": res["correct"],
        "failed": res["failed"],
        **{k: v["value"] for k, v in res["metrics"].items()},
        "dispatches": n, "wall_ms_per_dispatch": ms(a.seconds, n),
        "decode_wait_ms": ms(w["step_device_s.total"], n),
        "chunk_wait_ms": ms(w["prefill_stall_s.total"], chunks),
        "engine_host_ms": ms(w["step_host_s.total"]
                             - w["prefill_stall_s.total"], n),
        "active_slots_mean": w["active_slots.total"]
        / max(w["active_slots.count"], 1),
        "moe_local_rows_per_step": w.get("moe_local_rows", 0) / steps,
        "moe_experts_touched_per_step": w.get("moe_experts_touched", 0)
        / steps}), flush=True)


if __name__ == "__main__":
    main()
