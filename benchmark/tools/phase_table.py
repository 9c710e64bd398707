"""A step's phases out of a trace: for every ``engine.*`` span name how often it
ran, its milliseconds a step and the device's idle milliseconds under it, then
the idle time by where the host was (``program_spans.split``) and the idle
time under the two waits by where in the wait it falls. A step is an
``engine.step`` span that holds an ``engine.dispatch``. Usage:
    python3 benchmark/tools/phase_table.py [file.xplane.pb | directory]
(default: the newest trace under ``.bench_trace`` in the checkout, which is
what the last ``run.py --trace 1`` wrote)."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import program_spans as P, trace as T  # noqa: E402


def main():
    where = sys.argv[1] if len(sys.argv) > 1 else P.TRACE_DIR
    if os.path.isdir(where):
        where = P.newest_xplane(where)
    tr, spans = T.load(where), P.load_spans(where)
    got = P.split(tr, spans)
    if got is None:
        print(json.dumps({"phase_table": None, "file": where,
                          "why": "no device plane or no engine.step span "
                                 "that holds an engine.dispatch"}))
        return
    n = got["steps"]
    ms = lambda s: round(s * 1e3 / n, 4)                # noqa: E731
    print(json.dumps({
        "file": where, "steps": n, "window_s": tr.t1_s - tr.t0_s,
        "busy_s": T.busy_s(tr),
        "phases": [{"span": r["span"], "count": r["count"],
                    "ms_a_step": ms(r["seconds"]),
                    "idle_ms_a_step": ms(r["idle_s"])}
                   for r in P.phase_table(tr, spans)],
        "idle_ms_a_step": {k[:-2]: ms(v) for k, v in got.items()
                           if k.endswith("_s")},
        "idle_in_waits_ms_a_step": [
            {k.removesuffix("_s"): v if k == "span" else ms(v)
             for k, v in r.items()} for r in P.wait_table(tr, spans)]}))


if __name__ == "__main__":
    main()
