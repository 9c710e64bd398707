"""Print what a person looks at first in a trace: devices, host spans,
programs and operations by time. Usage:
    python3 benchmark/tools/describe_trace.py [file.xplane.pb | directory]
(default: the newest trace under ``.bench_trace`` in the checkout)."""
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import trace as T  # noqa: E402


def main():
    where = sys.argv[1] if len(sys.argv) > 1 else os.path.join(ROOT, ".bench_trace")
    if os.path.isdir(where):
        where = sorted(glob.glob(os.path.join(where, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)[-1]
    from jax.profiler import ProfileData
    data = ProfileData.from_file(where)
    for plane in data.planes:
        print(json.dumps({"plane": plane.name, "lines": [
            [ln.name, sum(1 for _ in ln.events)] for ln in plane.lines][:40]}))
    tr = T.load(where)
    print(json.dumps(T.describe(tr)))
    print(json.dumps({"idle_gaps": T.idle_gaps(tr)}))


if __name__ == "__main__":
    main()
