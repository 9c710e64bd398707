"""Spread of each metric over a set of runs, as the contract measures it: the
distance between the first and third quartile (``statistics.quantiles(values,
n=4)``) as a share of the median.

    python3 benchmark/tools/spread.py chiprun_out/c3/<cell>.*.txt [...]

Each file is the stdout of one run; the last line is the result object, the
``also`` line carries further percentiles for judging a candidate metric."""
import json
import statistics
import sys


def spread(values):
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else float("nan")


def main(files):
    cols, correct, extra = {}, [], {}
    for f in files:
        lines = [json.loads(x) for x in open(f) if x.startswith("{")]
        res = lines[-1]
        correct.append(res["correct"] and res["failed"] == 0)
        for k, v in res["metrics"].items():
            cols.setdefault(k, []).append(v["value"])
        for line in lines:
            for group, d in line.get("also", {}).items():
                if isinstance(d, dict):
                    for q, v in d.items():
                        extra.setdefault(f"{group}.p{q}", []).append(v)
                else:
                    extra.setdefault(group, []).append(d)
            if "checked" in line:
                for k in ("gap_max", "gap_mean", "reference_s"):
                    extra.setdefault(k, []).append(line["checked"].get(k))
                extra.setdefault("memory_peak_GB", []).append(
                    line["memory_peak_bytes"] / 1e9)
    print(f"{len(files)} runs, all correct and none failed: {all(correct)}")
    for name, table in (("metrics", cols), ("also", extra)):
        for k, v in table.items():
            v = [x for x in v if x is not None]
            print(f"{name:8s}{k:22s} median {statistics.median(v):12.4f}  "
                  f"spread {100 * spread(v):6.2f} %  min {min(v):.4f} "
                  f"max {max(v):.4f}")


if __name__ == "__main__":
    main(sys.argv[1:])
