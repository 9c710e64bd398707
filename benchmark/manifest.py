"""``BENCHMARK.json``: loading, look-ups by name, and the self-check that
catches in the sandbox what the driver would refuse before any run."""

from __future__ import annotations

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj\w*)_size|_dim$|_rank$"
                   r"|head_size|expan|experts_per_tok|^d_model$|^d_ff$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def load(path: str = os.path.join(ROOT, "BENCHMARK.json")) -> dict:
    with open(path) as f:
        return json.load(f)


def by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def metrics_of(manifest: dict, cell: str, section: str) -> list[dict]:
    """The metrics of ``section`` that ``cell`` reports: those that list it,
    and those with no list whose end-to-end metric the cell reports."""
    e2e = [m for m in manifest["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if section == "end_to_end":
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def traffic_file(root: str, paths, traffic: str) -> str:
    for p in paths:
        f = os.path.join(root, p, "workloads", traffic + ".json")
        if os.path.isfile(f):
            return f
    raise FileNotFoundError(f"no data file for traffic mix {traffic!r} under "
                            f"{[os.path.join(p, 'workloads') for p in paths]}")


def reader_file(root: str, paths, metric: str) -> str:
    for p in paths:
        f = os.path.join(root, p, "layer_metrics", metric + ".py")
        if os.path.isfile(f):
            return f
    raise FileNotFoundError(f"no reader for per-layer metric {metric!r}")


def check(manifest: dict, root: str = ROOT) -> list[str]:
    """Every fault found, as sentences; an empty list is a pass."""
    bad = []
    say = bad.append
    if set(manifest) != KEYS["top"]:
        say(f"top-level keys are {sorted(manifest)}, want {sorted(KEYS['top'])}")
        return bad
    if len(json.dumps(manifest)) > 64 * 1024:
        say("file is over 64 KiB")
    paths, cmd = manifest["paths"], manifest["command"]
    if not 1 <= len(paths) <= 16 or not all(PATH.match(p) for p in paths):
        say("paths: 1 to 16 relative paths of letters, digits, _ . - /")
    if not 1 <= len(cmd) <= 32:
        say("command: 1 to 32 strings")
    for w in cmd:
        if not 1 <= len(w) <= 200 or "\t" in w or "\n" in w:
            say(f"command word {w!r}: 1 to 200 characters on one line")
        if w.startswith("/") or ".." in w.split("/"):
            say(f"command word {w!r} leaves the repo")
        if "/" in w and not any(w == p or w.startswith(p + "/") for p in paths):
            say(f"command names {w!r}, a file outside paths")
    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        say("run_seconds: a whole number from 1 to 51")
    elif (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 > 43200:
        say("run_seconds: a full check of 24 cells would not fit 43200 s")

    def one_line(s, what):
        if not isinstance(s, str) or not 1 <= len(s) <= 200 \
                or "\t" in s or "\n" in s:
            say(f"{what}: 1 to 200 characters on one line, no tab")

    def names(entries, what):
        seen = set()
        for e in entries:
            n = e.get("name", "")
            if not NAME.match(n):
                say(f"{what} name {n!r} is not a name")
            if n in seen:
                say(f"{what} name {n!r} appears twice")
            seen.add(n)
        return seen

    def keys(entries, section, extra=()):
        for e in entries:
            want = KEYS[section]
            if not want <= set(e) <= want | set(extra):
                say(f"{section} entry {e.get('name')!r} has keys "
                    f"{sorted(e)}, want {sorted(want)}")

    cfgs, cells = manifest["configs"], manifest["workloads"]
    e2e, per = manifest["end_to_end"], manifest["per_layer"]
    keys(cfgs, "configs"), keys(cells, "workloads")
    keys(e2e, "end_to_end", ("workloads",))
    keys(per, "per_layer", ("workloads",))
    if bad:
        return bad
    for lst, lo, hi, what in ((cfgs, 1, 24, "configs"), (cells, 1, 24,
                              "workloads"), (e2e, 1, 16, "end_to_end"),
                              (per, 1, 128, "per_layer")):
        if not lo <= len(lst) <= hi:
            say(f"{what}: {lo} to {hi} entries")
    cfg_names = names(cfgs, "configuration")
    cell_names = names(cells, "cell")
    names(e2e + per, "metric")
    files = set()
    for c in cfgs:
        one_line(c["source"], f"source of {c['name']}")
        one_line(c["why"], f"why of {c['name']}")
        f = c["file"]
        if not any(f.startswith(p + "/") for p in paths):
            say(f"configuration file {f!r} is not under paths")
        if f in files:
            say(f"configuration file {f!r} is used twice")
        files.add(f)
        if not os.path.isfile(os.path.join(root, f)):
            say(f"configuration file {f!r} does not exist")
        if len(c["reduced"]) > 16:
            say(f"{c['name']}: reduced has over 16 keys")
        for k in c["reduced"]:
            if not NAME.match(k):
                say(f"reduced key {k!r} is not a name")
            if WIDTH.search(k):
                say(f"reduced names a width: {k!r}")
        if c["name"] not in {w["config"] for w in cells}:
            say(f"configuration {c['name']!r} is used by no cell")
    pairs = set()
    for w in cells:
        one_line(w["why"], f"why of {w['name']}")
        if w["config"] not in cfg_names:
            say(f"cell {w['name']!r} names no configuration: {w['config']!r}")
        if not NAME.match(w["traffic"]):
            say(f"traffic {w['traffic']!r} is not a name")
        if w["chips"] not in (1, 4):
            say(f"cell {w['name']!r}: chips is 1 or 4")
        if (w["config"], w["traffic"]) in pairs:
            say(f"pair {(w['config'], w['traffic'])} appears twice")
        pairs.add((w["config"], w["traffic"]))
        try:
            traffic_file(root, paths, w["traffic"])
        except FileNotFoundError as e:
            say(str(e))
    four = sum(1 for w in cells if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        say(f"{four} four-chip cells of {len(cells)}: at most a quarter, "
            "rounded down, and one always")
    e2e_names = {m["name"] for m in e2e}
    if "setup_s" not in e2e_names:
        say("end_to_end lacks setup_s")
    for m in e2e + per:
        if not UNIT.match(m["unit"]):
            say(f"unit {m['unit']!r} of {m['name']}")
        if m["better"] not in ("lower", "higher"):
            say(f"better of {m['name']}")
        if m["source"] not in SOURCES:
            say(f"source of {m['name']}: {m['source']!r}")
        for c in m.get("workloads", []):
            if c not in cell_names:
                say(f"{m['name']} lists no cell: {c!r}")
    for m in e2e:
        if m["source"] not in ("host_clock", "device_trace"):
            say(f"end-to-end {m['name']} takes host_clock or device_trace")
        b = m["bound"]
        if not (isinstance(b, (int, float)) and 0.01 <= b <= 0.1):
            say(f"bound of {m['name']}: 0.01 to 0.1")
    for m in per:
        one_line(m["layer"], f"layer of {m['name']}")
        if m["moves"] not in e2e_names:
            say(f"{m['name']} moves no end-to-end metric: {m['moves']!r}")
        if m["name"].endswith("_roofline") and m["unit"] != "%":
            say(f"{m['name']}: a roofline share has the unit %")
        try:
            reader_file(root, paths, m["name"])
        except FileNotFoundError as e:
            say(str(e))
    for w in cells:
        mine = {m["name"] for m in metrics_of(manifest, w["name"], "end_to_end")}
        if "setup_s" not in mine or len(mine) < 2:
            say(f"cell {w['name']!r} reports setup_s and one other at least")
        layer = metrics_of(manifest, w["name"], "per_layer")
        if not layer:
            say(f"cell {w['name']!r} reports no per-layer metric")
        for m in layer:
            if m["moves"] not in mine:
                say(f"{m['name']} moves {m['moves']}, which cell "
                    f"{w['name']!r} does not report")
    return bad
