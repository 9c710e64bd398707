"""Self-check of BENCHMARK.json: what the driver would refuse before any run
is caught here."""
import copy
import json
import os

import pytest

from benchmark import manifest as mf
from conftest import REHEARSAL


def test_the_manifest_passes_its_self_check():
    assert mf.check(mf.load()) == []
    assert mf.check(mf.load(REHEARSAL)) == []


def test_every_file_a_cell_names_exists_and_parses():
    m = mf.load()
    for c in m["configs"]:
        with open(os.path.join(mf.ROOT, c["file"])) as f:
            cfg = json.load(f)
        for k in c["reduced"]:
            assert k in cfg, f"{c['name']}: reduced key {k} not in its file"
        assert os.path.isfile(os.path.join(
            mf.ROOT, "benchmark", "references", cfg["reference"] + ".py"))
        assert os.path.isfile(os.path.join(
            mf.ROOT, "benchmark", "adapters", cfg["adapter"] + ".py"))
    for w in m["workloads"]:
        with open(mf.traffic_file(mf.ROOT, m["paths"], w["traffic"])) as f:
            json.load(f)
        assert mf.metrics_of(m, w["name"], "per_layer")


# applied to the rehearsal manifest: three cells, one of them on four chips
FAULTS = {
    "a name with a space": lambda m: m["workloads"][0].update(name="a b"),
    "a unit with a space": lambda m: m["end_to_end"][0].update(unit="tokens per s"),
    "a source over 200 characters": lambda m: m["configs"][0].update(source="x" * 201),
    "a missing configuration file": lambda m: m["configs"][0].update(
        file="benchmark/tests/rehearsal/configs/none.json"),
    "a metric that moves what its cell does not report": lambda m:
        m["per_layer"][0].update(moves="out_tok_s", workloads=["tiny-steady"]),
    "a second four-chip cell among three": lambda m: m["workloads"][0].update(chips=4),
    "a stray key on a metric": lambda m: m["per_layer"][0].update(why="x"),
    "a width in reduced": lambda m: m["configs"][0].update(reduced=["hidden_size"]),
    "a bound over 0.1": lambda m: m["end_to_end"][0].update(bound=0.2),
    "run_seconds over the limit": lambda m: m.update(run_seconds=52),
    "a traffic mix without a file": lambda m: m["workloads"][0].update(traffic="nope"),
    "a per-layer metric without a reader": lambda m: m["per_layer"][0].update(name="nope"),
    "the same pair twice": lambda m: m["workloads"][2].update(traffic="tiny-steady"),
    "no setup_s": lambda m: m["end_to_end"].pop(),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_self_check_catches(fault):
    m = copy.deepcopy(mf.load(REHEARSAL))
    FAULTS[fault](m)
    assert mf.check(m), fault
