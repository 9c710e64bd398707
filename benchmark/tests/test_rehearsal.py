"""CPU walk-through of the harness (tiny configurations, interpret-mode
kernels): ``run.py`` end to end, the output check's control, and a run with
the timed path broken underneath. Nothing here is a measurement: every metric
is printed under a ``cpu_rehearsal.`` name. The walks through the interpreter
take minutes and are marked ``slow``: ``python3 -m pytest benchmark/tests -q -m
slow`` runs them too."""
import argparse
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest as mf
from conftest import REHEARSAL, ROOT

RUN = [sys.executable, os.path.join(ROOT, "benchmark", "run.py")]
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def run_py(*argv, cwd=ROOT, script=RUN):
    return subprocess.run([*script, *argv], cwd=cwd, env=ENV, text=True,
                          capture_output=True, timeout=900)


def last_line(proc):
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.parametrize("cell,trace,seconds", [
    ("tiny-steady", 0, 3), ("tiny-steady", 1, 3), ("tiny-backlog", 0, 30)])
def test_run_py_end_to_end(cell, trace, seconds):
    """One device, and a 1x2x2 mesh of four virtual devices (whose
    interpreted step takes ten seconds and more, hence the longer window)."""
    p = run_py("--workload", cell, "--seed", str(2**31 + 9),
               "--seconds", str(seconds),
               "--trace", str(trace), "--rehearsal", "--manifest", REHEARSAL)
    assert p.returncode == 0, p.stderr[-2000:]
    res = last_line(p)
    want = {"correct", "attempted", "failed", "metrics", "device", "rehearsal"}
    assert set(res) == want | ({"breakdown"} if trace else set())
    assert res["rehearsal"] is True and res["correct"] is True
    assert res["attempted"] >= 1
    assert res["device"]["platform"] == "cpu"
    m = mf.load(REHEARSAL)
    section = "per_layer" if trace else "end_to_end"
    names = {x["name"] for x in mf.metrics_of(m, cell, section)}
    got = set(res["metrics"])
    assert got and all(k.startswith("cpu_rehearsal.") for k in got)
    assert {k.split(".", 1)[1] for k in got} <= names
    if not trace:                      # every end-to-end metric of the cell
        assert {k.split(".", 1)[1] for k in got} == names
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()[:-1]]
    checks = [x for x in lines if "check" in x]
    assert {"gap_max", "gap_mean", "compiles_in_window"} <= {
        x["check"] for x in checks}
    assert all("limit" in x and "value" in x for x in checks)


def test_no_accelerator_no_result():
    p = run_py("--workload", "mistral7b-chat-steady", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_alone_in_a_directory_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    script = [sys.executable, str(tmp_path / "benchmark" / "run.py")]
    p = run_py("--workload", "tiny-steady", "--seed", "1", "--seconds", "1",
               "--trace", "0", "--rehearsal", "--manifest",
               str(tmp_path / "benchmark/tests/rehearsal/BENCHMARK.json"),
               cwd=tmp_path, script=script)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.slow
def test_the_sweep_tool_reads_knee_columns_and_the_control():
    """``tools/sweep.py``: two rates in one process, each with a seed of its
    own; every line has the knee's columns and the check's readings."""
    tool = [sys.executable, os.path.join(ROOT, "benchmark", "tools", "sweep.py")]
    p = run_py("--workload", "tiny-check", "--rates", "0.6,0.9", "--seconds",
               "4", "--seed", "31", "--rehearsal", "--manifest", REHEARSAL,
               script=tool)
    assert p.returncode == 0, p.stderr[-2000:]
    rows = [json.loads(x) for x in p.stdout.strip().splitlines()]
    rows = [r for r in rows if "sweep" in r]
    assert [r["rate_rps"] for r in rows] == [0.6, 0.9]
    assert [r["seed"] for r in rows] == [31, 32]
    for r in rows:
        assert {"arrived", "queue_at_close", "ttft_p95_ms", "gap_max",
                "gap_mean", "control_gap_max", "control_gap_mean"} <= set(r)
        assert r["correct"] is True


@pytest.fixture(scope="module")
def built():
    """One tiny system for the in-process tests."""
    from benchmark import run as R
    from benchmark.tools.sweep import build
    c = R.load_cell(ROOT, "tiny-check", REHEARSAL)
    sut, weights, ref = build(c, 11, rehearsal=True)
    return R, c, sut, weights, ref


def _args(seed):
    return argparse.Namespace(seed=seed, seconds=4.0, trace=0, rehearsal=True)


@pytest.mark.slow
def test_the_control_fails_the_limits_and_sound_runs_pass(built):
    """The reference computed in float8 stands in the program's place: at
    each position of the same prompts and tokens the token IT puts first is
    held to the same limits, and fails one at least, on every seed."""
    R, c, sut, weights, ref = built
    from benchmark.tools.sweep import drain
    limits = c["cfg"]["check"]["limits"]
    for seed in (11, 12, 13):
        w = R.make_weights(ref, c["cfg"], seed, None)
        sut.set_weights(w)
        res = R.run_cell(_args(seed), c, sut, w, control="fp8")
        n = res["_run"]["numbers"]
        drain(sut)
        assert res["correct"] is True
        assert all(n[k] <= limits[k] for k in limits), n
        assert any(n["control_" + k] > limits[k] for k in limits), n


@pytest.mark.slow
def test_a_broken_timed_path_comes_out_not_correct(built, monkeypatch):
    """A token altered where it is produced: the decode program's token slab
    is shifted before the engine reads it. Everything else of a run is
    driven as it is."""
    R, c, sut, weights, ref = built
    from benchmark.tools.sweep import drain
    sut.set_weights(weights)
    vocab = c["cfg"]["vocab_size"]
    sound = sut.eng._step

    def broken(*a):
        out = sound(*a)
        return ((out[0] + 7) % vocab,) + tuple(out[1:])

    monkeypatch.setattr(sut.eng, "_step", broken)
    res = R.run_cell(_args(21), c, sut, weights)
    drain(sut)
    assert res["correct"] is False
    monkeypatch.undo()
    res = R.run_cell(_args(21), c, sut, weights)
    drain(sut)
    assert res["correct"] is True
