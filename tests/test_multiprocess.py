"""Two-process CPU cluster integration test.

Every other test runs the single-process simulator; the reference exercises
its multi-process model in every test via torchrun (SURVEY §4). This spawns
2 coordinator-connected ``jax.distributed`` CPU processes running
tests/mp_worker.py — the only place ``process_count() == 2`` paths execute:
the env-gated bootstrap, a cross-process XLA collective, and the autotuner's
MAX consensus. One variant launches through scripts/launch.sh to cover its
env mapping (generic COORDINATOR_ADDRESS → JAX_COORDINATOR_ADDRESS).
"""

import os
import socket
import subprocess
import sys

import jax
import pytest

# The pinned 2-process overlap-kernel outcome on the installed jax: spanning
# XLA collectives work; the interpret-mode AG kernel deadlocks on in-process
# semaphore state and the worker's watchdog pins it. MP_AG_OK or
# MP_AG_WRONG_RESULT in its place fails the test until a human re-measures
# — an either-or would let a regression in one direction read as the other.
_PINNED_OUTCOME = "MP_AG_UNSUPPORTED"

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
WORKER = os.path.join(HERE, "mp_worker.py")
LAUNCH = os.path.join(REPO, "scripts", "launch.sh")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _worker_env(pid: int, nproc: int, addr: str, generic_env: bool) -> dict:
    env = dict(os.environ)
    # a clean jax env: no inherited XLA_FLAGS device-count forcing (the
    # worker pins its own platform and device count)
    for k in ("XLA_FLAGS", "JAX_PLATFORMS",
              "JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS"):
        env.pop(k, None)
    env["PYTHONPATH"] = REPO
    env["JAX_NUM_PROCESSES"] = str(nproc)
    env["JAX_PROCESS_ID"] = str(pid)
    # the generic spelling exercises launch.sh's mapping
    env["COORDINATOR_ADDRESS" if generic_env
        else "JAX_COORDINATOR_ADDRESS"] = addr
    return env


def _run_cluster(via_launch_sh):
    """Launch the 2-process cluster once; returns (procs, outs) or raises
    TimeoutExpired after killing the children."""
    addr = f"127.0.0.1:{_free_port()}"
    cmd = ([LAUNCH, sys.executable, WORKER] if via_launch_sh
           else [sys.executable, WORKER])
    procs = [
        subprocess.Popen(cmd, env=_worker_env(pid, 2, addr, via_launch_sh),
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True)
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            # generous: the worker ends with a 20 s overlap-kernel
            # watchdog, and a fully loaded CI box stretches everything
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        raise
    return procs, outs


@pytest.mark.parametrize("via_launch_sh", [False, True])
def test_two_process_cluster(via_launch_sh):
    expected = _PINNED_OUTCOME
    try:
        procs, outs = _run_cluster(via_launch_sh)
    except subprocess.TimeoutExpired:
        pytest.fail("multi-process workers timed out")
    if any(p.returncode != 0 for p in procs):
        # one retry with a FRESH port: the free-port probe releases the
        # socket before the children rebind it, and on a busy box another
        # process can grab it in between — a launch race, not a product
        # failure. A second consecutive failure is real and surfaces.
        try:
            procs, outs = _run_cluster(via_launch_sh)
        except subprocess.TimeoutExpired:
            pytest.fail(f"multi-process workers timed out on retry; "
                        f"first attempt: {outs}")
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"
        assert f"MP_OK process={pid}/2" in out, out
        # the overlap-kernel attempt (VERDICT r4 #8) must report exactly
        # the pinned outcome. MP_AG_WRONG_RESULT
        # (ran, corrupt data) matches no pin and fails here — as it
        # must. A flip between MP_AG_OK and MP_AG_UNSUPPORTED (runtime
        # gained/lost cross-process interpret support) also fails until
        # a human re-measures and re-pins, which is the point.
        assert expected in out, (
            f"worker {pid}: overlap-kernel outcome differs from the "
            f"pin ({expected}) for jax {jax.__version__}:\n{out}")
    # regex-extract: concurrent C++ (Gloo) log lines can interleave into the
    # same stdout line as the python print
    import re
    picks = {m for out in outs
             for m in re.findall(r"picked=([0-9.]+)", out)}
    assert len(picks) == 1, f"processes picked different configs: {picks}"


def test_two_process_merged_profile(tmp_path):
    """Multi-host ``group_profile``: both processes trace, process 0 merges
    one Perfetto-loadable timeline with per-host tracks (reference
    utils.py:282-501 parity)."""
    import gzip
    import json

    addr = f"127.0.0.1:{_free_port()}"
    procs = []
    for pid in range(2):
        env = _worker_env(pid, 2, addr, generic_env=False)
        env["TDT_PROF_DIR"] = str(tmp_path)
        procs.append(subprocess.Popen(
            [sys.executable, WORKER], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    outs = []
    try:
        for p in procs:
            # generous: the worker ends with a 20 s overlap-kernel
            # watchdog, and a fully loaded CI box stretches everything
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail(f"profiled workers timed out; partial: {outs}")
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"
    assert any("MP_PROF_MERGED" in o for o in outs), outs

    merged = tmp_path / "mp" / "merged.trace.json.gz"
    assert merged.exists()
    with gzip.open(merged, "rt") as f:
        data = json.load(f)
    names = {ev["args"]["name"] for ev in data["traceEvents"]
             if ev.get("ph") == "M" and ev.get("name") == "process_name"}
    hosts = {n.split("/")[0] for n in names}
    assert {"host0", "host1"} <= hosts, f"per-host tracks missing: {names}"
    # both processes contributed real events, not just metadata
    pids = {ev.get("pid", 0) for ev in data["traceEvents"]}
    assert any(p >= 200000 for p in pids) and any(
        100000 <= p < 200000 for p in pids), sorted(pids)[:10]
