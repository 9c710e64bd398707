"""Shared tutorial harness: case registry + argparse + mesh bootstrap
(the reference's ``register_test``/``--case`` pattern,
test/nvidia/test_ag_gemm_intra_node.py:44-73, plus ``--list``)."""

from __future__ import annotations

import argparse

_CASES: dict = {}
_SIM_WORLD: list = []   # set by --sim: mesh size (may be < device count)


def register_case(name: str):
    def deco(fn):
        _CASES[name] = fn
        return fn
    return deco


def _force_sim(n: int) -> None:
    """Switch to the CPU simulator. More devices than mesh participants are
    created: the interpreter's device threads can deadlock in its internal
    allocator when every thread simultaneously blocks in a barrier (see
    tests/conftest.py), so the mesh runs over a prefix subset."""
    _SIM_WORLD.append(n)
    from triton_dist_tpu.utils.env import force_virtual_cpu_devices
    force_virtual_cpu_devices(max(8, n + 2))


def tutorial_main(description: str, default_case: str = "correctness"):
    """Parse args, bootstrap the backend, run the selected case, exit 0 on
    success (cases signal failure by raising)."""
    ap = argparse.ArgumentParser(description=description)
    ap.add_argument("--case", default=default_case, choices=sorted(_CASES),
                    help="which case to run")
    ap.add_argument("--sim", type=int, default=None, metavar="N",
                    help="simulate an N-device CPU mesh (interpret mode)")
    ap.add_argument("--list", action="store_true", help="list cases")
    args = ap.parse_args()
    if args.list:
        for name in sorted(_CASES):
            print(name)
        return
    if args.sim:
        _force_sim(args.sim)
    import jax
    print(f"[tutorial] backend={jax.devices()[0].platform} "
          f"devices={len(jax.devices())} case={args.case}")
    _CASES[args.case]()
    print(f"[tutorial] {args.case}: PASS")


def perf_report(name: str, seconds: float, extra: str = "") -> None:
    us = seconds * 1e6
    print(f"[perf] {name}: {us:.1f} us/call {extra}".rstrip())


def time_op(fn, iters: int = 50, warmup: int = 5) -> float:
    """Simple wall-clock per-call timing (block_until_ready); bench.py's
    differenced chains cancel the fixed dispatch cost this includes."""
    import time

    import jax
    for _ in range(warmup):
        out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def world_size() -> int:
    import jax
    return _SIM_WORLD[0] if _SIM_WORLD else len(jax.devices())


def world_context_2d(axis_names=("node", "x")):
    """Factor the world into a 2-axis (outer, inner) mesh with the outer
    ("node"/slow) axis taking the largest divisor ≤ sqrt(world) — the mesh
    shape the multi-tier tutorials run on. A single chip degenerates to
    (1, 1)."""
    ws = world_size()
    no = 1
    for d in range(int(ws ** 0.5), 0, -1):
        if ws % d == 0:
            no = d
            break
    return world_context(axis_names=axis_names, mesh_shape=(no, ws // no))


def world_context(axis_names=("x",), mesh_shape=None):
    from triton_dist_tpu.shmem.context import initialize_distributed
    if mesh_shape is None:
        if len(axis_names) != 1:
            raise ValueError(
                "world_context needs an explicit mesh_shape for multi-axis "
                f"meshes (axis_names={axis_names}) — the --sim world size "
                "cannot be factorized implicitly")
        mesh_shape = (world_size(),)
    return initialize_distributed(axis_names=axis_names,
                                  mesh_shape=mesh_shape)
