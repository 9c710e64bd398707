"""Backend/environment detection.

The same kernel code runs in two modes:
- compiled Mosaic on real TPU chips (bench, production), and
- Pallas TPU *interpret mode* on a virtual CPU device mesh (tests, CI) —
  an improvement over the reference, whose tests require real GPUs
  (reference SURVEY: no single-process cluster simulator).
"""

from __future__ import annotations

import os
from functools import lru_cache

import jax
from jax.experimental.pallas import tpu as pltpu

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure_compile_cache() -> str:
    """Place jax's persistent compilation cache; returns the directory.

    Every entry point that can reach the chip calls this before its first
    compile. ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself, so
    nothing is touched and no other directory is set in code. Unset:
    ``<checkout>/.jax_cache`` — the path ``scripts/launch.sh`` exports. The
    path is part of the cache key, so it is never a temp name, pid or time:
    a directory that moves never hits."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def force_virtual_cpu_devices(n: int) -> None:
    """Re-point jax at an ``n``-device virtual CPU platform, dropping any
    live backend. Only ever called because the caller was ASKED to
    simulate (``--sim``, ``tests/conftest``, the trace-only sigcheck CLI);
    nothing falls back to it when the real device count is short — that
    is ``require_devices``' error. The single shared copy of this
    order-sensitive recipe.

    Order matters: drop the cached backends (including the memoized
    ``get_backend`` — ``_clear_backends`` alone does not clear it) BEFORE
    the config updates; ``jax_num_cpu_devices`` refuses to change once it
    believes backends are live."""
    import jax._src.xla_bridge as xb
    xb._clear_backends()
    xb.get_backend.cache_clear()
    flag = f"--xla_force_host_platform_device_count={n}"
    if flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)
    backend_platform.cache_clear()


def require_devices(n: int, what: str) -> None:
    """Fail, naming the count, when the live backend has fewer than ``n``
    devices. A short device count is never papered over with a CPU mesh:
    simulation is something the caller asks for by flag."""
    have = jax.device_count()
    if have < n:
        raise RuntimeError(
            f"{what} needs {n} devices but jax sees {have} "
            f"({backend_platform()}); run on a host with {n} chips, or ask "
            "for the CPU simulator explicitly (--sim)")


@lru_cache(None)
def backend_platform() -> str:
    return jax.devices()[0].platform.lower()


def on_cpu() -> bool:
    return backend_platform() == "cpu"


def on_tpu() -> bool:
    return backend_platform() == "tpu"


def interpret_params(**kw):
    """TPU-interpret-mode params used when running on CPU devices.

    ``dma_execution_mode='on_wait'`` preserves the async-DMA/semaphore
    semantics closely enough to catch missing waits; set
    ``TDT_DETECT_RACES=1`` to enable the interpreter's race detector
    (the reference's analog is sleep-noise fuzzing, allgather.py:72-76)."""
    if os.environ.get("TDT_DETECT_RACES") == "1":
        kw.setdefault("detect_races", True)
    return pltpu.InterpretParams(**kw)


@lru_cache(None)
def _register_cpu_tpu_info():
    """Interpret mode runs kernels on CPU devices, but Pallas helpers that
    model the hardware (``emit_pipeline`` tiling) still query
    ``tpu_info.get_tpu_info()``. Register a v5e-like profile for the "cpu"
    device kind via the module's public ``registry`` hook so those helpers
    work in the simulator."""
    try:
        from jax._src.pallas.mosaic import tpu_info
    except ImportError:
        return  # private API moved; only emit_pipeline-style helpers notice

    def _cpu_info():  # matches jax 0.9 TpuInfo; guarded below for drift
        return tpu_info.TpuInfo(
            chip_version=tpu_info.ChipVersion.TPU_V5E,
            generation=5,
            num_cores=1,
            num_lanes=128,
            num_sublanes=8,
            mxu_column_size=128,
            vmem_capacity_bytes=128 * 1024 * 1024,
            cmem_capacity_bytes=0,
            smem_capacity_bytes=1024 * 1024,
            hbm_capacity_bytes=17_200_000_000,
            mem_bw_bytes_per_second=int(8.20e11),
            bf16_ops_per_second=int(1.97e14),
            int8_ops_per_second=int(3.94e14),
            fp8_ops_per_second=0,
            int4_ops_per_second=int(7.88e14),
        )

    try:
        _cpu_info()  # fail fast here (not inside a kernel) if TpuInfo drifted
        tpu_info.registry.setdefault("cpu", _cpu_info)
    except Exception:
        pass  # only emit_pipeline-dependent paths will then raise, with
        #       jax's own "Unsupported TPU device kind" message


def default_interpret():
    """What to pass as ``pallas_call(interpret=...)`` on this backend.

    ``TDT_FORCE_COMPILED=1`` (read at trace time) forces the compiled Mosaic
    path regardless of the live backend — used when lowering against an
    *abstract TPU topology* (AOT deployment, the CI topology-compile gate in
    tests/test_aot_topology.py) from a process whose default backend is CPU."""
    if on_cpu():    # emit_pipeline asks the LIVE backend's tpu_info, forced
        _register_cpu_tpu_info()                    # compile or not
    if os.environ.get("TDT_FORCE_COMPILED") == "1" or not on_cpu():
        return False
    return interpret_params()
