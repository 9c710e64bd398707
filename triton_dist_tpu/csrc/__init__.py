"""ctypes bindings for the native host ops in ``csrc/`` (analog of reference
csrc's pybind module ``libtriton_distributed`` → ``distributed.*`` ops,
op_pybind.cc:34-48 — here a C ABI + ctypes, no pybind11 in the image).

The library builds lazily on first use (g++ is in the base image) from the
TRACKED sources in ``csrc/`` into ``_build/``, under a name keyed by their
content: a stale ``.so`` left in a working tree (or copied to another
machine with fresh mtimes) can never be what gets loaded. Set
``TDT_NO_NATIVE=1`` to skip the native path entirely (pure-jnp fallbacks in
ops.group_gemm keep everything functional).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(os.path.dirname(_HERE))
_SRCS = [os.path.join(_REPO, "csrc", f)
         for f in ("moe_align.cc", "a2a_route.cc")]

_lib = None


def _so_path() -> str:
    digest = hashlib.sha256()
    for src in _SRCS:
        with open(src, "rb") as f:
            digest.update(f.read())
    return os.path.join(_HERE, "_build",
                        f"libtdt_host-{digest.hexdigest()[:16]}.so")


def _build(so: str) -> None:
    os.makedirs(os.path.dirname(so), exist_ok=True)
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-fPIC", "-shared", "-std=c++17", "-Wall",
           *_SRCS, "-o", tmp]
    subprocess.run(cmd, check=True, capture_output=True, text=True)
    os.replace(tmp, so)       # atomic: a concurrent loader never sees half


def get_lib() -> ctypes.CDLL | None:
    """The loaded native library, building it if needed; None when disabled
    or the toolchain is unavailable."""
    global _lib
    if _lib is not None:
        return _lib
    if os.environ.get("TDT_NO_NATIVE") == "1":
        return None
    try:
        so = _so_path()
        if not os.path.exists(so):
            _build(so)
        lib = ctypes.CDLL(so)
    except (OSError, subprocess.CalledProcessError):
        return None
    lib.tdt_moe_align_padded_rows.restype = ctypes.c_int64
    lib.tdt_moe_align_padded_rows.argtypes = [
        ctypes.c_int64, ctypes.c_int32, ctypes.c_int32]
    lib.tdt_moe_align_block_size.restype = ctypes.c_int32
    lib.tdt_moe_align_block_size.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, ctypes.c_int32,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int32)]
    lib.tdt_a2a_slot_assign.restype = ctypes.c_int32
    lib.tdt_a2a_slot_assign.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, ctypes.c_int32,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8)]
    lib.tdt_a2a_bincount.restype = ctypes.c_int32
    lib.tdt_a2a_bincount.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32)]
    _lib = lib
    return _lib


def moe_align_block_size(ids: np.ndarray, num_experts: int, block_m: int):
    """Native host-side twin of ops.group_gemm.align_tokens_by_expert:
    returns (gather_idx [P] i32, row_valid [P] bool, block_expert [P/bm] i32)
    for a host routing table — no device round-trip."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable "
                           "(TDT_NO_NATIVE=1 or no toolchain)")
    ids = np.ascontiguousarray(ids, dtype=np.int32)
    T = ids.shape[0]
    P = lib.tdt_moe_align_padded_rows(T, num_experts, block_m)
    gather_idx = np.zeros(P, np.int32)
    row_valid = np.zeros(P, np.uint8)
    block_expert = np.zeros(P // block_m, np.int32)
    rc = lib.tdt_moe_align_block_size(
        ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), T, num_experts,
        block_m,
        gather_idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        row_valid.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        block_expert.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    assert rc == 0, f"tdt_moe_align_block_size failed: rc={rc}"
    return gather_idx, row_valid.astype(bool), block_expert


def a2a_slot_assign(dest: np.ndarray, n_dst: int, cap: int,
                    valid: np.ndarray | None = None
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Host-native slot allocation (contract-identical to
    ops.all_to_all._slot_assign; cross-tested). Returns (slot, ok)."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable "
                           "(TDT_NO_NATIVE=1 or no toolchain)")
    dest = np.ascontiguousarray(dest, dtype=np.int32)
    R = dest.shape[0]
    slot = np.zeros(R, np.int32)
    ok = np.zeros(R, np.uint8)
    vptr = None
    if valid is not None:
        valid = np.ascontiguousarray(valid, dtype=np.uint8)
        vptr = valid.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    rc = lib.tdt_a2a_slot_assign(
        dest.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), R, n_dst, cap,
        vptr, slot.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ok.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    assert rc == 0, f"tdt_a2a_slot_assign failed: rc={rc}"
    return slot, ok.astype(bool)


def a2a_bincount(dest: np.ndarray, n_dst: int) -> np.ndarray:
    """Host-native per-destination token counts (the wire `splits`)."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable "
                           "(TDT_NO_NATIVE=1 or no toolchain)")
    dest = np.ascontiguousarray(dest, dtype=np.int32)
    counts = np.zeros(n_dst, np.int32)
    rc = lib.tdt_a2a_bincount(
        dest.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), dest.shape[0],
        n_dst, counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    assert rc == 0, f"tdt_a2a_bincount failed: rc={rc}"
    return counts


__all__ = ["get_lib", "moe_align_block_size", "a2a_slot_assign",
           "a2a_bincount"]


def native_or_none(fname: str, *args, **kw):
    """Named once: the host-routing-table dispatch pattern. Calls the
    native twin ``fname`` and returns its result, or None when the native
    library is unavailable (TDT_NO_NATIVE=1 / no toolchain) so the caller
    falls back to its jnp twin. Keeps the fallback policy in one place
    (a future "warn when native is missing" change lands here only)."""
    try:
        return globals()[fname](*args, **kw)
    except RuntimeError:
        return None
