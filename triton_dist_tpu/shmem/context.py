"""Host-side tpushmem runtime: mesh bootstrap + symmetric buffers.

Role analog of the reference's ``pynvshmem`` host extension + wrapper
(reference shmem/nvshmem_bind/pynvshmem/src/pynvshmem.cc:130-214 and
python/pynvshmem/__init__.py:93-171), re-thought for TPU/JAX:

- *bootstrap*: NVSHMEM's UID handshake over a torch process group
  (pynvshmem/__init__.py:157-171) becomes ``jax.distributed.initialize`` +
  ``jax.sharding.Mesh`` construction — jax is single-controller, so there is
  no per-rank rendezvous to re-implement.
- *symmetric heap*: ``nvshmem_create_tensor(shape)`` (same shape on every PE,
  peer-addressable) becomes a jax Array of shape ``(n_pes, *local_shape)``
  sharded over the mesh axis: inside ``shard_map`` every device sees an
  identically-shaped local ref, and remote refs are addressed *by device id*
  in ``pltpu.make_async_remote_copy`` — symmetric by construction, no
  ``nvshmem_ptr`` pointer translation needed (cf. symm_at,
  dialect DistributedOps.td:135-149).
"""

from __future__ import annotations

import dataclasses
import os
import functools
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_DEFAULT_CONTEXT: "ShmemContext | None" = None


def initialize_distributed(axis_names: Sequence[str] = ("x",),
                           mesh_shape: Sequence[int] | None = None,
                           seed: int = 42) -> "ShmemContext":
    """Bootstrap the distributed runtime and build the default device mesh.

    Analog of the reference's ``initialize_distributed``
    (python/triton_dist/utils.py:91-111): there it creates a NCCL process
    group, seeds, and boots NVSHMEM off a broadcast unique id. Here:
    multi-host jax initializes from cluster env automatically, and the
    "symmetric heap" needs no setup beyond a Mesh.
    """
    global _DEFAULT_CONTEXT
    # Multi-host bootstrap. Must happen BEFORE any backend use (so no
    # jax.process_count()/jax.devices() in this guard). Opt-in via the
    # coordinator env vars ONLY; failures are surfaced, not swallowed, so a
    # pod never silently degrades to single-host. TPU_WORKER_ID is not a
    # trigger: single-host TPU machines export it too (the v5e host this
    # repo runs on sets TPU_WORKER_ID=0), and initializing a cluster there
    # waits on a coordinator that does not exist.
    multihost_env = any(os.environ.get(k) for k in (
        "JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS",
        "MEGASCALE_COORDINATOR_ADDRESS",
    ))
    if multihost_env and not jax.distributed.is_initialized():
        # jax auto-detects only managed clusters (Slurm/MPI/GKE-TPU);
        # the explicit JAX_NUM_PROCESSES/JAX_PROCESS_ID spelling that
        # scripts/launch.sh documents for ad-hoc pods must be forwarded by
        # hand (coordinator address jax reads itself).
        nproc = os.environ.get("JAX_NUM_PROCESSES")
        pid = os.environ.get("JAX_PROCESS_ID")
        if (nproc is None) != (pid is None):
            missing = "JAX_PROCESS_ID" if pid is None else "JAX_NUM_PROCESSES"
            present = "JAX_NUM_PROCESSES" if pid is None else "JAX_PROCESS_ID"
            raise RuntimeError(
                f"{present} is set but {missing} is not; ad-hoc multi-host "
                "bootstrap needs both (see scripts/launch.sh), or neither "
                "on a managed cluster where jax auto-detects them")
        jax.distributed.initialize(
            num_processes=int(nproc) if nproc else None,
            process_id=int(pid) if pid else None)
    devices = np.array(jax.devices())
    if mesh_shape is None:
        mesh_shape = (devices.size,) + (1,) * (len(axis_names) - 1)
    n_mesh = int(np.prod(mesh_shape))
    if n_mesh > devices.size:
        raise ValueError(f"mesh_shape {mesh_shape} needs {n_mesh} devices, "
                         f"only {devices.size} available")
    if (n_mesh == devices.size and n_mesh > 1
            and devices[0].platform == "cpu"
            and not jax.distributed.is_initialized()
            and os.environ.get("TDT_NO_CPU_SPARES") != "1"):
        # (n_mesh > 1: a single-device mesh has no cross-device waits to
        # deadlock — don't churn the backend for it.)
        # (single-process only: in a jax.distributed cluster the local
        # device count is recorded with the coordination service, and
        # re-creating the backend with extra local devices is rejected —
        # "Different local topology for node 0". Multi-process interpret
        # runs keep the spare-device responsibility with the launcher.)
        # Full-participation interpreter deadlock workaround: the Pallas
        # TPU interpreter's per-device kernel threads run on the CPU
        # client's execution pool, which is sized by device count. When
        # EVERY device thread blocks in a semaphore wait simultaneously
        # (any collective with enough in-kernel work), no pool thread is
        # left to drive the cross-device progress machinery and the
        # process hangs (reproduced: ag_gemm [512,512]x[512,1024] at
        # 8-of-8 deadlocks; identical shape at 8-of-12 runs in 4 s).
        # Transparently re-point jax at n + max(4, n) virtual devices
        # (spares = n: thinner ratios still starved occasionally — a
        # 12-of-18 run was observed taking 169 s vs the usual 6 s)
        # and build the mesh over the first n, so a user's all-device
        # CPU mesh just works. Real-chip meshes are untouched.
        # Re-pointing REPLACES the backend: arrays/meshes created before
        # this call die with a deleted-client error — warn so the failure
        # is attributable (create the context first, or opt out).
        import warnings
        warnings.warn(
            f"initialize_distributed: CPU mesh spans all {n_mesh} visible "
            "devices; provisioning spare virtual devices to avoid the "
            "interpreter's full-participation deadlock. This resets the "
            "jax CPU backend — jax arrays created before this call are "
            "invalidated (set TDT_NO_CPU_SPARES=1 to opt out).",
            stacklevel=2)
        from triton_dist_tpu.utils.env import force_virtual_cpu_devices
        force_virtual_cpu_devices(n_mesh + max(4, n_mesh))
        devices = np.array(jax.devices())
    dev_grid = None
    if n_mesh == devices.size and devices[0].platform == "tpu":
        # Topology-aware device ordering: ring/relay neighbors along the
        # innermost mesh axis should be physically adjacent on the ICI
        # torus. This is the TPU analog of the reference's NVLink/NUMA
        # topology detection feeding its AG method pick
        # (utils.py:504-607, allgather.py:54-69) — here jax's device-coords
        # mesh builder does the detection.
        from jax.experimental import mesh_utils
        try:
            dev_grid = mesh_utils.create_device_mesh(tuple(mesh_shape))
        except (ValueError, NotImplementedError, AssertionError) as e:
            # a mesh shape jax cannot lay on this topology: enumeration
            # order still works (LOGICAL ids follow the mesh, not the
            # torus), but neighbors may no longer be ICI-adjacent — say so
            import warnings
            warnings.warn(
                f"create_device_mesh{tuple(mesh_shape)} failed "
                f"({type(e).__name__}: {e}); falling back to device "
                "enumeration order — ring neighbors may not be "
                "ICI-adjacent", stacklevel=2)
    if dev_grid is None:
        # Prefix subset (e.g. a 4-device test mesh on an 8-device host) or
        # non-TPU backend: plain enumeration order.
        dev_grid = devices[:n_mesh].reshape(tuple(mesh_shape))
    mesh = Mesh(dev_grid, tuple(axis_names))
    ctx = ShmemContext(mesh=mesh)
    _DEFAULT_CONTEXT = ctx
    return ctx


def get_default_context() -> "ShmemContext":
    global _DEFAULT_CONTEXT
    if _DEFAULT_CONTEXT is None:
        _DEFAULT_CONTEXT = initialize_distributed()
    return _DEFAULT_CONTEXT


@functools.lru_cache(maxsize=None)
def _mesh_axis_crosses_slices(mesh: Mesh, axis: str) -> bool:
    """Constant for a given (mesh, axis) — cached so the per-collective
    ``is_dcn_axis`` check costs a dict lookup, not a device scan (only the
    TDT_DCN_AXES env override stays dynamic)."""
    idx = mesh.axis_names.index(axis)
    devs = np.moveaxis(mesh.devices, idx, 0)
    # any column along the axis whose devices span >1 slice_index
    cols = devs.reshape(devs.shape[0], -1)
    for j in range(cols.shape[1]):
        if len({getattr(d, "slice_index", 0) for d in cols[:, j]}) > 1:
            return True
    return False


@dataclasses.dataclass(frozen=True)
class ShmemContext:
    """Mesh + symmetric-buffer factory. Frozen so it can live in closures of
    jitted functions."""

    mesh: Mesh

    @property
    def axis_names(self) -> tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    @property
    def num_ranks(self) -> int:
        return self.mesh.devices.size

    def axis_size(self, axis: str | Sequence[str] | None = None) -> int:
        """Devices along ``axis`` — a name, a tuple of names (product, for
        hierarchical multi-tier PE groups), or None (whole mesh)."""
        if axis is None:
            return self.num_ranks
        if not isinstance(axis, str):
            n = 1
            for a in axis:
                n *= self.mesh.shape[a]
            return n
        return self.mesh.shape[axis]

    def is_dcn_axis(self, axis: str) -> bool:
        """True when neighbouring devices along ``axis`` live on different
        TPU slices — their link is DCN (data-center network), not ICI, and
        ``pltpu.make_async_remote_copy`` cannot cross it. Hierarchical ops
        route such an axis' tier through XLA collectives (host-driven DCN
        transfers) instead of remote DMA; an ICI-only mesh is unchanged.
        This is the TPU analog of the reference's intra/inter-node split
        (its inter-node tier is a different transport — IBRC/IBGDA,
        reference allgather.py:291-375, ep_a2a.py:35-147).

        Detection: ``device.slice_index`` varies along the axis. The
        ``TDT_DCN_AXES`` env var (comma-separated axis names) forces axes
        to DCN for testing/virtual topologies — the AOT topology gate
        compiles the DCN variants this way on hosts with no multi-slice
        hardware."""
        forced = os.environ.get("TDT_DCN_AXES")
        if forced and axis in [a.strip() for a in forced.split(",")]:
            return True
        return _mesh_axis_crosses_slices(self.mesh, axis)

    # -- symmetric heap -----------------------------------------------------

    def create_symm_tensor(self, local_shape: Sequence[int], dtype,
                           axis: str | None = None) -> jax.Array:
        """Symmetric buffer: one ``local_shape`` block per PE along ``axis``
        (default: the whole mesh, flattened). Analog of
        ``pynvshmem.nvshmem_create_tensor`` (pynvshmem/__init__.py:130-136).
        """
        n = self.axis_size(axis)
        spec = P(self.axis_names if axis is None else axis)
        shape = (n, *local_shape)
        sharding = NamedSharding(self.mesh, spec)
        # Allocate each shard in place (no full-array staging on device 0).
        return jnp.zeros(shape, dtype, device=sharding)

    def shard(self, x: jax.Array, spec: P) -> jax.Array:
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    # -- shard_map wrapper --------------------------------------------------

    def shard_map(self, f: Callable[..., Any], in_specs, out_specs,
                  axis_names: Sequence[str] | None = None):
        """SPMD-launch ``f`` over the mesh — the analog of "one process per
        GPU running this kernel" in the reference's torchrun model. Pallas
        kernels with manual DMA/semaphores do not carry varying-manual-axes
        info, hence ``check_vma=False``."""
        return jax.shard_map(f, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)
