"""Device-side tpushmem primitives — usable *inside* Pallas TPU kernels.

This is the TPU-native re-creation of the reference's portability seam
``triton.language.extra.libshmem_device`` (reference
patches/triton/python/triton/language/extra/libshmem_device.py — the
vendor-neutral interface NVSHMEM/ROCSHMEM backends implement) and of its
NVIDIA implementation ``libnvshmem_device.py`` (put/get/signal/fence/quiet/
barrier device API, see reference SURVEY §2.2).

Mapping (GPU one-sided shmem → TPU):

===========================  ==============================================
reference primitive          TPU-native equivalent here
===========================  ==============================================
``my_pe()`` / ``n_pes()``    mesh axis index / size (``lax.axis_index``)
``putmem_nbi_block``         ``pltpu.make_async_remote_copy(...).start()``
``putmem_signal_nbi_block``  remote copy; the *receiver-side DMA semaphore*
                             is the delivery-ordered signal (hardware
                             signals it when data lands — stronger than
                             NVSHMEM's separate signal word)
``signal_op(SET/ADD)``       ``pltpu.semaphore_signal`` (counting ADD only;
                             SET has no TPU analog — protocols here are
                             redesigned around counted arrivals)
``signal_wait_until``        ``pltpu.semaphore_wait`` (NOTE: decrements)
``fence``/``quiet``          wait on local send semaphores (``quiet``);
                             per-destination ordering via semaphores
``barrier_all``              barrier semaphore all-to-all signal + wait
``symm_at(ptr, pe)``         not needed: remote refs are (buffer, device_id)
                             pairs — symmetric by construction
===========================  ==============================================

All functions take mesh-axis names because the "PE space" is a (possibly
multi-axis) jax mesh, not a flat rank list.
"""

from __future__ import annotations

import itertools
import os
from typing import Sequence

from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import faults
from . import trace


# -- producer-delay fuzzing --------------------------------------------------

_NOISE_SITE = itertools.count()


def _noise_trips() -> int:
    try:
        return int(os.environ.get("TDT_NOISE", "0") or "0")
    except ValueError:
        return 0


def producer_noise(src_ref) -> None:
    """Sync-bug fuzzing hook (analog of the reference's
    ``_add_noise_workload_debug`` sleep injection, allgather.py:72-76).

    When ``TDT_NOISE=<n>`` is set at trace time, emits ``n * (site%3 + 1)``
    effectful self-copies of ``src_ref`` before a put — per-call-site-varied
    busywork that widens producer/consumer timing windows so missing waits
    surface in interpret mode (pair with ``TDT_DETECT_RACES=1``). A no-op
    (zero emitted ops) when unset; debug knob only — it emits real DMAs if
    enabled on hardware.

    An active :class:`~triton_dist_tpu.shmem.faults.FaultPlan` with
    ``device_put_delay=k`` adds ``k`` flat extra trips on top — the
    "delay a put by extra noise trips" fault of the protocol matrix."""
    if trace.active_tracer() is not None:
        return  # busywork has no protocol meaning; skip under event capture
    trips = _noise_trips()
    plan = faults.active_plan()
    extra = plan.device_put_delay if plan is not None else 0
    if not trips and not extra:
        return
    k = next(_NOISE_SITE) % 3 + 1
    for _ in range(trips * k + extra):
        pltpu.sync_copy(src_ref, src_ref)


# -- serialized-execution bisection mode ------------------------------------

def _serial() -> bool:
    """``TDT_SERIAL=1`` (read at trace time) forces every put to complete
    synchronously at the source before the kernel proceeds — the analog of
    the reference's ``serial=True`` debug switch on its overlap ops
    (allgather_gemm.py:428,482-485), which serializes the copy/compute
    overlap to bisect hangs and races. With it set, all cross-device
    pipelining collapses to a lock-step schedule; correctness must be
    unchanged, only slower — any behavioral difference is a sync bug."""
    return os.environ.get("TDT_SERIAL") == "1"


class _CompletedDMA:
    """Stand-in descriptor returned by ``putmem_nbi`` in TDT_SERIAL mode:
    the put already completed at source, so ``quiet``/``wait_send`` become
    no-ops (a second wait on the consumed send semaphore would hang).

    ``wait()`` intentionally RAISES: on a real remote-copy descriptor it
    also waits the *receive* semaphore, which serial mode cannot have
    satisfied (delivery is signaled on the peer, not here) — silently
    no-opping would turn the bisection mode itself into a race. Kernels
    awaiting their own incoming delivery must use ``wait_recv``."""

    def wait_send(self):
        return None

    def wait(self):
        raise RuntimeError(
            "TDT_SERIAL: .wait() on a serialized put is ambiguous (the real "
            "descriptor would also wait the recv semaphore). Use wait_recv("
            "dst_ref, recv_sem) for deliveries; send completion already "
            "happened.")


_COMPLETED_DMA = _CompletedDMA()


# -- PE identity ------------------------------------------------------------

def my_pe(axis: str | Sequence[str]):
    """Rank of this device along ``axis`` (or flattened over several axes,
    major-to-minor). Analog of ``nvshmem_my_pe`` (libnvshmem_device.py:85)."""
    if isinstance(axis, str):
        return lax.axis_index(axis)
    pid = lax.axis_index(axis[0])
    for name in axis[1:]:
        pid = pid * lax.axis_size(name) + lax.axis_index(name)
    return pid


def n_pes(axis: str | Sequence[str]):
    """Number of PEs along ``axis``. Analog of ``nvshmem_n_pes``."""
    if isinstance(axis, str):
        return lax.axis_size(axis)
    n = 1
    for name in axis:
        n = n * lax.axis_size(name)
    return n


def pe_at(axis_names: Sequence[str], axis: str, index):
    """Flat LOGICAL device id of the device whose coordinate along ``axis``
    is ``index`` and whose other mesh coordinates equal ours.

    ``pltpu.make_async_remote_copy`` addresses peers by *flat* logical id
    over the whole mesh (row-major over ``axis_names``); this computes it —
    the role ``nvshmem_ptr``/``symm_at`` pointer translation plays on GPU
    (reference DistributedOps.td:135-149) without any pointer math.
    """
    pid = 0
    for name in axis_names:
        coord = index if name == axis else lax.axis_index(name)
        pid = pid * lax.axis_size(name) + coord
    return pid


def pe_at_group(mesh_axes: Sequence[str], group_axes: Sequence[str], index):
    """Flat LOGICAL device id of the device at flattened coordinate ``index``
    over ``group_axes`` (major-to-minor), other mesh coordinates equal ours.
    Generalizes ``pe_at`` to a multi-axis PE group — the addressing the
    hierarchical kernels use for their inner (fast-tier) group."""
    if isinstance(group_axes, str):
        group_axes = (group_axes,)
    rem = index
    coords = {}
    for name in reversed(tuple(group_axes)):
        sz = lax.axis_size(name)
        coords[name] = lax.rem(rem, sz)
        rem = rem // sz
    pid = 0
    for name in mesh_axes:
        coord = coords.get(name, lax.axis_index(name))
        pid = pid * lax.axis_size(name) + coord
    return pid


# -- one-sided puts ---------------------------------------------------------

def putmem_nbi(dst_ref, src_ref, send_sem, recv_sem, pe,):
    """Non-blocking one-sided put: copy ``src_ref`` (local) into ``dst_ref``
    on device ``pe`` (flat logical id). Returns the DMA descriptor; call
    ``.wait_send()`` (quiet) locally, receiver waits ``recv_sem``.

    Analog of ``libshmem_device.putmem_nbi_block``
    (libnvshmem_device.py put family; docs/primitives.md:22-56). The
    receiving device's ``recv_sem`` (same scratch slot) is signaled by the
    DMA engine when the data has fully landed — this gives the
    "putmem_signal" delivery guarantee for free.

    An active FaultPlan with ``device_peer_dead`` swallows the put: the
    DMA never starts, the returned descriptor is already "complete" at
    source, and nothing ever arrives at the peer — the consumer's
    ``wait_recv`` hangs exactly like a dead link would (host-side
    deadlines are what bound that hang; see docs/robustness.md).
    """
    tracer = trace.active_tracer()
    if tracer is not None:
        return tracer.putmem_nbi(dst_ref, src_ref, send_sem, recv_sem, pe)
    plan = faults.active_plan()
    if plan is not None and plan.device_peer_dead:
        return _COMPLETED_DMA
    producer_noise(src_ref)
    rdma = pltpu.make_async_remote_copy(
        src_ref=src_ref,
        dst_ref=dst_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=pe,
        device_id_type=pltpu.DeviceIdType.LOGICAL,
    )
    rdma.start()
    if _serial():
        rdma.wait_send()
        return _COMPLETED_DMA
    return rdma


def putmem_block(dst_ref, src_ref, send_sem, recv_sem, pe):
    """Blocking-at-source put: start + wait local send completion.
    (Remote delivery is still signaled via ``recv_sem``.)"""
    rdma = putmem_nbi(dst_ref, src_ref, send_sem, recv_sem, pe)
    rdma.wait_send()
    return rdma


# -- signals ----------------------------------------------------------------

def signal_op(sem_ref, inc, pe=None):
    """Atomically add ``inc`` to (possibly remote) semaphore. Analog of
    ``libshmem_device.signal_op(..., NVSHMEM_SIGNAL_ADD)``
    (low_latency_all_to_all.py:96-117 uses the SET form with call_count;
    on TPU the counting form is native and protocols count arrivals).

    An active FaultPlan may drop the signal (nothing emitted — the
    consumer's counted wait starves) or duplicate it (doubled increment —
    the over-signal poison the ledger layer must detect)."""
    tracer = trace.active_tracer()
    if tracer is not None:
        return tracer.signal_op(sem_ref, inc, pe)
    plan = faults.active_plan()
    if plan is not None:
        inc = plan.device_signal_inc(inc)
        if inc is None:
            return
    if pe is None:
        pltpu.semaphore_signal(sem_ref, inc=inc)
    else:
        pltpu.semaphore_signal(sem_ref, inc=inc, device_id=pe,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)


def signal_wait_until(sem_ref, value):
    """Block until the (REGULAR/barrier) semaphore has accumulated ``value``,
    then *consume* it (TPU semaphores decrement on wait — unlike NVSHMEM's
    ``signal_wait_until`` which leaves the flag set; protocols in ``ops/``
    are designed around consumption). DMA delivery waits use ``wait_recv``.
    """
    tracer = trace.active_tracer()
    if tracer is not None:
        return tracer.signal_wait_until(sem_ref, value)
    pltpu.semaphore_wait(sem_ref, value)


def wait_recv(dst_ref, recv_sem):
    """Wait for delivery of a put into ``dst_ref`` tracked by ``recv_sem``
    (a DMA semaphore). DMA semaphores count transferred bytes, so the wait
    is phrased through a descriptor of the expected shape — the standard
    same-ref trick."""
    tracer = trace.active_tracer()
    if tracer is not None:
        return tracer.wait_recv(dst_ref, recv_sem)
    pltpu.make_async_copy(dst_ref, dst_ref, recv_sem).wait()


def signal_read(sem_ref):
    """Non-destructive read of the semaphore count (debug/poll)."""
    tracer = trace.active_tracer()
    if tracer is not None:
        return tracer.signal_read(sem_ref)
    return pl.semaphore_read(sem_ref)


# -- ordering ---------------------------------------------------------------

def quiet(*rdmas):
    """Wait until our outstanding puts have left this device (local send
    completion). Analog of ``libshmem_device.quiet``."""
    tracer = trace.active_tracer()
    if tracer is not None:
        return tracer.quiet(*rdmas)
    for r in rdmas:
        r.wait_send()


def fence():
    """Analog of ``libshmem_device.fence`` (ordering of puts to the same PE).
    TPU remote DMAs carry their own completion semaphores; ordering is
    expressed by waiting those, so ``fence`` is a no-op kept for API parity.
    """
    tracer = trace.active_tracer()
    if tracer is not None:
        return tracer.fence()
    return None


# -- barriers ---------------------------------------------------------------

def barrier_all(axis_names: Sequence[str], mesh_axes: Sequence[str] | None = None):
    """Barrier across the devices spanned by ``axis_names`` inside a kernel:
    signal every other participant's barrier semaphore, wait for n-1
    arrivals. Analog of ``libshmem_device.barrier_all`` /
    ``barrier_all_intra_node_*`` (reference kernels/nvidia/common_ops.py:88-159).

    ``mesh_axes`` is the full, ordered axis-name tuple of the enclosing mesh;
    it is required when ``axis_names`` is a *subset* of a multi-axis mesh,
    because LOGICAL device ids are flat over the whole mesh (devices outside
    the barrier group keep their own coordinates on the other axes).

    The enclosing ``pallas_call`` must set
    ``compiler_params=pltpu.CompilerParams(collective_id=...)``.
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    mesh_axes = tuple(mesh_axes) if mesh_axes is not None else tuple(axis_names)
    tracer = trace.active_tracer()
    if tracer is not None:
        return tracer.barrier_all(axis_names, mesh_axes)
    sem = pltpu.get_barrier_semaphore()
    npes = n_pes(axis_names)
    me = my_pe(axis_names)

    def body(i, carry):
        pid = pe_at_group(mesh_axes, axis_names, i)

        @pl.when(i != me)
        def _():
            pltpu.semaphore_signal(sem, inc=1, device_id=pid,
                                   device_id_type=pltpu.DeviceIdType.LOGICAL)
        return carry

    lax.fori_loop(0, npes, body, 0)
    pltpu.semaphore_wait(sem, npes - 1)


def barrier_pair(axis_names: Sequence[str], peer):
    """Two-device barrier with flat-id ``peer`` (ring neighbors etc.)."""
    tracer = trace.active_tracer()
    if tracer is not None:
        return tracer.barrier_pair(axis_names, peer)
    sem = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(sem, inc=1, device_id=peer,
                           device_id_type=pltpu.DeviceIdType.LOGICAL)
    pltpu.semaphore_wait(sem, 1)
