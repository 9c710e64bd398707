"""Trace-time event capture: replay kernels per rank, record the protocol.

The capture replays an op's ``shard_map`` body ONCE PER RANK, sequentially,
with concrete rank coordinates and numpy-backed fake refs. Every shmem
primitive (via :mod:`triton_dist_tpu.shmem.trace`) and every raw Pallas
DMA/semaphore call (via monkeypatched ``pl``/``pltpu`` attributes) appends
a symbolic :class:`~.events.Event` instead of emitting a Mosaic op. Waits
record but do not block — cross-rank feasibility (deadlock, starvation) is
decided afterwards by :mod:`.checker`'s simulation over the recorded
streams.

Sequential replay is sound here because no kernel in this repo makes a
*protocol* decision based on data received from a remote put: peers,
semaphores, increments and regions depend only on the rank's own inputs,
scalar prefetch and shapes. Remote payloads may therefore be garbage
(zeros) during capture without changing the recorded event structure.

Capture runs under ``TDT_FORCE_COMPILED=1`` so every op builds its real
one-sided protocol (not an interpret-mode mirror), and with
``TDT_NOISE``/``TDT_SERIAL`` cleared so debug modes don't distort it.
"""

from __future__ import annotations

import contextlib
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..shmem import trace
from .events import Event, Region, SemId


def _as_int(x) -> int:
    return int(np.asarray(x))


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


# -- fake buffers and refs ---------------------------------------------------

class BufferInfo:
    """One concrete buffer: stable (per-rank-deterministic) id + np storage."""

    def __init__(self, buf_id: str, array: np.ndarray):
        self.id = buf_id
        self.array = array


class _At:
    def __init__(self, ref: "FakeRef"):
        self._ref = ref

    def __getitem__(self, idx) -> "FakeRef":
        return FakeRef(self._ref.info, self._ref._resolve(idx))


class FakeRef:
    """View into a :class:`BufferInfo`: per-base-dimension ``(start, size,
    keep)`` selection (``keep=False`` marks integer-indexed, squeezed dims).
    Reads/writes record events on the active tracer and move real numpy
    data, so host-level glue around the kernels keeps working."""

    def __init__(self, info: BufferInfo, sel=None):
        self.info = info
        self.sel = sel if sel is not None else tuple(
            (0, d, True) for d in info.array.shape)

    # ---- geometry

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(sz for (_, sz, keep) in self.sel if keep)

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def dtype(self):
        return self.info.array.dtype

    @property
    def nbytes(self) -> int:
        return _prod(self.shape) * self.info.array.dtype.itemsize

    @property
    def at(self) -> _At:
        return _At(self)

    def region(self) -> Region:
        return Region(self.info.id,
                      tuple((st, st + sz) for (st, sz, _) in self.sel))

    def _np_index(self):
        return tuple(slice(st, st + sz) if keep else st
                     for (st, sz, keep) in self.sel)

    def _resolve(self, idx):
        if not isinstance(idx, tuple):
            idx = (idx,)
        visible = [i for i, (_, _, keep) in enumerate(self.sel) if keep]
        if any(e is Ellipsis for e in idx):
            pos = next(i for i, e in enumerate(idx) if e is Ellipsis)
            pad = len(visible) - (len(idx) - 1)
            idx = idx[:pos] + (slice(None),) * pad + idx[pos + 1:]
        idx = idx + (slice(None),) * (len(visible) - len(idx))
        if len(idx) > len(visible):
            raise IndexError(
                f"sigcheck capture: {len(idx)} indices into rank-"
                f"{len(visible)} ref {self.info.id}")
        newsel = list(self.sel)
        for elem, d in zip(idx, visible):
            st, sz, _ = self.sel[d]
            if hasattr(elem, "start") and hasattr(elem, "size"):
                # pl.ds / pallas Slice
                newsel[d] = (st + _as_int(elem.start), _as_int(elem.size),
                             True)
            elif isinstance(elem, slice):
                if elem.step not in (None, 1):
                    raise NotImplementedError(
                        "sigcheck capture: strided ref slices unsupported")
                lo = 0 if elem.start is None else _as_int(elem.start)
                hi = sz if elem.stop is None else _as_int(elem.stop)
                if lo < 0:
                    lo += sz
                if hi < 0:
                    hi += sz
                newsel[d] = (st + lo, hi - lo, True)
            else:
                i = _as_int(elem)
                if i < 0:
                    i += sz
                newsel[d] = (st + i, 1, False)
        return tuple(newsel)

    # ---- data access (records events)

    def __getitem__(self, idx):
        sub = FakeRef(self.info, self._resolve(idx))
        t = trace.active_tracer()
        if t is not None:
            t.record_read(sub)
        return self.info.array[sub._np_index()]

    def __setitem__(self, idx, value):
        sub = FakeRef(self.info, self._resolve(idx))
        t = trace.active_tracer()
        if t is not None:
            t.record_write(sub)
        self.info.array[sub._np_index()] = np.asarray(value)


class FakeSem:
    """Semaphore allocation (cell array): symbolic identity + local int64
    counts. Counts only mirror *local* effects (self-signals, local DMA
    credits) so ``signal_read`` polls stay meaningful; the cross-rank
    arithmetic lives in the checker."""

    def __init__(self, alloc: str, shape: Tuple[int, ...], kind: str,
                 counts: np.ndarray | None = None, sel=None):
        self.alloc = alloc
        self.base_shape = tuple(shape)
        self.kind = kind
        self.counts = counts if counts is not None else np.zeros(
            self.base_shape, np.int64)
        self.sel = sel if sel is not None else tuple(
            (0, d, True) for d in self.base_shape)

    @property
    def at(self):
        return _SemAt(self)

    def _narrow(self, idx):
        helper = FakeRef(BufferInfo(self.alloc, self.counts), self.sel)
        return FakeSem(self.alloc, self.base_shape, self.kind, self.counts,
                       helper._resolve(idx))

    def cell(self) -> SemId:
        coords = []
        for (st, sz, _) in self.sel:
            if sz != 1:
                raise NotImplementedError(
                    f"sigcheck capture: semaphore {self.alloc} used with "
                    f"unresolved cell range {self.sel}")
            coords.append(st)
        return SemId(self.alloc, tuple(coords), self.kind)

    def _cell_index(self):
        return tuple(st for (st, _, _) in self.sel)

    def add(self, inc: int):
        self.counts[self._cell_index()] += inc

    def read(self) -> int:
        return int(self.counts[self._cell_index()])


class _SemAt:
    def __init__(self, sem: FakeSem):
        self._sem = sem

    def __getitem__(self, idx) -> FakeSem:
        return self._sem._narrow(idx)


# -- DMA descriptors ---------------------------------------------------------

class FakeRDMA:
    """Descriptor returned by a captured ``putmem_nbi``."""

    def __init__(self, tracer: "RankTracer", rdma_id: int, dst_ref: FakeRef,
                 recv_sem: FakeSem, send_sem: Optional[FakeSem],
                 nbytes: int):
        self._tracer = tracer
        self._id = rdma_id
        self._dst = dst_ref
        self._recv = recv_sem
        self._send = send_sem
        self._nbytes = nbytes

    def wait_send(self):
        # draining the send sem consumes the source-side credit the put made
        if self._send is not None:
            self._tracer._emit("wait_send", rdma_id=self._id,
                               sem=self._send.cell(), value=self._nbytes)
        else:
            self._tracer._emit("wait_send", rdma_id=self._id)

    def wait(self):
        # a full .wait() on a remote copy waits send AND (local) recv — the
        # local recv sem is credited by the symmetric peer's incoming put
        self.wait_send()
        self._tracer.wait_recv(self._dst, self._recv)


class _PendingRemoteCopy:
    """Patched ``pltpu.make_async_remote_copy``: records on .start()."""

    def __init__(self, tracer, src_ref, dst_ref, send_sem, recv_sem,
                 device_id):
        self._args = (tracer, src_ref, dst_ref, send_sem, recv_sem, device_id)
        self._rdma: FakeRDMA | None = None

    def start(self):
        tracer, src, dst, send, recv, pe = self._args
        self._rdma = tracer.putmem_nbi(dst, src, send, recv, pe)
        return self._rdma

    def _started(self) -> FakeRDMA:
        if self._rdma is None:
            raise RuntimeError("sigcheck capture: wait before start on a "
                               "remote copy descriptor")
        return self._rdma

    def wait_send(self):
        self._started().wait_send()

    def wait(self):
        self._started().wait()


class FakeCopy:
    """Patched ``pltpu.make_async_copy``: local async copy (start/wait) or
    the same-ref ``wait_recv`` trick (wait only)."""

    def __init__(self, tracer, src_ref, dst_ref, sem):
        self._tracer = tracer
        self._src = src_ref
        self._dst = dst_ref
        self._sem = sem

    def start(self):
        self._tracer.local_copy_start(self._src, self._dst, self._sem)

    def wait(self):
        self._tracer.wait_recv(self._dst, self._sem)


# -- per-rank tracer ---------------------------------------------------------

class _CallCtx:
    def __init__(self, key: str, collective_id, grid_dims: Tuple[int, ...]):
        self.key = key
        self.collective_id = collective_id
        self.grid_dims = grid_dims
        self.grid_pos: Tuple[int, ...] = ()


class RankTracer:
    """Implements the ``shmem.trace`` hook protocol for one rank and records
    the event stream while that rank's replay runs."""

    def __init__(self, state: "CaptureState", coords: Dict[str, int]):
        self.state = state
        self.coords = dict(coords)
        self.flat = state.flat(coords)
        self.events: List[Event] = []
        self.seq = 0
        self.call_index = 0
        self.scope_index = 0
        self.rdma_index = 0
        self.call_stack: List[_CallCtx] = []
        self.barrier_sems: Dict[str, FakeSem] = {}

    # ---- bookkeeping

    def _grid(self):
        return self.call_stack[-1].grid_pos if self.call_stack else None

    def _site(self):
        return self.call_stack[-1].key if self.call_stack else "<host>"

    def _emit(self, kind: str, **kw) -> Event:
        e = Event(rank=self.flat, seq=self.seq, kind=kind, grid=self._grid(),
                  site=self._site(), **kw)
        self.seq += 1
        self.events.append(e)
        return e

    def push_call(self, name: str, collective_id,
                  grid_dims: Tuple[int, ...]) -> _CallCtx:
        key = f"c{self.call_index}:{name}"
        self.call_index += 1
        ctx = _CallCtx(key, collective_id, grid_dims)
        self.call_stack.append(ctx)
        return ctx

    def pop_call(self):
        self.call_stack.pop()

    def barrier_sem(self, collective_id) -> FakeSem:
        alloc = f"barrier:{collective_id}"
        sem = self.barrier_sems.get(alloc)
        if sem is None:
            sem = FakeSem(alloc, (), "barrier")
            self.barrier_sems[alloc] = sem
        return sem

    # ---- data events

    def record_read(self, ref: FakeRef):
        self._emit("read", src=ref.region())

    def record_write(self, ref: FakeRef):
        self._emit("write", dst=ref.region())

    # ---- shmem.device hook protocol

    def putmem_nbi(self, dst_ref, src_ref, send_sem, recv_sem, pe) -> FakeRDMA:
        pe = _as_int(pe)
        rdma_id = self.rdma_index
        self.rdma_index += 1
        nbytes = src_ref.nbytes
        self._emit("put", src=src_ref.region(), dst=dst_ref.region(),
                   dst_rank=pe, sem=recv_sem.cell(),
                   send_sem=send_sem.cell() if send_sem is not None else None,
                   value=nbytes, rdma_id=rdma_id)
        if pe == self.flat:
            dst_ref.info.array[dst_ref._np_index()] = (
                src_ref.info.array[src_ref._np_index()].reshape(dst_ref.shape))
            recv_sem.add(nbytes)
        return FakeRDMA(self, rdma_id, dst_ref, recv_sem, send_sem, nbytes)

    def local_copy_start(self, src_ref, dst_ref, sem):
        rdma_id = self.rdma_index
        self.rdma_index += 1
        nbytes = src_ref.nbytes
        self._emit("put", src=src_ref.region(), dst=dst_ref.region(),
                   dst_rank=self.flat, sem=sem.cell(), value=nbytes,
                   rdma_id=rdma_id)
        if src_ref is not dst_ref:
            dst_ref.info.array[dst_ref._np_index()] = (
                src_ref.info.array[src_ref._np_index()].reshape(dst_ref.shape))
        sem.add(nbytes)

    def signal_op(self, sem_ref, inc, pe):
        inc = _as_int(inc)
        dst = self.flat if pe is None else _as_int(pe)
        self._emit("signal", sem=sem_ref.cell(), dst_rank=dst, value=inc)
        if dst == self.flat:
            sem_ref.add(inc)

    def signal_wait_until(self, sem_ref, value):
        v = _as_int(value)
        self._emit("wait", sem=sem_ref.cell(), value=v)
        sem_ref.add(-v)

    def wait_recv(self, dst_ref, recv_sem):
        nbytes = dst_ref.nbytes
        self._emit("wait_recv", dst=dst_ref.region(), sem=recv_sem.cell(),
                   value=nbytes)
        recv_sem.add(-nbytes)

    def signal_read(self, sem_ref):
        self._emit("sem_read", sem=sem_ref.cell())
        return jnp.int32(sem_ref.read())

    def quiet(self, *rdmas):
        for r in rdmas:
            r.wait_send()

    def fence(self):
        self._emit("fence")

    # ---- barriers (device.py routes here before touching Mosaic)

    def _pe_at_group(self, mesh_axes, group_axes, index: int) -> int:
        rem = index
        coords = {}
        for name in reversed(tuple(group_axes)):
            sz = self.state.sizes[name]
            coords[name] = rem % sz
            rem //= sz
        pid = 0
        for name in mesh_axes:
            pid = pid * self.state.sizes[name] + coords.get(
                name, self.coords[name])
        return pid

    def barrier_all(self, axis_names: Sequence[str],
                    mesh_axes: Sequence[str]):
        cid = (self.call_stack[-1].collective_id
               if self.call_stack else None)
        sem = self.barrier_sem(cid)
        npes = _prod(self.state.sizes[a] for a in axis_names)
        me = 0
        for name in axis_names:
            me = me * self.state.sizes[name] + self.coords[name]
        for i in range(npes):
            if i != me:
                pid = self._pe_at_group(mesh_axes, axis_names, i)
                self._emit("signal", sem=sem.cell(), dst_rank=pid, value=1)
        self._emit("wait", sem=sem.cell(), value=npes - 1)

    def barrier_pair(self, axis_names: Sequence[str], peer):
        cid = (self.call_stack[-1].collective_id
               if self.call_stack else None)
        sem = self.barrier_sem(cid)
        self._emit("signal", sem=sem.cell(), dst_rank=_as_int(peer), value=1)
        self._emit("wait", sem=sem.cell(), value=1)


# -- capture state + mesh ----------------------------------------------------

class CaptureState:
    def __init__(self, axes: Tuple[Tuple[str, int], ...]):
        self.axes = tuple(axes)
        self.sizes = dict(self.axes)
        self.n = _prod(sz for _, sz in self.axes)
        self.tracers: Dict[int, RankTracer] = {}
        self.cur: RankTracer | None = None

    def flat(self, coords: Dict[str, int]) -> int:
        pid = 0
        for name, sz in self.axes:
            pid = pid * sz + coords[name]
        return pid

    def unflatten(self, flat: int) -> Dict[str, int]:
        coords = {}
        for name, sz in reversed(self.axes):
            coords[name] = flat % sz
            flat //= sz
        return coords

    @contextlib.contextmanager
    def rank(self, coords: Dict[str, int]):
        flat = self.flat(coords)
        tracer = self.tracers.get(flat)
        if tracer is None:
            tracer = RankTracer(self, coords)
            self.tracers[flat] = tracer
        prev = self.cur
        self.cur = tracer
        trace.set_tracer(tracer)
        try:
            yield tracer
        finally:
            self.cur = prev
            trace.set_tracer(prev)

    def require(self) -> RankTracer:
        if self.cur is None:
            raise RuntimeError(
                "sigcheck capture: pallas/collective call outside a rank "
                "replay (op built work outside ctx.shard_map?)")
        return self.cur

    def streams(self) -> Dict[int, List[Event]]:
        return {r: t.events for r, t in sorted(self.tracers.items())}


# -- fake pallas_call --------------------------------------------------------

def _is_sem_scratch(s) -> bool:
    from jax.experimental.pallas import tpu as pltpu
    if isinstance(s, pltpu.SemaphoreType):
        return True
    dt = getattr(s, "dtype", None)
    return dt is not None and "sem" in str(dt)


def _scratch_objs(key: str, scratch) -> list:
    """Fake refs and semaphores for a kernel's ``scratch_shapes`` (or a
    ``run_scoped`` body's allocations)."""
    objs = []
    for j, s in enumerate(scratch):
        shp = tuple(getattr(s, "shape", ()) or ())
        if _is_sem_scratch(s):
            objs.append(FakeSem(f"{key}/sem{j}", shp, _sem_kind(s)))
        else:
            objs.append(FakeRef(BufferInfo(
                f"{key}/scratch{j}",
                np.zeros(shp, getattr(s, "dtype", np.float32)))))
    return objs


def _sem_kind(s) -> str:
    from jax.experimental.pallas import tpu as pltpu
    if isinstance(s, pltpu.SemaphoreType):
        name = getattr(s, "name", str(s)).lower()
    else:
        name = str(getattr(s, "dtype", ""))
    if "dma" in name:
        return "dma"
    if "barrier" in name:
        return "barrier"
    return "regular"


def _spec_list(specs, count: int) -> list:
    if specs is None:
        return [None] * count
    if isinstance(specs, (list, tuple)):
        out = list(specs)
    else:
        out = [specs]
    if len(out) != count:
        raise NotImplementedError(
            f"sigcheck capture: {len(out)} block specs for {count} operands")
    return out


def _block_ref(info: BufferInfo, spec, grid_idx, prefetch_refs) -> FakeRef:
    block_shape = getattr(spec, "block_shape", None) if spec is not None \
        else None
    if block_shape is None:
        return FakeRef(info)
    index_map = getattr(spec, "index_map", None)
    if index_map is None:
        bidx = tuple(grid_idx)[:len(block_shape)]
    else:
        bidx = index_map(*grid_idx, *prefetch_refs)
    if not isinstance(bidx, tuple):
        bidx = (bidx,)
    if len(bidx) != len(block_shape):
        raise NotImplementedError(
            f"sigcheck capture: index_map arity {len(bidx)} vs block rank "
            f"{len(block_shape)}")
    sel = []
    for b, bs, dim in zip(bidx, block_shape, info.array.shape):
        if bs is None:
            sel.append((_as_int(b), 1, False))
        else:
            bs = int(bs)
            sel.append((_as_int(b) * bs, bs, True))
    return FakeRef(info, tuple(sel))


def _fake_pallas_call(state: CaptureState):
    def pallas_call(kernel, out_shape=None, *, grid_spec=None, grid=None,
                    in_specs=None, out_specs=None, scratch_shapes=(),
                    input_output_aliases=None, compiler_params=None,
                    name=None, **_ignored):
        def runner(*args):
            tracer = state.require()
            if grid_spec is not None:
                nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0) or 0)
                g = getattr(grid_spec, "grid", ()) or ()
                ins = getattr(grid_spec, "in_specs", None)
                outs = getattr(grid_spec, "out_specs", None)
                scratch = getattr(grid_spec, "scratch_shapes", ()) or ()
            else:
                nsp = 0
                g = grid if grid is not None else ()
                ins = in_specs
                outs = out_specs
                scratch = scratch_shapes or ()
            if isinstance(g, int):
                g = (g,)
            g = tuple(int(x) for x in g)
            cid = getattr(compiler_params, "collective_id", None)
            call_name = name or getattr(kernel, "__name__", "kernel")

            out_leaves, out_tree = jax.tree_util.tree_flatten(out_shape)
            aliases = dict(input_output_aliases or {})

            call = tracer.push_call(call_name, cid, g)
            key = call.key
            try:
                arrays = [np.array(a, copy=True) for a in args]
                infos = [BufferInfo(f"{key}/in{j}", a)
                         for j, a in enumerate(arrays)]
                prefetch_refs = [FakeRef(infos[j]) for j in range(nsp)]
                data_infos = infos[nsp:]
                ins = _spec_list(ins, len(data_infos))
                outs = _spec_list(outs, len(out_leaves))

                out_infos = []
                for j, leaf in enumerate(out_leaves):
                    src = next((i for i, o in aliases.items() if o == j),
                               None)
                    if src is not None:
                        out_infos.append(infos[src])
                    else:
                        out_infos.append(BufferInfo(
                            f"{key}/out{j}",
                            np.zeros(leaf.shape, leaf.dtype)))

                scratch_objs = _scratch_objs(key, scratch)

                def invoke(grid_idx):
                    call.grid_pos = tuple(int(i) for i in grid_idx)
                    refs = list(prefetch_refs)
                    refs += [_block_ref(info, spec, grid_idx, prefetch_refs)
                             for info, spec in zip(data_infos, ins)]
                    refs += [_block_ref(info, spec, grid_idx, prefetch_refs)
                             for info, spec in zip(out_infos, outs)]
                    refs += scratch_objs
                    kernel(*refs)

                if not g:
                    invoke(())
                else:
                    for idx in np.ndindex(*g):
                        invoke(idx)
            finally:
                tracer.pop_call()

            results = [jnp.asarray(info.array) for info in out_infos]
            return jax.tree_util.tree_unflatten(out_tree, results)

        return runner

    return pallas_call


# -- patched jax surface -----------------------------------------------------

def _axis_total(state: CaptureState, axis_name) -> int:
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    return _prod(state.sizes[a] for a in names)


def _axis_flat_index(state: CaptureState, axis_name) -> int:
    tracer = state.require()
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    idx = 0
    for a in names:
        idx = idx * state.sizes[a] + tracer.coords[a]
    return idx


def _fake_collectives(state: CaptureState):
    def all_gather(x, axis_name, *, axis_index_groups=None, axis=0,
                   tiled=False, **_kw):
        n = _axis_total(state, axis_name)
        xs = [np.asarray(x)] * n
        return jnp.asarray(np.concatenate(xs, axis=axis) if tiled
                           else np.stack(xs, axis=axis))

    def psum(x, axis_name, *, axis_index_groups=None, **_kw):
        n = _axis_total(state, axis_name)
        return jax.tree_util.tree_map(lambda v: jnp.asarray(v) * n, x)

    def psum_scatter(x, axis_name, *, scatter_dimension=0,
                     axis_index_groups=None, tiled=False, **_kw):
        n = _axis_total(state, axis_name)
        me = _axis_flat_index(state, axis_name)
        full = np.asarray(x) * n
        if tiled:
            seg = full.shape[scatter_dimension] // n
            return jnp.asarray(np.take(
                full, range(me * seg, (me + 1) * seg),
                axis=scatter_dimension))
        return jnp.asarray(np.take(full, me, axis=scatter_dimension))

    def ppermute(x, axis_name, perm, **_kw):
        return jnp.asarray(np.asarray(x))

    def all_to_all(x, axis_name, split_axis, concat_axis, *,
                   axis_index_groups=None, tiled=False, **_kw):
        n = _axis_total(state, axis_name)
        parts = np.split(np.asarray(x), n, axis=split_axis)
        if tiled:
            return jnp.asarray(np.concatenate(parts, axis=concat_axis))
        return jnp.asarray(np.stack(
            [np.take(p, 0, axis=split_axis) for p in parts],
            axis=concat_axis))

    def axis_index(axis_name):
        return jnp.int32(_axis_flat_index(state, axis_name))

    def axis_size(axis_name):
        return _axis_total(state, axis_name)

    def fori_loop(lower, upper, body_fun, init_val, **_kw):
        carry = init_val
        for i in range(_as_int(lower), _as_int(upper)):
            carry = body_fun(jnp.int32(i), carry)
        return carry

    def while_loop(cond_fun, body_fun, init_val):
        carry = init_val
        while bool(np.asarray(cond_fun(carry))):
            carry = body_fun(carry)
        return carry

    def cond(pred, true_fun, false_fun, *operands, **_kw):
        return true_fun(*operands) if bool(np.asarray(pred)) \
            else false_fun(*operands)

    def switch(index, branches, *operands, **_kw):
        at = min(max(_as_int(index), 0), len(branches) - 1)
        return branches[at](*operands)

    return dict(all_gather=all_gather, psum=psum, psum_scatter=psum_scatter,
                ppermute=ppermute, all_to_all=all_to_all,
                axis_index=axis_index, axis_size=axis_size,
                fori_loop=fori_loop, while_loop=while_loop, cond=cond,
                switch=switch)


def _fake_when(condition):
    concrete = bool(np.asarray(condition))

    def decorator(f):
        if concrete:
            f()
        return None

    return decorator


@contextlib.contextmanager
def patched(state: CaptureState):
    """Monkeypatch the pl/pltpu/lax surface the kernels touch. Everything is
    restored on exit, including the env knobs the capture pins."""
    from jax import lax as lax_mod
    from jax.experimental import pallas as pl_mod
    from jax.experimental.pallas import tpu as pltpu_mod

    saves: List[Tuple[Any, str, Any]] = []
    _MISSING = object()

    def patch(mod, attr, val):
        # some attrs (e.g. sync_copy) are absent on older jax — the repo's
        # kernels still call them, so install the fake and delete on exit
        saves.append((mod, attr, getattr(mod, attr, _MISSING)))
        setattr(mod, attr, val)

    def tracer():
        return state.require()

    # pallas core
    patch(pl_mod, "pallas_call", _fake_pallas_call(state))
    patch(pl_mod, "when", _fake_when)
    patch(pl_mod, "program_id",
          lambda axis: jnp.int32(tracer().call_stack[-1].grid_pos[axis]))
    patch(pl_mod, "num_programs",
          lambda axis: int(tracer().call_stack[-1].grid_dims[axis]))
    if hasattr(pl_mod, "semaphore_read"):
        patch(pl_mod, "semaphore_read", lambda sem: tracer().signal_read(sem))

    def run_scoped(f, *types, **_kw):
        # a kernel's own scratch (``ops/group_gemm.py::_emit_run_walk``):
        # fresh buffers and semaphores for the body's life
        t = tracer()
        t.scope_index += 1
        site = t.call_stack[-1].key if t.call_stack else "<host>"
        return f(*_scratch_objs(f"{site}/scope{t.scope_index}", types))

    patch(pl_mod, "run_scoped", run_scoped)

    # pallas tpu
    patch(pltpu_mod, "make_async_copy",
          lambda src_ref, dst_ref, sem: FakeCopy(tracer(), src_ref, dst_ref,
                                                 sem))

    def make_async_remote_copy(*, src_ref, dst_ref, send_sem, recv_sem,
                               device_id, device_id_type=None):
        return _PendingRemoteCopy(tracer(), src_ref, dst_ref, send_sem,
                                  recv_sem, device_id)

    patch(pltpu_mod, "make_async_remote_copy", make_async_remote_copy)

    def sync_copy(src_ref, dst_ref):
        t = tracer()
        t.record_read(src_ref)
        t.record_write(dst_ref)
        if src_ref is not dst_ref:
            dst_ref.info.array[dst_ref._np_index()] = (
                src_ref.info.array[src_ref._np_index()].reshape(
                    dst_ref.shape))

    patch(pltpu_mod, "sync_copy", sync_copy)

    def emit_pipeline(body=None, *, grid=None, in_specs=None, out_specs=None,
                      **_kw):
        # Compute pipelines carry no signal protocol in this repo; model one
        # as whole-ref reads of its inputs and writes of its outputs.
        n_in = len(in_specs) if in_specs is not None else 0

        def run(*refs, **_rkw):
            t = tracer()
            for r in refs[:n_in]:
                t.record_read(r)
            for r in refs[n_in:]:
                t.record_write(r)

        return run

    patch(pltpu_mod, "emit_pipeline", emit_pipeline)

    def get_barrier_semaphore():
        t = tracer()
        cid = t.call_stack[-1].collective_id if t.call_stack else None
        return t.barrier_sem(cid)

    patch(pltpu_mod, "get_barrier_semaphore", get_barrier_semaphore)

    def semaphore_signal(sem, inc=1, *, device_id=None, device_id_type=None,
                         **_kw):
        tracer().signal_op(sem, inc, device_id)

    patch(pltpu_mod, "semaphore_signal", semaphore_signal)
    patch(pltpu_mod, "semaphore_wait",
          lambda sem, value=1: tracer().signal_wait_until(sem, value))

    # host-level collectives + control flow
    for attr, val in _fake_collectives(state).items():
        patch(lax_mod, attr, val)

    # jit must not trace the fake driver: capture replays kernels eagerly on
    # numpy buffers, and a jit boundary would turn the assembled outputs into
    # tracers (ops like barrier_all_op wrap their shard_map in jax.jit)
    def fake_jit(fun=None, **_kw):
        if fun is None:
            return lambda f: f
        return fun

    patch(jax, "jit", fake_jit)

    # env: force the compiled protocol path, silence debug perturbations
    env_saves = {}
    for k, v in (("TDT_FORCE_COMPILED", "1"), ("TDT_NOISE", None),
                 ("TDT_SERIAL", None), ("TDT_DETECT_RACES", None)):
        env_saves[k] = os.environ.get(k)
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v

    try:
        yield
    finally:
        for mod, attr, old in reversed(saves):
            if old is _MISSING:
                delattr(mod, attr)
            else:
                setattr(mod, attr, old)
        for k, old in env_saves.items():
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old


# -- fake context ------------------------------------------------------------

def _spec_names(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


class FakeContext:
    """Duck-typed stand-in for :class:`triton_dist_tpu.shmem.ShmemContext`
    whose ``shard_map`` is a sequential per-rank replay driver."""

    def __init__(self, mesh_shape: Dict[str, int] | Sequence[Tuple[str, int]]):
        axes = tuple(mesh_shape.items()) if isinstance(mesh_shape, dict) \
            else tuple(mesh_shape)
        self.state = CaptureState(axes)

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(name for name, _ in self.state.axes)

    @property
    def num_ranks(self) -> int:
        return self.state.n

    def axis_size(self, axis=None) -> int:
        if axis is None:
            return self.num_ranks
        if not isinstance(axis, str):
            return _prod(self.state.sizes[a] for a in axis)
        return self.state.sizes[axis]

    def is_dcn_axis(self, axis: str) -> bool:
        return False

    def create_symm_tensor(self, local_shape, dtype, axis=None):
        n = self.axis_size(axis)
        return jnp.zeros((n, *local_shape), dtype)

    def shard(self, x, spec):
        return x

    # ---- the per-rank replay driver

    def _shard_index(self, coords: Dict[str, int], names) -> Tuple[int, int]:
        idx = 0
        n = 1
        for a in names:
            idx = idx * self.state.sizes[a] + coords[a]
            n *= self.state.sizes[a]
        return idx, n

    def _slice_arg(self, x, spec, coords):
        if spec is None or not hasattr(x, "shape"):
            return x
        arr = np.asarray(x)
        index = [slice(None)] * arr.ndim
        for d, entry in enumerate(tuple(spec)):
            names = _spec_names(entry)
            if not names:
                continue
            idx, n = self._shard_index(coords, names)
            seg = arr.shape[d] // n
            index[d] = slice(idx * seg, (idx + 1) * seg)
        return jnp.asarray(arr[tuple(index)])

    def _assemble(self, shards, spec):
        arr0 = np.asarray(shards[0])
        if spec is None:
            return jnp.asarray(arr0)
        shape = list(arr0.shape)
        dims = []
        for d, entry in enumerate(tuple(spec)):
            names = _spec_names(entry)
            if not names:
                continue
            _, n = self._shard_index(self.state.unflatten(0), names)
            shape[d] *= n
            dims.append((d, names))
        full = np.zeros(tuple(shape), arr0.dtype)
        for flat, shard in enumerate(shards):
            coords = self.state.unflatten(flat)
            index = [slice(None)] * len(shape)
            for d, names in dims:
                idx, n = self._shard_index(coords, names)
                seg = shape[d] // n
                index[d] = slice(idx * seg, (idx + 1) * seg)
            full[tuple(index)] = np.asarray(shard)
        return jnp.asarray(full)

    def shard_map(self, f: Callable[..., Any], in_specs, out_specs,
                  axis_names=None):
        def runner(*args):
            if not isinstance(in_specs, (list, tuple)) or isinstance(
                    in_specs, P):
                specs = (in_specs,) * len(args)
            else:
                specs = tuple(in_specs)
            per_rank = []
            for flat in range(self.state.n):
                coords = self.state.unflatten(flat)
                with self.state.rank(coords):
                    shard_args = [self._slice_arg(a, s, coords)
                                  for a, s in zip(args, specs)]
                    per_rank.append(f(*shard_args))
            out0 = per_rank[0]
            if isinstance(out0, (list, tuple)):
                ospecs = out_specs if isinstance(out_specs, (list, tuple)) \
                    and not isinstance(out_specs, P) \
                    else (out_specs,) * len(out0)
                return tuple(
                    self._assemble([r[i] for r in per_rank], s)
                    for i, s in enumerate(ospecs))
            return self._assemble(per_rank, out_specs)

        return runner


# -- top-level capture -------------------------------------------------------

def capture_op(run: Callable[[FakeContext], Any],
               mesh_shape: Dict[str, int] | Sequence[Tuple[str, int]],
               ) -> Dict[int, List[Event]]:
    """Replay ``run(ctx)`` under a fake mesh of ``mesh_shape`` and return the
    recorded per-rank event streams ({flat_rank: [Event, ...]})."""
    ctx = FakeContext(mesh_shape)
    with patched(ctx.state):
        run(ctx)
    return ctx.state.streams()
