"""Low-latency All-to-All + MoE EP dispatch/combine (analog of reference
python/triton_dist/kernels/nvidia/low_latency_all_to_all.py — the README
showcase kernel, 137 µs vs DeepEP's 182 µs — and ep_a2a.py).

Reference protocol (low_latency_all_to_all.py:35-118): one CTA per peer does
``putmem_nbi_block`` of capacity-padded token data + splits into the peer's
symmetric buffer, ``fence``, ``signal_op``; then ``signal_wait_until`` on its
own flags; double-buffered by call-count parity (:125-164).

TPU-native redesign:

- The token-routing scatter the reference does with warp-level atomic slot
  allocation inside the kernel (ep_a2a.py:64-147) has no TPU analog (no
  per-warp atomics); it is a *static-shape scatter* here, computed on the VPU
  with one-hot cumsums (`route_tokens`) — compiler-friendly and fully
  vectorized.
- The wire collective is ``all_to_all_push``: every PE owns a
  ``[n, capacity, ...]`` payload, slot p goes to peer p; delivery is signaled
  by the receive DMA semaphore (no separate flag word needed). Payload sizes
  are static (capacity-padded) — the reference pads to MAX_M the same way
  (:141-147).
- Per-call output buffers + an entry barrier replace the call-count parity
  scheme: a peer cannot write into a buffer instance of call k+1 before
  every PE has entered call k+1.
"""

from __future__ import annotations

import dataclasses
import typing

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.ops.common import collective_id_for
from triton_dist_tpu.shmem import device as shd
from triton_dist_tpu.shmem.context import ShmemContext
from triton_dist_tpu.utils import default_interpret


def _xla_wire(ctx: ShmemContext, axis: str) -> bool:
    """True when this axis' wire exchange must run as plain XLA collectives
    instead of the Pallas remote-DMA kernel: the host-driven DCN tier
    (remote DMA cannot cross a slice boundary). Every ICI axis — and the
    CPU simulator, whose TPU interpreter models remote DMA and semaphores —
    takes the kernel."""
    return ctx.is_dcn_axis(axis)


# ---------------------------------------------------------------------------
# wire collective
# ---------------------------------------------------------------------------

def _quant_slot_pipeline(x_at_p, q_at_p, s_at_p, wire_q, cap, H):
    """Quantize one destination slot's [cap, H] rows into the wire staging
    refs, (128, H) row tiles at a time — the send-edge mirror of
    ``_dequant_slot_pipeline``. Row math is bit-identical to ``_quant``
    (same f32 amax / divide chain; zero rows quantize to zeros with scale
    1). Module-level so the single-device golden test can drive the exact
    kernel tile math without the collective around it."""
    qmax = _qmax(wire_q)
    is_float = jnp.issubdtype(wire_q, jnp.floating)

    def body(x_blk, q_blk, s_blk):
        xf = x_blk[...].astype(jnp.float32)
        amax = jnp.max(jnp.abs(xf), axis=-1)              # [128]
        scale = jnp.where(amax > 0, amax / qmax, 1.0)
        q = xf / scale[:, None]
        if not is_float:
            q = jnp.round(q)
        q_blk[...] = q.astype(wire_q)
        # scale run [i*128, (i+1)*128) of the flattened wire is row i
        # of the [cap//128, 128] side-channel (same layout the dequant
        # pipeline reads back on the receive edge)
        s_blk[...] = scale.reshape(1, -1)

    # whole-(128, H) row tiles: the per-row amax needs the full row in
    # one block, which is why the fused path requires H lane-aligned
    pltpu.emit_pipeline(
        body,
        grid=(cap // 128,),
        in_specs=[pl.BlockSpec((128, H), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((128, H), lambda i: (i, 0)),
                   pl.BlockSpec((1, 128), lambda i: (i, 0))],
    )(x_at_p, q_at_p, s_at_p)


def _dequant_slot_pipeline(q_at_p, s_at_p, o_at_p, out_dtype, cap, H, bn):
    """Dequantize one arrived slot's [cap, H] wire rows into ``o_at_p``,
    (128, bn) tiles at a time (receive edge of the quantized wire)."""

    def body(q_blk, sc_blk, o_blk):
        sc = sc_blk[0]                                    # [128] lanes
        o_blk[...] = (q_blk[...].astype(jnp.float32)
                      * sc[:, None]).astype(out_dtype)

    pltpu.emit_pipeline(
        body,
        grid=(cap // 128, H // bn),
        in_specs=[
            pl.BlockSpec((128, bn), lambda i, j: (i, j)),
            # scale run [i*128, (i+1)*128) of the flattened wire is
            # exactly row i of the [rows, 128] side-channel (the fused
            # path requires cap % 128 == 0 — Mosaic rejects sub-128
            # lane slices)
            pl.BlockSpec((1, 128), lambda i, j: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((128, bn), lambda i, j: (i, j))],
    )(q_at_p, s_at_p, o_at_p)


def _a2a_kernel(axis, mesh_axes, n_arrays, dequant, quant, refs):
    """refs = [in_0..in_{A-1}, (qsend, qsc,)? (deq_out,)?
    out_0..out_{W-1}, send_sems, recv_sems] with W = A wire arrays (A+1
    under ``quant``: the f32 scale wire is appended LAST). Each array is
    [n, ...]: in slot p is the payload for peer p; out slot p is the
    payload received from peer p.

    ``dequant`` (None or ``(out_dtype, cap, H, bn)``; cap % 128 == 0) fuses
    the receive-edge dequantization INTO the collective: array 0 is then the
    quantized [n, cap, H] payload, the LAST array its f32 scale wire
    [n, cap_cols//128, 128], and each peer's slot is dequantized into
    ``deq_out`` as soon as it arrives — early arrivals' dequant overlaps the
    wait for later peers, so only the LAST slot's dequant rides the critical
    path (vs a full extra pass after the kernel). The reference's fp8 wire
    does the same: scales ride the kernel and apply in place
    (low_latency_all_to_all.py:60-88).

    ``quant`` (None or ``(wire_dtype, cap, H)``; cap % 128 == 0) is the
    SEND-side mirror: in_0 is a [n, cap, H] compute-dtype payload that is
    quantized per-row into the ``qsend``/``qsc`` staging buffers — slot p
    tile-by-tile, IMMEDIATELY before slot p's put is issued — so peer p's
    wire bytes leave as soon as its slot is quantized instead of after a
    whole-buffer pass, and no standalone qpack pass exists outside the
    collective. Row math is bit-identical to ``_quant`` (same f32 amax /
    divide chain, zero rows quantize to zeros with scale 1)."""
    ins = refs[:n_arrays]
    off = n_arrays
    if quant is not None:
        qsend, qsc = refs[off], refs[off + 1]
        off += 2
    deq = None
    if dequant is not None:
        deq = refs[off]
        off += 1
    n_wire = n_arrays + (1 if quant is not None else 0)
    outs = refs[off:off + n_wire]
    send_sems, recv_sems = refs[off + n_wire:]
    me = shd.my_pe(axis)
    n = shd.n_pes(axis)

    # send sources: under quant, the staged wire payload replaces in_0 and
    # the staged scales ride as the extra LAST wire array
    srcs = ((qsend,) + tuple(ins[1:]) + (qsc,)) if quant is not None else ins

    shd.barrier_all((axis,), mesh_axes=mesh_axes)

    def quant_slot(p):
        wire_q, cap, H = quant
        _quant_slot_pipeline(ins[0].at[p], qsend.at[p], qsc.at[p],
                             wire_q, cap, H)

    if quant is not None:
        quant_slot(me)
    local_copies = []
    for a in range(n_wire):
        c = pltpu.make_async_copy(srcs[a].at[me], outs[a].at[me],
                                  recv_sems.at[a, me])
        c.start()
        local_copies.append(c)
    rdmas = []
    for p in range(1, n):
        dst = lax.rem(me + p, n)
        pid = shd.pe_at(mesh_axes, axis, dst)
        if quant is not None:
            quant_slot(dst)   # slot dst's wire bytes exist just in time
        for a in range(n_wire):
            rdmas.append(shd.putmem_nbi(outs[a].at[me], srcs[a].at[dst],
                                        send_sems.at[a, dst],
                                        recv_sems.at[a, me], pid))

    def dequant_slot(p):
        out_dtype, cap, H, bn = dequant
        _dequant_slot_pipeline(outs[0].at[p], outs[-1].at[p], deq.at[p],
                               out_dtype, cap, H, bn)

    for c in local_copies:
        c.wait()
    if dequant is not None:
        dequant_slot(me)
    for p in range(1, n):
        src = lax.rem(me + p, n)
        # every WIRE array, i.e. including the scale wire ``quant``
        # appends past the caller's arrays: an unwaited delivery is read
        # before it lands and leaves its semaphore signalled at exit
        for a in range(n_wire):
            shd.wait_recv(outs[a].at[src], recv_sems.at[a, src])
        if dequant is not None:
            dequant_slot(src)
    shd.quiet(*rdmas)


def all_to_all_push(ctx: ShmemContext, *arrays: jax.Array,
                    axis: str | None = None,
                    spec: P | None = None,
                    dequant_to=None,
                    fuse_dequant: bool = True,
                    quant_from=None,
                    fuse_quant: bool = True) -> tuple[jax.Array, ...]:
    """Generic low-latency All-to-All: each input is locally ``[n, ...]``
    where slot p is the payload destined for peer p along ``axis``. Returns
    same-shaped arrays where local slot p holds the payload *received from*
    peer p. One kernel, one put per (peer, array), arrival = DMA semaphore.

    ``spec`` is the dim-0 sharding of the global arrays. The default
    ``P(axis)`` means globally ``[n*n, ...]`` with devices differing only on
    other mesh axes holding replicas (data-parallel semantics). Pass
    ``P(mesh_axes)`` (flat, globally ``[n_devices*n, ...]``) when every
    device holds distinct payloads — e.g. one tier of the hierarchical
    dispatch.

    ``dequant_to=<dtype>`` fuses the receive-edge dequantization into the
    kernel (quantized-wire convention: ``arrays[0]`` is the [n, cap, H]
    payload, ``arrays[-1]`` its per-slot f32 scale wire). The first returned
    array is then [n, cap, H] in ``<dtype>`` — each peer's slot dequantized
    as it arrived, overlapping the waits for later peers.
    ``fuse_dequant=False`` keeps the dequant as one post-kernel XLA pass
    instead (cheaper at n=1 where there are no later-peer waits to hide the
    in-kernel pipeline behind; see docs/benchmarks.md fp8-edge table).

    ``quant_from=<wire dtype>`` is the send-side mirror: ``arrays[0]`` is a
    compute-dtype [n, cap, H] payload that the KERNEL quantizes per
    destination slot, tile-by-tile, immediately before that slot's put —
    no standalone qpack pass precedes the collective, and peer p's bytes
    leave as soon as slot p is quantized. The f32 scale wire is created
    internally and returned as the LAST output (so returns have
    ``len(arrays) + 1`` entries: quantized payload (or its dequantized form
    under ``dequant_to``), pass-through arrays, scale). Sub-128 caps, DCN
    tiers and ``fuse_quant=False`` fall back to one XLA quantize pass in
    front of the plain wire push — same outputs, bit-identical rows."""
    axis = axis or ctx.axis_names[0]
    n = ctx.axis_size(axis)
    mesh_axes = ctx.axis_names
    spec = spec if spec is not None else P(axis)
    n_arrays = len(arrays)
    quant = None
    if quant_from is not None:
        wire_q = jnp.dtype(quant_from)
        cap_q, H_q = arrays[0].shape[-2:]
        q_aligned = cap_q % 128 == 0 and H_q % 128 == 0
        if _xla_wire(ctx, axis) or not (fuse_quant and q_aligned):
            # send-edge fallback (host-driven DCN tier,
            # sub-128 caps that can't take the in-kernel (128, H) row
            # tiles, or an explicit fuse_quant=False): one XLA quantize
            # pass, then the plain quantized-wire push below
            cols = _id_cols(cap_q)

            def _qpack(x):
                nl = x.shape[0]
                q, s = _quant(x.reshape(nl * cap_q, H_q), wire_q)
                sc = jnp.ones((nl, cols), jnp.float32).at[:, :cap_q].set(
                    s.reshape(nl, cap_q))
                return q.reshape(x.shape), sc.reshape(nl, -1, 128)

            pq, psc = ctx.shard_map(_qpack, in_specs=spec,
                                    out_specs=(spec, spec))(arrays[0])
            return all_to_all_push(ctx, pq, *arrays[1:], psc, axis=axis,
                                   spec=spec, dequant_to=dequant_to,
                                   fuse_dequant=fuse_dequant)
        quant = (wire_q, cap_q, H_q)
    if _xla_wire(ctx, axis):
        # DCN tier:
        # remote DMA cannot cross a slice boundary — run this axis'
        # exchange as an XLA ``lax.all_to_all`` (host-driven DCN
        # transfers, XLA-scheduled). Identical slot semantics: local slot
        # p of dim -3 goes to peer p / arrives from peer p. The
        # hierarchical ops compose per-axis pushes, so marking the outer
        # axis DCN re-routes exactly that tier (reference inter-node
        # transport split, allgather.py:291-375).
        def xla_tier(*shards):
            # local view: every wire array is [n, ...] with dim 0 = peer
            # slot; exchanging dim 0 IS the push semantics
            return tuple(
                lax.all_to_all(s, axis, split_axis=0, concat_axis=0,
                               tiled=True)
                for s in shards)

        sm = ctx.shard_map(xla_tier, in_specs=tuple(spec for _ in arrays),
                           out_specs=tuple(spec for _ in arrays))
        out = sm(*arrays)
        if dequant_to is not None:
            cap = arrays[0].shape[-2]
            scale = out[-1].reshape(out[-1].shape[0], -1)[:, :cap]
            return (_dequant(out[0], scale, dequant_to),) + out[1:]
        return out
    dequant = None
    cap = None
    if dequant_to is not None:
        import math
        if quant is None:
            assert n_arrays >= 2, "quantized wire needs payload + scale arrays"
        _, cap, H = arrays[0].shape[-3:]
        if fuse_dequant and cap % 128 == 0 and H % 128 == 0:
            # in-kernel per-arrival dequant (sub-128 caps or hidden dims
            # would need unaligned lane slices — gcd(512, H) < 128 makes
            # the (128, bn) BlockSpec lane-unaligned — which Mosaic
            # rejects; those fall back to the post-kernel pass below)
            dequant = (jnp.dtype(dequant_to), cap, H, math.gcd(512, H))

    def f(*shards):
        kernel = lambda *refs: _a2a_kernel(axis, mesh_axes, n_arrays,
                                           dequant, quant, refs)
        n_loc = shards[0].shape[0]
        pre = ()
        if quant is not None:
            q_sds = jax.ShapeDtypeStruct(shards[0].shape, wire_q)
            sc_sds = jax.ShapeDtypeStruct((n_loc, cap_q // 128, 128),
                                          jnp.float32)
            pre = (q_sds, sc_sds)       # send-side staging (wire + scales)
            wire_outs = (q_sds,) + tuple(
                jax.ShapeDtypeStruct(s.shape, s.dtype)
                for s in shards[1:]) + (sc_sds,)
        else:
            wire_outs = tuple(
                jax.ShapeDtypeStruct(s.shape, s.dtype) for s in shards)
        deq_shape = ()
        if dequant is not None:
            deq_shape = (jax.ShapeDtypeStruct(shards[0].shape, dequant[0]),)
        n_wire = len(wire_outs)
        out = pl.pallas_call(
            kernel,
            out_shape=pre + deq_shape + wire_outs,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_arrays,
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),) * (
                len(pre) + len(deq_shape) + n_wire),
            scratch_shapes=[
                pltpu.SemaphoreType.DMA((n_wire, n)),
                pltpu.SemaphoreType.DMA((n_wire, n)),
            ],
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True,
                # keyed by axis: the 2-tier dispatch runs two of these
                # back-to-back over different axes — sharing one physical
                # barrier semaphore would let stage-2 signals satisfy
                # devices still waiting in stage 1 (cf. allgather.py)
                collective_id=collective_id_for(f"all_to_all_{axis}")),
            interpret=default_interpret(),
        )(*shards)
        out = out[len(pre):]            # drop the send-side staging
        if dequant is not None:
            # visible outs = (dequantized, raw wire ws, rest...): swap the
            # raw payload ws for the dequantized buffer, keep the rest
            return (out[0],) + out[2:]
        if dequant_to is not None:
            # unfused fallback (cap not 128-aligned): one XLA pass after
            # the kernel
            scale = out[-1].reshape(out[-1].shape[0], -1)[:, :cap]
            return (_dequant(out[0], scale, dequant_to),) + out[1:]
        return out if isinstance(out, tuple) else (out,)

    n_out = n_arrays + (1 if quant is not None else 0)
    sm = ctx.shard_map(f, in_specs=tuple(spec for _ in arrays),
                       out_specs=tuple(spec for _ in range(n_out)))
    return sm(*arrays)


def _seg_chunks(shape: tuple, segments: int, itemsize: int):
    """Static per-segment ``(row_offset, rows)`` split of one wire array's
    slot rows (dim 1 of the local ``[n, rows, ...]`` view), each boundary
    rounded DOWN to the dtype's sublane tile so every chunk's DMA slice
    meets Mosaic's tiling alignment (same 8/16/32-row tiles as
    ``_cap_round``). Arrays too small (or too low-rank) to split ride whole
    in segment 0 — the ``"full"`` sentinel — so side-channels like the id
    wire gate on the first segment's signal. Degenerate chunks are ``None``
    (no put, no wait)."""
    if len(shape) < 3:
        return ("full",) + (None,) * (segments - 1)
    rows = shape[1]
    align = max(1, 32 // max(1, itemsize))
    bounds = [0]
    for s in range(1, segments):
        b = (rows * s // segments) // align * align
        bounds.append(max(bounds[-1], min(b, rows)))
    bounds.append(rows)
    if bounds[1] == 0 and segments > 1:
        # alignment swallowed the split: don't degrade to an all-in-the-
        # LAST-segment schedule — ship whole under segment 0 instead
        return ("full",) + (None,) * (segments - 1)
    return tuple(
        (bounds[s], bounds[s + 1] - bounds[s])
        if bounds[s + 1] > bounds[s] else None
        for s in range(segments))


def _seg_view(ref, idx, chunk):
    """The ref slice one segment chunk addresses: the whole peer slot for
    the ``"full"`` sentinel, a static-size row window otherwise."""
    if chunk == "full":
        return ref.at[idx]
    off, rows = chunk
    return ref.at[idx, pl.ds(off, rows)]


def _a2a_seg_kernel(axis, mesh_axes, n_arrays, chunks, refs):
    """Segmented counted-signal variant of ``_a2a_kernel`` (plain wire
    arrays only — the quant/dequant edges run as XLA passes outside).

    ``chunks[a]`` is the static per-segment row split of array ``a``
    (``_seg_chunks``). The producer issues the puts of one (peer, segment)
    pair and then ANNOUNCES the segment with one counted
    ``shd.signal_op(+1)`` on the peer's per-segment REGULAR semaphore —
    ``ops/page_migrate.py``'s counted-signal protocol. The consumer gates on
    ``shd.signal_wait_until(seg_sems[s], n-1)`` per segment in FIXED order
    and only then drains that segment's receive DMA semaphores — so a
    caller interleaving compute between segment waits overlaps segment
    s+1's flight time with segment s's compute while consuming arrivals in
    a rank-independent order. Every byte lands in the same slot as the
    unsegmented kernel: outputs are bitwise identical, only the schedule is
    finer."""
    segments = len(chunks[0])
    ins = refs[:n_arrays]
    outs = refs[n_arrays:2 * n_arrays]
    send_sems = refs[2 * n_arrays]
    recv_sems = refs[2 * n_arrays + 1]
    seg_sems = refs[2 * n_arrays + 2:]
    me = shd.my_pe(axis)
    n = shd.n_pes(axis)

    shd.barrier_all((axis,), mesh_axes=mesh_axes)

    local_copies = []
    for a in range(n_arrays):
        c = pltpu.make_async_copy(ins[a].at[me], outs[a].at[me],
                                  recv_sems.at[a, me, 0])
        c.start()
        local_copies.append(c)
    rdmas = []
    for p in range(1, n):
        dst = lax.rem(me + p, n)
        pid = shd.pe_at(mesh_axes, axis, dst)
        for s in range(segments):
            for a in range(n_arrays):
                if chunks[a][s] is None:
                    continue
                rdmas.append(shd.putmem_nbi(
                    _seg_view(outs[a], me, chunks[a][s]),
                    _seg_view(ins[a], dst, chunks[a][s]),
                    send_sems.at[a, dst, s],
                    recv_sems.at[a, me, s], pid))
            # announce segment s the moment its puts are in flight —
            # the peer's gate for starting compute on s while s+1 flies
            shd.signal_op(seg_sems[s], 1, pe=pid)
    for c in local_copies:
        c.wait()
    if n > 1:
        for s in range(segments):
            shd.signal_wait_until(seg_sems[s], n - 1)
            for p in range(1, n):
                src = lax.rem(me + p, n)
                for a in range(n_arrays):
                    if chunks[a][s] is None:
                        continue
                    shd.wait_recv(_seg_view(outs[a], src, chunks[a][s]),
                                  recv_sems.at[a, src, s])
    shd.quiet(*rdmas)


def all_to_all_push_seg(ctx: ShmemContext, *arrays: jax.Array,
                        axis: str | None = None,
                        spec: P | None = None,
                        segments: int = 2,
                        dequant_to=None,
                        fuse_dequant: bool = False,
                        quant_from=None,
                        fuse_quant: bool = False) -> tuple[jax.Array, ...]:
    """Segmented counted-signal variant of ``all_to_all_push`` — the wire
    collective behind the serving overlap schedule (ISSUE 16). Each
    (peer, array) payload is split row-wise into ``segments`` static
    chunks; the producer announces every segment with one counted
    ``signal_op`` after its puts are issued and the consumer drains
    segments in fixed order behind per-segment ``signal_wait_until`` gates
    (``ops/page_migrate.py``'s protocol). The same bytes land in the same
    slots as the plain push — outputs are BITWISE identical; only the
    delivery schedule is finer, which is what lets the microbatched EP
    pipeline overlap expert compute with the next microbatch's flight.

    ``fuse_dequant`` / ``fuse_quant`` are accepted for call-site parity
    with ``all_to_all_push`` and ignored: the segmented wire always takes
    the UNFUSED quant/dequant edges (one XLA pass outside the collective),
    whose rows are bit-identical to the fused in-kernel pipelines by
    construction (same f32 amax/divide chain — see ``_quant_slot_pipeline``).
    DCN tiers fall back to ``all_to_all_push``'s XLA
    exchange — identical slot semantics, identical bytes."""
    del fuse_dequant, fuse_quant
    axis = axis or ctx.axis_names[0]
    segments = max(1, int(segments))
    spec = spec if spec is not None else P(axis)
    if quant_from is not None:
        # always the send-edge XLA quantize pass (bit-identical rows to the
        # fused path), then the plain quantized-wire segmented push
        wire_q = jnp.dtype(quant_from)
        cap_q, H_q = arrays[0].shape[-2:]
        cols = _id_cols(cap_q)

        def _qpack(x):
            nl = x.shape[0]
            q, s = _quant(x.reshape(nl * cap_q, H_q), wire_q)
            sc = jnp.ones((nl, cols), jnp.float32).at[:, :cap_q].set(
                s.reshape(nl, cap_q))
            return q.reshape(x.shape), sc.reshape(nl, -1, 128)

        pq, psc = ctx.shard_map(_qpack, in_specs=spec,
                                out_specs=(spec, spec))(arrays[0])
        return all_to_all_push_seg(ctx, pq, *arrays[1:], psc, axis=axis,
                                   spec=spec, segments=segments,
                                   dequant_to=dequant_to)
    if _xla_wire(ctx, axis):
        return all_to_all_push(ctx, *arrays, axis=axis, spec=spec,
                               dequant_to=dequant_to, fuse_dequant=False)
    n = ctx.axis_size(axis)
    mesh_axes = ctx.axis_names
    n_arrays = len(arrays)
    cap = arrays[0].shape[-2] if dequant_to is not None else None

    def f(*shards):
        chunks = tuple(
            _seg_chunks(s.shape, segments, jnp.dtype(s.dtype).itemsize)
            for s in shards)
        n_segs = len(chunks[0])
        kernel = lambda *refs: _a2a_seg_kernel(axis, mesh_axes, n_arrays,
                                               chunks, refs)
        out = pl.pallas_call(
            kernel,
            out_shape=tuple(jax.ShapeDtypeStruct(s.shape, s.dtype)
                            for s in shards),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_arrays,
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),) * n_arrays,
            scratch_shapes=(
                [pltpu.SemaphoreType.DMA((n_arrays, n, n_segs)),
                 pltpu.SemaphoreType.DMA((n_arrays, n, n_segs))]
                + [pltpu.SemaphoreType.REGULAR] * n_segs),
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True,
                collective_id=collective_id_for(f"all_to_all_seg_{axis}")),
            interpret=default_interpret(),
        )(*shards)
        return out if isinstance(out, tuple) else (out,)

    sm = ctx.shard_map(f, in_specs=tuple(spec for _ in arrays),
                       out_specs=tuple(spec for _ in arrays))
    out = sm(*arrays)
    if dequant_to is not None:
        scale = out[-1].reshape(out[-1].shape[0], -1)[:, :cap]
        return (_dequant(out[0], scale, dequant_to),) + out[1:]
    return out


# ---------------------------------------------------------------------------
# MoE EP dispatch / combine
# ---------------------------------------------------------------------------

class QuantTokens(typing.NamedTuple):
    """Quantized-wire tokens as dispatched: ``q`` [..., cap, H] in the wire
    dtype plus the per-slot f32 ``scale`` [..., cap]. Produced by
    ``dispatch`` under ``dequant_edge="expert"`` — the scales are meant to
    be consumed by the expert grouped GEMM's accumulator
    (``ops.group_gemm.grouped_gemm(row_scale=...)``), never applied in a
    standalone pass; the reference's fp8 protocol works the same way (its
    post_process only slices, low_latency_all_to_all.py:251-270 — scales
    ride into the expert GEMM)."""
    q: jax.Array
    scale: jax.Array

@dataclasses.dataclass(frozen=True)
class EpAllToAllContext:
    """Analog of the reference's A2A context dataclass
    (low_latency_all_to_all.py:125-164): static shapes + mesh info.
    ``capacity`` is the per-(src,dst) token budget — tokens routed beyond it
    are dropped (standard expert-capacity semantics; the reference instead
    sizes buffers for the worst case, which equals
    ``capacity = max_tokens * topk``).

    ``wire_dtype`` (e.g. ``jnp.float8_e4m3fn`` or ``jnp.int8``) enables the
    quantized wire format: tokens ride the A2A as per-token symmetric
    quantized rows plus an f32 scale side-channel payload, halving (vs bf16)
    the wire bytes — the reference's fp8+scales showcase protocol
    (low_latency_all_to_all.py:60-88, README.md:55). Dequantization happens
    at the receiving edge; expert compute stays in ``dtype``.

    The wire-edge strategies (swept on-chip at the DeepSeek-infer
    shape, round 4 — docs/benchmarks.md fp8-edge table):
    - ``quant_edge``: "fused" (default, measured 93.5 µs dispatch) gathers
      rows and quantizes per slot in one fused XLA pass; "pre" (131.9 µs)
      quantizes the T source rows once and gathers the 1-byte wire rows —
      slower on TPU: sub-word row gathers don't vectorize as well as the
      fused f32 gather+quant chain. "kernel" gathers rows in the compute
      dtype and quantizes INSIDE the collective, per destination slot,
      immediately before that slot's put (``all_to_all_push(quant_from=)``)
      — peer p's wire bytes leave as soon as slot p is quantized, the
      multi-chip mirror of the per-arrival dequant.
    - ``dequant_edge``: "post" (default) = one XLA pass after the
      collective; "kernel" = per-arrival in-kernel ``emit_pipeline``
      dequant. Measured +106-125 µs at n=1 — the pipeline's fine-grained
      (128, bn) steps cost far more than the one fused XLA pass, so
      "kernel" is only worth trying multi-chip where it overlaps waits
      for later peers. "expert" skips dequantization entirely:
      ``dispatch`` returns ``QuantTokens(q, scale)`` and the expert
      grouped GEMM folds the scale into its f32 accumulator
      (``grouped_gemm(row_scale=...)``) — no dequant pass anywhere, and
      the expert reads half the token bytes. This is the reference's
      architecture (scales ride into the expert GEMM; its post_process
      never applies them).

    ``expert_major``: lay each (src, dst) capacity block out EXPERT-major —
    slots are grouped per (dst rank, local expert) with a per-expert budget
    ``capacity_per_expert = capacity // experts_per_rank``, so multinomial
    routing spill past one expert's budget is capped AT THE SOURCE instead
    of raggedly padding the receiver's block alignment (the roofline
    attributes ~25 % extra weight traffic to that padding: ≈20-of-16 used
    blocks at the DeepSeek serving shape). Rows
    ``[e*cap_e, (e+1)*cap_e)`` of every received src block belong to local
    expert ``e`` by construction, which makes the consumer's block→expert
    table a static constant and deletes the align gather/scatter passes
    entirely when ``cap_e`` is a block_m multiple
    (``moe_mlp_ep_overlap``). Trade-off: drops are per (src, dst, expert)
    rather than per (src, dst) — heavier skew toward one expert drops
    sooner; size ``capacity`` accordingly."""
    ctx: ShmemContext
    axis: str
    max_tokens: int      # tokens per rank entering dispatch
    hidden: int
    topk: int
    num_experts: int     # global expert count
    capacity: int        # slots per (src,dst) rank pair
    dtype: jnp.dtype = jnp.bfloat16
    wire_dtype: jnp.dtype | None = None
    quant_edge: str = "fused"     # "fused" | "pre" | "kernel"
    dequant_edge: str = "post"    # "post" | "kernel"
    expert_major: bool = False
    # >= 2: the wire collective runs as ``all_to_all_push_seg`` with this
    # many per-peer segments — the counted-signal schedule the serving
    # overlap path rides (ISSUE 16). Same bytes, same slots, bit-identical
    # outputs; 0/1 keeps the plain one-put-per-(peer, array) push.
    seg_push: int = 0

    def _dequant_in_kernel(self) -> bool:
        return self.dequant_edge == "kernel"

    @property
    def n_ranks(self) -> int:
        return self.ctx.axis_size(self.axis)

    @property
    def experts_per_rank(self) -> int:
        return self.num_experts // self.n_ranks

    @property
    def capacity_per_expert(self) -> int:
        assert self.expert_major, "capacity is per-rank unless expert_major"
        return self.capacity // self.experts_per_rank


# --- wire-dtype auto-selection (wire-fit driven) ---------------------------
#
# ``wire_dtype="auto"`` picks bf16 vs fp8 per message size from the same
# wire model bench.py's ``bench_a2a_wire_fit`` emits per dtype:
# ``t = t0 + bytes/BW``. fp8 moves half the payload bytes but pays a fixed
# quant/dequant + f32-scale-wire latency, so small dispatches (latency-
# dominated) keep the bf16 wire and large ones (bandwidth-dominated) take
# the fp8 win. Feed measured fits through ``wire_fit=`` — the
# ``{"bf16": {"t0_us", "gb_per_s"}, "fp8": {...}}`` shape of bench.py's
# ``a2a_wire_fit`` extras. The defaults below encode the ICI egress
# roofline (bench.py ``_ICI_EGRESS_GBS``) with a conservative fp8 latency
# premium (quant + dequant XLA passes + the scale side-channel) and only
# matter until a measured fit artifact is supplied.

_DEFAULT_WIRE_FIT = {
    "bf16": {"t0_us": 5.0, "gb_per_s": 180.0},
    "fp8": {"t0_us": 25.0, "gb_per_s": 180.0},
}


def a2a_wire_bytes(n_ranks: int, max_tokens: int, hidden: int, topk: int,
                   wire_dtype=None) -> int:
    """Dispatch+combine wire bytes for one rank at the drop-proof capacity
    (bench.py ``_wire_bytes`` twin — keep the formulas in sync): payload at
    the wire itemsize plus the int32 id columns, plus the f32 scale
    side-channel when quantized."""
    itemsize = jnp.dtype(wire_dtype or jnp.bfloat16).itemsize
    cap = _cap_round(max_tokens * topk, itemsize)
    idc = _id_cols(cap)
    b = n_ranks * (cap * hidden * itemsize + idc * 4)
    if wire_dtype is not None:
        b += n_ranks * idc * 4
    return 2 * b


def pick_wire_dtype(n_ranks: int, max_tokens: int, hidden: int, topk: int,
                    wire_fit: dict | None = None,
                    fp8_dtype=jnp.float8_e4m3fn):
    """Resolve ``wire_dtype="auto"``: ``None`` (bf16 wire) or ``fp8_dtype``,
    whichever the per-dtype wire fit predicts faster at this message size.
    Ties keep the bf16 wire (no quant pass to maintain)."""
    fit = wire_fit or _DEFAULT_WIRE_FIT

    def t_us(dt, seg):
        b = a2a_wire_bytes(n_ranks, max_tokens, hidden, topk, dt)
        return fit[seg]["t0_us"] + b / (fit[seg]["gb_per_s"] * 1e3)

    return None if t_us(None, "bf16") <= t_us(fp8_dtype, "fp8") else fp8_dtype


def create_all_to_all_context(ctx: ShmemContext, max_tokens: int, hidden: int,
                              topk: int, num_experts: int,
                              capacity: int | None = None,
                              axis: str | None = None,
                              dtype=jnp.bfloat16,
                              wire_dtype=None,
                              wire_fit: dict | None = None,
                              quant_edge: str = "fused",
                              dequant_edge: str = "post",
                              expert_major: bool = False,
                              seg_push: int = 0
                              ) -> EpAllToAllContext:
    axis = axis or ctx.axis_names[0]
    n = ctx.axis_size(axis)
    if isinstance(wire_dtype, str):
        assert wire_dtype == "auto", wire_dtype
        wire_dtype = pick_wire_dtype(n, max_tokens, hidden, topk,
                                     wire_fit=wire_fit)
    assert num_experts % n == 0, (num_experts, n)
    assert quant_edge in ("pre", "fused", "kernel"), quant_edge
    assert dequant_edge in ("kernel", "post", "expert"), dequant_edge
    if capacity is None:
        capacity = max_tokens * topk  # worst case: everything to one rank
    wire_itemsize = jnp.dtype(wire_dtype or dtype).itemsize
    capacity = _cap_round(capacity, wire_itemsize)
    if expert_major:
        # split the per-rank budget evenly per local expert, each sublane
        # tile-rounded so every expert segment is independently DMA-aligned
        epr = num_experts // n
        cap_e = _cap_round(-(-capacity // epr), wire_itemsize)
        capacity = cap_e * epr
    assert hidden % 128 == 0, f"hidden={hidden} must be a lane multiple (128)"
    return EpAllToAllContext(ctx=ctx, axis=axis, max_tokens=max_tokens,
                             hidden=hidden, topk=topk,
                             num_experts=num_experts, capacity=capacity,
                             dtype=jnp.dtype(dtype),
                             wire_dtype=(jnp.dtype(wire_dtype)
                                         if wire_dtype is not None else None),
                             quant_edge=quant_edge,
                             dequant_edge=dequant_edge,
                             expert_major=expert_major,
                             seg_push=int(seg_push))


def route_tokens(a2a: EpAllToAllContext, topk_ids: jax.Array):
    """Static-shape routing (replaces the reference's in-kernel atomic slot
    allocation, ep_a2a.py:64-147). ``topk_ids`` is the *local* [T, topk]
    expert assignment. Returns (dest [T,k], slot [T,k], valid [T,k]) where
    ``slot`` is the token's position in the capacity-padded lane to rank
    ``dest``. Pure jnp under jit/shard_map; a host routing table (numpy
    ``topk_ids``) takes the native C++ path (``csrc.a2a_slot_assign`` —
    the registered-host-op analog, csrc registry.cc:32-44) with no device
    round-trip. The twins are cross-tested in test_tools.py.

    Under ``expert_major`` the slot allocation groups by (dest rank, LOCAL
    expert) — the global expert id is the virtual destination over
    ``num_experts`` groups of ``capacity_per_expert`` slots each — and the
    returned slot is ``local_expert * cap_e + rank_in_group``, so each
    (src, dst) block arrives expert-segmented and per-expert spill drops at
    the source (see ``EpAllToAllContext.expert_major``)."""
    import numpy as np
    T, k = topk_ids.shape
    epr = a2a.experts_per_rank
    em = getattr(a2a, "expert_major", False)
    cap_e = a2a.capacity_per_expert if em else None
    if isinstance(topk_ids, np.ndarray) and not isinstance(
            topk_ids, jax.Array):
        from triton_dist_tpu import csrc
        ids32 = topk_ids.astype(np.int32)
        dest = ids32 // epr
        if em:
            # same counter kernel, finer groups: one per global expert
            res = csrc.native_or_none("a2a_slot_assign", ids32.reshape(-1),
                                      a2a.num_experts, cap_e)
            if res is not None:
                r, valid = res
                slot = (ids32.reshape(-1) % epr) * cap_e + r
                return dest, slot.reshape(T, k), valid.reshape(T, k)
        else:
            res = csrc.native_or_none("a2a_slot_assign", dest.reshape(-1),
                                      a2a.n_ranks, a2a.capacity)
            if res is not None:
                slot, valid = res
                return dest, slot.reshape(T, k), valid.reshape(T, k)
    dest = topk_ids // epr                                       # [T,k]
    if em:
        r, valid = _slot_assign(topk_ids.reshape(-1), a2a.num_experts, cap_e)
        slot = (topk_ids.reshape(-1) % epr) * cap_e + r
        return dest, slot.reshape(T, k), valid.reshape(T, k)
    slot, valid = _slot_assign(dest.reshape(-1), a2a.n_ranks, a2a.capacity)
    return dest, slot.reshape(T, k), valid.reshape(T, k)


def _a2a_push_fn(a2a):
    """The wire collective for this context: the plain one-put-per-(peer,
    array) push, or — ``seg_push >= 2`` — the segmented counted-signal push
    the serving overlap schedule rides. Bit-identical outputs either way
    (same bytes, same slots); only the delivery schedule differs."""
    if getattr(a2a, "seg_push", 0) >= 2:
        import functools
        return functools.partial(all_to_all_push_seg, segments=a2a.seg_push)
    return all_to_all_push


def dispatch(a2a: EpAllToAllContext, tokens: jax.Array, topk_ids: jax.Array):
    """EP dispatch (analog of ``fast_all_to_all``,
    low_latency_all_to_all.py:189-248). Global inputs sharded P(axis):
    ``tokens`` [n*T, H], ``topk_ids`` [n*T, topk]. Returns
    (recv_tokens [n, n, capacity, H] P(axis), recv_ids [n, n, capacity]
    P(axis), layout) — receiver slot (src, c) holds a token from rank src
    targeting local expert recv_ids[src, c] (or -1 padding). ``layout`` is
    kept for ``combine``."""
    ctx, axis = a2a.ctx, a2a.axis
    n, cap, H, k = a2a.n_ranks, a2a.capacity, a2a.hidden, a2a.topk
    assert tokens.shape == (n * a2a.max_tokens, H), (
        f"dispatch: tokens {tokens.shape} != "
        f"({n}*{a2a.max_tokens}, {H}) from the a2a context")
    assert topk_ids.shape == (n * a2a.max_tokens, k), (
        f"dispatch: topk_ids {topk_ids.shape} != ({n * a2a.max_tokens}, {k})")

    id_cols = _id_cols(cap)  # lane-aligned ids wire
    wire = a2a.wire_dtype
    # quant_edge="kernel": the gather stays in the compute dtype and the
    # collective quantizes per destination slot just before its put
    kq = wire is not None and a2a.quant_edge == "kernel"

    def build(tok_shard, ids_shard):
        dest, slot, valid = route_tokens(a2a, ids_shard)
        T = tok_shard.shape[0]
        d_f, s_f, v_f = (x.reshape(-1) for x in (dest, slot, valid))
        # over-capacity tokens get an out-of-bounds slot -> dropped by the
        # scatter (never clobbering a valid slot)
        s_drop = jnp.where(v_f, s_f, cap)
        local_eid = (ids_shard % a2a.experts_per_rank).reshape(-1)

        src = _slot_src_map(d_f, s_drop,
                            jnp.arange(T * k, dtype=jnp.int32) // k,
                            n, cap, T)
        if wire is not None and a2a.quant_edge == "pre":
            send_buf, send_sc = _slot_gather_prequant(tok_shard, src, wire,
                                                      n, id_cols, cap)
        elif wire is not None and not kq:
            # fused gather+quant: one logical pass builds wire buf + scales
            send_buf, sc = _slot_gather_quant(tok_shard, src, wire)
            send_sc = jnp.ones((n, id_cols), jnp.float32).at[:, :cap].set(
                sc).reshape(n, -1, 128)
        else:
            send_buf = _slot_gather(tok_shard, src, a2a.dtype)
        send_ids = jnp.full((n, id_cols), -1, jnp.int32).at[
            d_f, s_drop].set(local_eid, mode="drop")
        # wire format: [n, rows, 128] so the per-peer DMA slice is
        # lane-aligned on real TPUs
        outs = (send_buf, send_ids.reshape(n, id_cols // 128, 128))
        if wire is not None and not kq:
            outs += (send_sc,)
        return outs + (dest, slot, valid)

    n_wire = 3 if (wire is not None and not kq) else 2
    sm = ctx.shard_map(build, in_specs=(P(axis), P(axis)),
                       out_specs=(P(axis),) * (n_wire + 3))
    if wire is not None and not kq:
        send_buf, send_ids, send_sc, dest, slot, valid = sm(tokens, topk_ids)
    else:
        send_buf, send_ids, dest, slot, valid = sm(tokens, topk_ids)
    push = _a2a_push_fn(a2a)
    if wire is not None and a2a.dequant_edge == "expert":
        # no dequantization anywhere: tokens stay in the wire dtype and the
        # scales ride alongside for the expert GEMM's accumulator
        if kq:
            recv_q, recv_ids_wire, recv_sc = push(
                ctx, send_buf, send_ids, axis=axis, quant_from=wire)
        else:
            recv_q, recv_ids_wire, recv_sc = push(
                ctx, send_buf, send_ids, send_sc, axis=axis)
        unpack_sc = ctx.shard_map(
            lambda w: w.reshape(n, -1)[:, :cap],
            in_specs=P(axis), out_specs=P(axis))
        recv_tokens = QuantTokens(q=recv_q, scale=unpack_sc(recv_sc))
    elif wire is not None:
        # dequant at the receive edge, per the context's dequant_edge
        # policy: one post-kernel XLA pass (default) or per-arrival
        # in-kernel (multi-chip experiment: overlaps later peers' waits)
        if kq:
            recv_tokens, recv_ids_wire, _ = push(
                ctx, send_buf, send_ids, axis=axis, quant_from=wire,
                dequant_to=a2a.dtype, fuse_dequant=a2a._dequant_in_kernel())
        else:
            recv_tokens, recv_ids_wire, _ = push(
                ctx, send_buf, send_ids, send_sc, axis=axis,
                dequant_to=a2a.dtype, fuse_dequant=a2a._dequant_in_kernel())
    else:
        recv_tokens, recv_ids_wire = push(ctx, send_buf, send_ids,
                                          axis=axis)
    unpack = ctx.shard_map(
        lambda w: w.reshape(n, id_cols)[:, :cap],
        in_specs=P(axis), out_specs=P(axis))
    recv_ids = unpack(recv_ids_wire)
    layout = (dest, slot, valid)
    return recv_tokens, recv_ids, layout


def combine(a2a: EpAllToAllContext, processed: jax.Array, layout,
            topk_weights: jax.Array) -> jax.Array:
    """EP combine (analog of ``kernel_combine_token`` ep_a2a.py:150-241 +
    post-process :251-270): send processed tokens back to their source ranks
    at the same slots, then weighted-sum each token's topk copies.
    ``processed`` is [n*n, capacity, H] sharded P(axis) — local [n, cap, H]
    where slot (src, c) is the processed token for rank src's slot c."""
    ctx, axis = a2a.ctx, a2a.axis
    n, cap, H, k = a2a.n_ranks, a2a.capacity, a2a.hidden, a2a.topk
    wire = a2a.wire_dtype
    push = _a2a_push_fn(a2a)
    if wire is not None:
        # quantize the return trip too (reference sends fp8 both ways) —
        # INSIDE the collective, per departure slot (all_to_all_push's
        # quant_from; sub-128 capacities fall back to one XLA pass there)
        if a2a.dequant_edge == "expert":
            # no full-buffer dequant: the scale is gathered with the token
            # in the combine epilogue and folded into the f32 weighted sum
            back, back_sc = push(ctx, processed, axis=axis,
                                 quant_from=wire)
        else:
            back, _ = push(ctx, processed, axis=axis,
                           quant_from=wire,
                           dequant_to=a2a.dtype,
                           fuse_dequant=a2a._dequant_in_kernel())
            back_sc = None
    else:
        (back,) = push(ctx, processed, axis=axis)
        back_sc = None

    def gather_back(back_shard, dest, slot, valid, w, *sc):
        # back_shard: [n, cap, H] — slot (d, c) = my token processed by rank d
        d_f = dest.reshape(-1)
        s_f = jnp.where(valid, slot, 0).reshape(-1)
        tok = back_shard[d_f, s_f]                                # [T*k, H]
        tok = jnp.where(valid.reshape(-1)[:, None], tok, 0).astype(
            jnp.float32)
        if sc:
            s2d = sc[0].reshape(n, -1)[:, :cap]                   # [n, cap]
            tok = tok * jnp.where(valid.reshape(-1), s2d[d_f, s_f],
                                  1.0)[:, None]
        T = dest.shape[0]
        tok = tok.reshape(T, k, H)
        return jnp.sum(tok * w[..., None].astype(jnp.float32),
                       axis=1).astype(a2a.dtype)

    dest, slot, valid = layout
    n_sc = 1 if back_sc is not None else 0
    sm = ctx.shard_map(gather_back,
                       in_specs=(P(axis),) * (5 + n_sc),
                       out_specs=P(axis))
    return sm(back, dest, slot, valid, topk_weights,
              *((back_sc,) if back_sc is not None else ()))


# ---------------------------------------------------------------------------
# 2-tier hierarchical EP dispatch / combine (multi-axis mesh: DCN x ICI)
# ---------------------------------------------------------------------------

def expected_capacity(n_ranks: int, max_tokens: int, topk: int,
                      headroom: float = 2.0, wire_dtype=None) -> int:
    """Per-(src, dst) slot budget sized to EXPECTED load instead of the
    worst case: balanced routing sends ``max_tokens·topk/n`` rows to each
    peer; ``headroom`` (default 2×) absorbs routing skew, and the result
    is rounded to the wire dtype's sublane tile. The default capacity
    (``max_tokens·topk`` per pair) is drop-proof but pads the wire n×
    beyond the actual bytes at scale — the per-link latency model
    (docs/benchmarks.md) assumes a tuned capacity like this one. Tokens
    routed beyond capacity are dropped (standard expert-capacity
    semantics), so pick ``headroom`` to taste for the workload's skew."""
    cap = max(1, int(max_tokens * topk * headroom / max(n_ranks, 1)))
    itemsize = jnp.dtype(wire_dtype).itemsize if wire_dtype is not None else 2
    # never exceed the drop-proof worst case (at n <= headroom the scaled
    # budget would otherwise pad BEYOND everything-to-one-peer)
    return min(_cap_round(cap, itemsize),
               _cap_round(max_tokens * topk, itemsize))


def _cap_round(cap: int, wire_itemsize: int = 2) -> int:
    """Round a slot capacity up to the wire dtype's sublane tile (8 rows ×
    4 bytes: 8 for f32, 16 for bf16, 32 for fp8/int8) so [capacity, hidden]
    DMA slices meet Mosaic's tiling alignment."""
    mult = 32 // wire_itemsize
    return (cap + mult - 1) // mult * mult


def _slot_src_map(dest_flat, slot_drop, src_rows, n_dst, cap, n_rows):
    """slot -> source-row map: a small int scatter ([n_dst, cap]); unfilled
    slots hold ``n_rows`` (out of range)."""
    return jnp.full((n_dst, cap), n_rows, jnp.int32).at[
        dest_flat, slot_drop].set(src_rows, mode="drop")


# Below this source-row count the slot gather runs as a one-hot matmul on
# the MXU instead of an HBM take-gather. The matmul is EXACT (each one-hot
# row has a single 1.0; 1.0·x in bf16 is x; the f32 accumulation sums one
# nonzero), reads the R source rows once (VMEM-resident) instead of
# streaming ~cap duplicated rows through the gather unit, and unfilled
# slots (src >= R) compare to nothing -> all-zero one-hot row -> zeros, the
# same zero-fill the take path wants. At the DeepSeek dispatch shape
# (R = 128 tokens/rank, cap·n = 1024 slots, H = 7168) the FLOP cost is
# ~1.9 GFLOP ≈ 10 µs on the MXU vs a ~30 µs bandwidth-bound gather — the
# dispatch edge the reference builds outside its timed region
# (test_all_to_all.py:313-329) but we count in ours. Past ~512 source rows
# the R-wide contraction stops paying for itself.
_MXU_GATHER_MAX_ROWS = 512


def _slot_onehot(src, R):
    """[*, R] one-hot of the slot->source-row map (unfilled rows all-zero)."""
    return (src.reshape(-1)[:, None]
            == jnp.arange(R, dtype=src.dtype)[None, :])


def _sanitize_rows(rows):
    """Non-finite containment for the slot gathers: a single Inf/NaN source
    row would poison EVERY slot on the MXU one-hot path (the 0.0·x terms of
    the contraction are NaN), so non-finite values are clamped to the
    dtype's finite range (``jnp.nan_to_num``: NaN→0, ±Inf→±max) BEFORE the
    gather — on both paths, so the MXU and take twins stay bit-comparable.
    Behavior change (documented): a token carrying non-finite activations
    now dispatches as its clamped-finite row instead of corrupting the
    whole dispatch; integer/wire-int rows pass through untouched."""
    if jnp.issubdtype(rows.dtype, jnp.floating):
        return jnp.nan_to_num(rows)
    return rows


def _slot_gather(rows, src, out_dtype):
    """Build a [n_dst, cap, H] send buffer by gathering ``rows`` [R, H]
    through the slot->source-row map ``src`` [n_dst, cap] (value R =
    unfilled -> zeros). Small-R path: gather-by-MXU (see
    ``_MXU_GATHER_MAX_ROWS``). Large-R path: one take-gather instead of
    zero-init + scattering pre-expanded rows — half the HBM traffic on the
    dispatch critical path. Non-finite source rows are clamped first
    (``_sanitize_rows``) so one bad row cannot poison every slot via the
    one-hot contraction."""
    rows = _sanitize_rows(rows)
    R = rows.shape[0]
    out_shape = src.shape + rows.shape[1:]
    if R <= _MXU_GATHER_MAX_ROWS and rows.ndim == 2:
        onehot = _slot_onehot(src, R).astype(rows.dtype)
        return jnp.dot(onehot, rows,
                       preferred_element_type=jnp.float32
                       ).astype(out_dtype).reshape(out_shape)
    filled = (src < R)[..., None]
    take = jnp.take(rows, jnp.minimum(src, R - 1).reshape(-1), axis=0)
    return jnp.where(filled, take.reshape(out_shape), 0).astype(out_dtype)


def _qmax(wire_dtype) -> float:
    if jnp.issubdtype(wire_dtype, jnp.floating):
        return float(jnp.finfo(wire_dtype).max)
    return float(jnp.iinfo(wire_dtype).max)


def _quant(x: jax.Array, wire_dtype) -> tuple[jax.Array, jax.Array]:
    """Per-row symmetric quantization: (q rows in ``wire_dtype``,
    f32 scale per row). Zero rows get scale 1 (quantize to zeros)."""
    qmax = _qmax(wire_dtype)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    q = xf / scale[..., None]
    if not jnp.issubdtype(wire_dtype, jnp.floating):
        q = jnp.round(q)
    return q.astype(wire_dtype), scale


def _slot_gather_quant(rows, src, wire_dtype):
    """Fused ``_slot_gather`` + ``_quant``: build the [n_dst, cap, H]
    quantized send buffer AND its per-slot f32 scales in ONE logical pass
    over the gathered rows. This is the measured-best send edge (round-4
    on-chip sweep, docs/benchmarks.md fp8-edge table: 93.5 µs dispatch vs
    131.9 µs for the quantize-then-gather "pre" wiring at the
    DeepSeek-infer shape — 1-byte row gathers vectorize worse than the
    fused f32 gather+quant chain despite moving fewer bytes).

    A token routed to k slots has its amax recomputed per slot — identical
    scale each time (bit-for-bit: same reduction over the same row).
    Unfilled slots quantize to zeros with scale 1 (``_quant``'s zero-row
    rule). Non-finite source rows are clamped first (``_sanitize_rows``)."""
    rows = _sanitize_rows(rows)
    R = rows.shape[0]
    H = rows.shape[-1]
    if R <= _MXU_GATHER_MAX_ROWS and rows.ndim == 2:
        # gather-by-MXU (see _MXU_GATHER_MAX_ROWS): the one-hot product IS
        # the gathered f32 rows, and the quant chain fuses onto it
        onehot = _slot_onehot(src, R).astype(rows.dtype)
        take = jnp.dot(onehot, rows, preferred_element_type=jnp.float32)
    else:
        filled = src < R
        take = jnp.take(rows, jnp.minimum(src, R - 1).reshape(-1), axis=0)
        take = take.reshape(src.shape + (H,)).astype(jnp.float32)
        take = jnp.where(filled[..., None], take, 0.0)
    q, scale = _quant(take.reshape(-1, H), wire_dtype)
    return (q.reshape(src.shape + (H,)).astype(wire_dtype),
            scale.reshape(src.shape))


def _slot_gather_prequant(rows, src, wire_dtype, n_dst, cols, cap):
    """``quant_edge="pre"`` send edge: quantize the source ``rows`` ONCE,
    then gather quantized rows + per-row scales through the slot map
    ``src`` [n_dst, cap] — all gathered HBM traffic stays in the wire
    dtype. Moves the fewest bytes but measured behind the fused edge on
    TPU (see ``_slot_gather_quant``); kept selectable as the bit-parity
    twin. Returns (send_buf [n_dst, cap, H] wire, scale wire
    [n_dst, cols//128, 128] f32 with 1.0 in unfilled/pad slots)."""
    rows = _sanitize_rows(rows)
    R = rows.shape[0]
    q, s = _quant(rows, wire_dtype)
    send = _slot_gather(q, src, wire_dtype)
    sc = _slot_gather(s[:, None], src, jnp.float32)[..., 0]
    send_sc = jnp.ones((n_dst, cols), jnp.float32).at[:, :cap].set(
        jnp.where(src < R, sc, 1.0))
    return send, send_sc.reshape(n_dst, -1, 128)


def _dequant(q: jax.Array, scale: jax.Array, out_dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
            ).astype(out_dtype)


def _id_cols(cap: int) -> int:
    """Lane-aligned (128) column count for an int32 id wire of ``cap``."""
    return max((cap + 127) // 128 * 128, 128)


def _slot_assign(dest_flat: jax.Array, n: int, cap: int, valid=None):
    """Exclusive-cumsum slot allocation per destination (the static-shape
    replacement for the reference's per-warp atomic slot counters,
    ep_a2a.py:64-147). Returns (slot, ok) — ``ok`` False for over-capacity
    or already-invalid rows."""
    one_hot = jax.nn.one_hot(jnp.clip(dest_flat, 0, n - 1), n,
                             dtype=jnp.int32)
    if valid is not None:
        one_hot = one_hot * valid[:, None].astype(jnp.int32)
    slots = jnp.cumsum(one_hot, axis=0) - one_hot
    slot = jnp.take_along_axis(
        slots, jnp.clip(dest_flat, 0, n - 1)[:, None], axis=1)[:, 0]
    ok = slot < cap
    if valid is not None:
        ok = ok & valid
    return slot, ok


@dataclasses.dataclass(frozen=True)
class Ep2dAllToAllContext:
    """2-tier EP A2A over a (major, minor) mesh — the TPU shape of the
    reference's hierarchical inter-node dispatch (ep_a2a.py:35-147:
    inter-node token forward, then local scatter by expert). Tier 1 crosses
    the major (slow/DCN) axis once to the target major-row; tier 2 scatters
    along the minor (fast/ICI) axis to the expert's rank. Experts are
    sharded over the flattened (major, minor) rank order."""
    ctx: ShmemContext
    axes: tuple[str, str]      # (major, minor)
    max_tokens: int
    hidden: int
    topk: int
    num_experts: int
    cap1: int                  # tier-1 slots per (src, dst-major-row)
    cap2: int                  # tier-2 slots per (src, dst-minor) pair
    dtype: jnp.dtype = jnp.bfloat16
    # quantized wire (fp8/int8 + f32 per-token scale side-channel): tokens
    # are quantized ONCE at the source and the scales ride both tiers with
    # the same slot maps; dequantization happens only at the edges (expert
    # input, combine output) — no requantization at the intermediate hop.
    # This is the reference's showcase configuration (inter-node fp8 A2A,
    # README.md:55) on the hierarchical path.
    wire_dtype: jnp.dtype | None = None
    quant_edge: str = "fused"     # see EpAllToAllContext
    dequant_edge: str = "post"

    def _dequant_in_kernel(self) -> bool:
        return self.dequant_edge == "kernel"

    @property
    def n_major(self) -> int:
        return self.ctx.axis_size(self.axes[0])

    @property
    def n_minor(self) -> int:
        return self.ctx.axis_size(self.axes[1])

    @property
    def n_ranks(self) -> int:
        return self.n_major * self.n_minor

    @property
    def experts_per_rank(self) -> int:
        return self.num_experts // self.n_ranks


def create_all_to_all_context_2d(ctx: ShmemContext, max_tokens: int,
                                 hidden: int, topk: int, num_experts: int,
                                 axes: tuple[str, str] | None = None,
                                 cap1: int | None = None,
                                 cap2: int | None = None,
                                 dtype=jnp.bfloat16,
                                 wire_dtype=None,
                                 quant_edge: str = "fused",
                                 dequant_edge: str = "post"
                                 ) -> Ep2dAllToAllContext:
    axes = axes or (ctx.axis_names[0], ctx.axis_names[1])
    n = ctx.axis_size(axes[0]) * ctx.axis_size(axes[1])
    assert num_experts % n == 0, (num_experts, n)
    assert quant_edge in ("pre", "fused"), quant_edge
    assert dequant_edge in ("kernel", "post", "expert"), dequant_edge
    assert hidden % 128 == 0, f"hidden={hidden} must be a lane multiple (128)"
    itemsize = jnp.dtype(wire_dtype or dtype).itemsize
    if cap1 is None:
        cap1 = max_tokens * topk
    cap1 = _cap_round(cap1, itemsize)
    if cap2 is None:
        cap2 = ctx.axis_size(axes[0]) * cap1
    cap2 = _cap_round(cap2, itemsize)
    return Ep2dAllToAllContext(ctx=ctx, axes=tuple(axes),
                               max_tokens=max_tokens, hidden=hidden,
                               topk=topk, num_experts=num_experts,
                               cap1=cap1, cap2=cap2, dtype=jnp.dtype(dtype),
                               wire_dtype=(jnp.dtype(wire_dtype)
                                           if wire_dtype is not None
                                           else None),
                               quant_edge=quant_edge,
                               dequant_edge=dequant_edge)


def route_tokens_2d(a2a: Ep2dAllToAllContext, topk_ids: jax.Array):
    """Tier-1 (major-hop) routing plan — the same ``a_dst``/``slot``/``ok``
    that ``dispatch_2d``'s first stage computes (build1), reshaped to the
    ``route_tokens`` [T, topk] convention. The tier-2 plan is
    arrival-dependent (it re-slots whatever tokens land on the intermediate
    device), so it can only be produced by ``dispatch_2d`` itself — it is
    returned there as ``layouts[1]``. Pure jnp; runs under jit/shard_map per
    source shard."""
    T, k = topk_ids.shape
    eid = topk_ids.reshape(-1)
    rank = eid // a2a.experts_per_rank
    a_dst = rank // a2a.n_minor
    slot, ok = _slot_assign(a_dst, a2a.n_major, a2a.cap1)
    return (a_dst.reshape(T, k), slot.reshape(T, k), ok.reshape(T, k))


def dispatch_2d(a2a: Ep2dAllToAllContext, tokens: jax.Array,
                topk_ids: jax.Array):
    """2-tier EP dispatch. Global inputs sharded P((major, minor)):
    ``tokens`` [n*T, H], ``topk_ids`` [n*T, topk] (global expert ids).
    Returns (recv_tokens [n, n_minor, cap2, H] P((major, minor)),
    recv_ids — local expert per slot (or -1), layouts for ``combine_2d``).

    Tier 1 (major/DCN): each token hops once to the device with its target
    major coordinate (same minor coordinate as the source). Tier 2
    (minor/ICI): the intermediate re-slots arrivals by target minor
    coordinate and scatters. The reference's two-kernel structure
    (inter-node putmem forward + local expert scatter, ep_a2a.py:35-147)
    maps to two ``all_to_all_push`` tiers with VPU slot allocation."""
    ctx = a2a.ctx
    major, minor = a2a.axes
    nM, nm = a2a.n_major, a2a.n_minor
    epr = a2a.experts_per_rank
    T, H, k = a2a.max_tokens, a2a.hidden, a2a.topk
    cap1, cap2 = a2a.cap1, a2a.cap2
    c1_cols, c2_cols = _id_cols(cap1), _id_cols(cap2)
    both = P((major, minor))

    wire = a2a.wire_dtype

    def build1(tok_shard, ids_shard):
        eid = ids_shard.reshape(-1)                          # [T*k] global
        rank = eid // epr
        a_dst = rank // nm
        slot, ok = _slot_assign(a_dst, nM, cap1)
        s_drop = jnp.where(ok, slot, cap1)
        src = _slot_src_map(a_dst, s_drop,
                            jnp.arange(T * k, dtype=jnp.int32) // k,
                            nM, cap1, T)
        meta = jnp.full((nM, c1_cols), -1, jnp.int32).at[a_dst, s_drop].set(
            eid, mode="drop")
        outs = ()
        if wire is not None and a2a.quant_edge == "pre":
            # quantize ONCE at the source; the f32 scale side-channel rides
            # the same slot maps through both tiers (no requantization)
            send, send_sc = _slot_gather_prequant(tok_shard, src, wire,
                                                  nM, c1_cols, cap1)
            outs = (send_sc,)
        elif wire is not None:
            send, sc = _slot_gather_quant(tok_shard, src, wire)
            outs = (jnp.ones((nM, c1_cols), jnp.float32).at[:, :cap1].set(
                sc).reshape(nM, -1, 128),)
        else:
            send = _slot_gather(tok_shard, src, a2a.dtype)
        return (send, meta.reshape(nM, c1_cols // 128, 128)) + outs + (
            a_dst, slot, ok)

    nw = 3 if wire is not None else 2
    sm1 = ctx.shard_map(build1, in_specs=(both, both),
                        out_specs=(both,) * (nw + 3))
    *wires1, a_dst, slot1, ok1 = sm1(tokens, topk_ids)
    recv1, meta1r, *sc1r = all_to_all_push(ctx, *wires1, axis=major,
                                           spec=both)

    def build2(r1_shard, m1_shard, *sc_shard):
        meta = m1_shard.reshape(nM, c1_cols)[:, :cap1].reshape(-1)
        valid = meta >= 0
        rank = jnp.where(valid, meta, 0) // epr
        b_dst = rank % nm
        slot, ok = _slot_assign(b_dst, nm, cap2, valid)
        toks = r1_shard.reshape(nM * cap1, H)
        s_drop = jnp.where(ok, slot, cap2)
        R = nM * cap1
        src = _slot_src_map(b_dst, s_drop,
                            jnp.arange(R, dtype=jnp.int32),
                            nm, cap2, R)
        # pass-through re-slot: the payload stays in the wire dtype
        send = _slot_gather(toks, src,
                            wire if wire is not None else a2a.dtype)
        meta2 = jnp.full((nm, c2_cols), -1, jnp.int32).at[b_dst, s_drop].set(
            meta, mode="drop")
        outs = ()
        if wire is not None:
            s1 = sc_shard[0].reshape(nM, c1_cols)[:, :cap1].reshape(-1)
            sc2 = _slot_gather(s1[:, None], src, jnp.float32)[..., 0]
            send_sc = jnp.ones((nm, c2_cols), jnp.float32).at[:, :cap2].set(
                jnp.where(src < R, sc2, 1.0))
            outs = (send_sc.reshape(nm, -1, 128),)
        return (send, meta2.reshape(nm, c2_cols // 128, 128)) + outs + (
            b_dst, slot, ok)

    sm2 = ctx.shard_map(build2, in_specs=(both,) * nw,
                        out_specs=(both,) * (nw + 3))
    *wires2, b_dst, slot2, ok2 = sm2(recv1, meta1r, *sc1r)
    if wire is not None and a2a.dequant_edge == "expert":
        # QuantTokens out: the scale side-channel that rode both tiers is
        # handed to the expert GEMM with the wire-dtype rows
        recv2, meta2r, sc2w = all_to_all_push(ctx, *wires2, axis=minor,
                                              spec=both)
        unpack_sc = ctx.shard_map(
            lambda w: w.reshape(nm, -1)[:, :cap2],
            in_specs=both, out_specs=both)
        recv2 = QuantTokens(q=recv2, scale=unpack_sc(sc2w))
    else:
        recv2, meta2r, *sc2r = all_to_all_push(
            ctx, *wires2, axis=minor, spec=both,
            dequant_to=a2a.dtype if wire is not None else None,
            fuse_dequant=a2a._dequant_in_kernel())

    unpack = ctx.shard_map(
        lambda w: jnp.where(
            w.reshape(nm, c2_cols)[:, :cap2] >= 0,
            w.reshape(nm, c2_cols)[:, :cap2] % epr, -1),
        in_specs=both, out_specs=both)
    recv_ids = unpack(meta2r)
    layouts = ((a_dst, slot1, ok1), (b_dst, slot2, ok2))
    return recv2, recv_ids, layouts


def combine_2d(a2a: Ep2dAllToAllContext, processed: jax.Array, layouts,
               topk_weights: jax.Array) -> jax.Array:
    """Reverse path of ``dispatch_2d``: minor-tier return, intermediate
    re-gather to tier-1 arrival order, major-tier return, topk-weighted sum
    at the source (analog of kernel_combine_token, ep_a2a.py:150-241)."""
    ctx = a2a.ctx
    major, minor = a2a.axes
    nM, nm = a2a.n_major, a2a.n_minor
    T, H, k = a2a.max_tokens, a2a.hidden, a2a.topk
    cap1, cap2 = a2a.cap1, a2a.cap2
    c1_cols, c2_cols = _id_cols(cap1), _id_cols(cap2)
    (a_dst, slot1, ok1), (b_dst, slot2, ok2) = layouts
    both = P((major, minor))
    wire = a2a.wire_dtype

    if wire is not None:
        # quantize the return trip once at the experts — inside the minor
        # collective, per departure slot (all_to_all_push's quant_from;
        # sub-128 capacities fall back to one XLA pass there); scales ride
        # both hops with the payload (reference sends fp8 both ways)
        back2, b2sc = all_to_all_push(ctx, processed, axis=minor, spec=both,
                                      quant_from=wire)
    else:
        (back2,) = all_to_all_push(ctx, processed, axis=minor, spec=both)

    def regroup(b2_shard, bd, s2, ok, *scs):
        idx = jnp.where(ok, s2, 0)
        tok = b2_shard[bd, idx]
        if wire is not None:
            tok = jnp.where(ok[:, None], tok, 0).astype(wire)
            # reshape(nm, -1): the fused-quant scale wire is
            # [nm, cap2//128, 128]; the XLA-fallback wire [nm, c2_cols//128,
            # 128] — both flatten to >= cap2 scale columns
            sv = scs[0].reshape(nm, -1)[:, :cap2][bd, idx]
            sc = jnp.ones((nM, c1_cols), jnp.float32).at[:, :cap1].set(
                jnp.where(ok, sv, 1.0).reshape(nM, cap1))
            return (tok.reshape(nM, cap1, H), sc.reshape(nM, -1, 128))
        tok = jnp.where(ok[:, None], tok, 0).astype(a2a.dtype)
        return (tok.reshape(nM, cap1, H),)

    nmid = 2 if wire is not None else 1
    mid = ctx.shard_map(
        regroup, in_specs=(both,) * (4 + (1 if wire is not None else 0)),
        out_specs=(both,) * nmid)(
        back2, b_dst, slot2, ok2, *((b2sc,) if wire is not None else ()))
    back1, *b1sc = all_to_all_push(ctx, *mid, axis=major, spec=both)

    def gather(b1_shard, ad, s1, ok, w, *scs):
        idx = jnp.where(ok, s1, 0)
        tok = b1_shard[ad, idx]
        tok = jnp.where(ok[:, None], tok, 0)
        if wire is not None:
            sv = scs[0].reshape(nM, c1_cols)[:, :cap1][ad, idx]
            tok = tok.astype(jnp.float32) * jnp.where(ok, sv, 1.0)[:, None]
        tok = tok.reshape(T, k, H)
        return jnp.sum(tok.astype(jnp.float32)
                       * w[..., None].astype(jnp.float32),
                       axis=1).astype(a2a.dtype)

    return ctx.shard_map(
        gather, in_specs=(both,) * (5 + (1 if wire is not None else 0)),
        out_specs=both)(
        back1, a_dst, slot1, ok1, topk_weights, *b1sc)


__all__ = ["all_to_all_push", "all_to_all_push_seg", "EpAllToAllContext",
           "create_all_to_all_context", "route_tokens", "dispatch", "combine",
           "Ep2dAllToAllContext", "create_all_to_all_context_2d",
           "route_tokens_2d", "dispatch_2d", "combine_2d", "a2a_wire_bytes",
           "pick_wire_dtype"]
