"""KV page migration: the disaggregated-serving producer/consumer kernel
(ISSUE 6 tentpole) — a prefill worker pushes one chunk's worth of finished
KV pages into a decode worker's page pool over the one-sided shmem layer.

This is the paper's core protocol applied at the serving tier (PAPER.md
§0; ROADMAP item 2): the producer moves data with one-sided puts and sets
a per-segment signal; the consumer waits on exactly the signals covering
what it will read — no barrier between chunks, no host round-trip in the
wait path. Per chunk:

- **producer** (prefill role): for each finalized page, one
  ``putmem_nbi`` per (layer, page) of k and of v into the consumer's
  symmetric pool at the RESERVED destination ids (the decode-side pages
  the host allocator handed out at admission — "remote reservation"),
  then ``signal_op(+n_pages)`` on the consumer's chunk semaphore: one
  counted arrival per page pushed.
- **consumer** (decode role): waits the chunk signal up to ``n_pages``,
  then waits each page's DMA delivery semaphore (``wait_recv`` — the
  TPU-native "putmem_signal" delivery guarantee, see shmem/device.py) —
  exactly the signals covering the pages this chunk delivers, nothing
  else. Only after those waits does it report the landed count, which is
  the HOST ledger's sole source of truth for signal-gated admission
  (serving/disagg.py): a page whose count never lands is never exposed
  through a block table.

The page ids ride in SMEM as runtime scalars, so ONE compiled program
serves every chunk of every request (the serving compile-guard relies on
this); the static shape is only (pages-per-chunk max, layers, page).

Entry barrier: like ``_ag_push_kernel``, the DMA and
chunk semaphores are physical registers reused across calls — the barrier
keeps a fast producer's call k+1 signals out of a consumer still draining
call k. Chunk-to-chunk overlap therefore happens at the SERVING level
(the next chunk's compute overlaps this chunk's migration only on real
async hardware); within a call, all (layer, page) puts are in flight at
once and are quieted in a second pass.

The CPU simulator (Pallas TPU interpret mode) runs this same protocol —
remote signals, the entry barrier and divergent role branches included.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.ops.common import collective_id_for
from triton_dist_tpu.shmem import device as shd
from triton_dist_tpu.shmem.context import ShmemContext
from triton_dist_tpu.utils import default_interpret


def _transport_kernel(axis, mesh_axes, producer, consumer, n_layers,
                      n_ref, src_ref, dst_ref, tag_ref, kpool, vpool,
                      kpool_out, vpool_out, landed_ref,
                      send_k, recv_k, send_v, recv_v, chunk_sem):
    """Both roles run this SPMD; ``producer``/``consumer`` are role indices
    along ``axis``. Pools are the [L*P, Hkv, ps, D] page-flattened local
    shards of the symmetric pool (aliased through as outputs).

    ``tag_ref`` is the send's attempt/generation tag (ISSUE 7): the
    landed report echoes it next to the count, so the host ledger can
    tell a report from THIS attempt apart from a delayed one belonging
    to an earlier attempt of the same chunk — retry re-sends bump the
    tag, and stale reports are discarded instead of double-counted. The
    echo is grounded here, in the same report that is ordered after the
    delivery waits, not in host bookkeeping.

    All pool traffic goes through the OUTPUT refs (the alias makes them
    the same buffer as the inputs)."""
    del kpool, vpool                  # aliased: use the output refs only
    kpool, vpool = kpool_out, vpool_out
    me = shd.my_pe(axis)
    pages = kpool.shape[0] // n_layers
    pmax = src_ref.shape[0]
    n = n_ref[0]
    landed_ref[0, 0] = 0
    landed_ref[0, 1] = tag_ref[0]

    # -- the full one-sided protocol --------------------------------------
    # entry barrier: the semaphores are physical registers reused across
    # calls (see module docstring / _ag_push_kernel)
    shd.barrier_all((axis,), mesh_axes=mesh_axes)

    @pl.when(me == producer)
    def _():
        peer = shd.pe_at(mesh_axes, axis, consumer)
        for i in range(pmax):
            @pl.when(i < n)
            def _(i=i):
                s, d = src_ref[i], dst_ref[i]
                for l in range(n_layers):
                    shd.putmem_nbi(kpool.at[l * pages + d],
                                   kpool.at[l * pages + s],
                                   send_k.at[l, i], recv_k.at[l, i], peer)
                    shd.putmem_nbi(vpool.at[l * pages + d],
                                   vpool.at[l * pages + s],
                                   send_v.at[l, i], recv_v.at[l, i], peer)
        # the per-chunk signal: one counted arrival per page pushed
        shd.signal_op(chunk_sem, n, pe=peer)
        if not shd._serial():
            # quiet (skip under TDT_SERIAL — sends already completed at
            # source there, a second wait would hang): the descriptors are
            # out of scope, so wait the send semaphores through the
            # standard same-ref-shape trick
            for i in range(pmax):
                @pl.when(i < n)
                def _(i=i):
                    s = src_ref[i]
                    for l in range(n_layers):
                        pltpu.make_async_copy(kpool.at[l * pages + s],
                                              kpool.at[l * pages + s],
                                              send_k.at[l, i]).wait()
                        pltpu.make_async_copy(vpool.at[l * pages + s],
                                              vpool.at[l * pages + s],
                                              send_v.at[l, i]).wait()
        landed_ref[0, 0] = n          # producer-side report: pages pushed

    @pl.when(me == consumer)
    def _():
        # signal-gated consumption: first the chunk announcement (counts
        # must cover every page of the chunk), then each page's delivery
        shd.signal_wait_until(chunk_sem, n)
        for i in range(pmax):
            @pl.when(i < n)
            def _(i=i):
                d = dst_ref[i]
                for l in range(n_layers):
                    shd.wait_recv(kpool.at[l * pages + d], recv_k.at[l, i])
                    shd.wait_recv(vpool.at[l * pages + d], recv_v.at[l, i])
        # ordered after the waits: this count is only ever observed when
        # every covered page has physically landed
        landed_ref[0, 0] = n


def paged_transport(ctx: ShmemContext, pool_k: jax.Array, pool_v: jax.Array,
                    src_ids: jax.Array, dst_ids: jax.Array,
                    n_pages: jax.Array, axis: str | None = None,
                    producer: int = 0, consumer: int = 1,
                    tag: jax.Array | int = 0, name: str = "page_migrate"):
    """The shared per-(layer, page) put + counted-signal transport core
    (ISSUE 17 refactor): ``migrate_pages`` (disagg prefill→decode handoff)
    and ``lend_pages`` (cluster prefix lending) are the SAME wire protocol
    with different role semantics, so both are thin fronts over this one
    host wrapper. ``name`` keys the collective id — distinct fronts get
    distinct collective channels even on the same axis.

    ``pool_k``/``pool_v``: symmetric pools from ``create_symm_tensor`` —
    global ``[n_roles, L, P, Hkv, page_size, D]`` sharded ``P(axis)``
    (each role owns an identically-shaped local pool; remote refs are
    (buffer, device) pairs, symmetric by construction). Page id 0 of each
    local pool must be a reserved scratch page (never a live sequence's).
    ``src_ids``/``dst_ids``: ``[pmax]`` int32, replicated — producer-local
    source page ids and consumer-side destination ids, valid up to
    ``n_pages`` (``[1]`` int32). Entries past ``n_pages`` are never
    dereferenced, so pad with anything in range. ``tag`` is the attempt/
    generation stamp echoed back in the landed report (see
    ``_transport_kernel``; 0 for first sends, bumped per retry).

    Returns ``(pool_k, pool_v, landed [n_roles, 2] int32)`` — pools
    aliased in place, ``landed[consumer] == (count, tag)``: the kernel-
    reported delivered-page count (the signal ledger's ground truth)
    plus the echoed attempt tag. ALL ranks on ``axis`` must enter this
    call (it is one SPMD program, like every collective in ops/); ranks
    outside the ``{producer, consumer}`` pair participate only in the
    entry barrier."""
    axis = axis or ctx.axis_names[0]
    mesh_axes = ctx.axis_names

    def f(n, src, dst, tg, kp, vp):
        L = kp.shape[1]
        flat = lambda a: a.reshape((a.shape[1] * a.shape[2],) + a.shape[3:])
        kpl, vpl = flat(kp), flat(vp)
        pmax = src.shape[0]
        kernel = lambda *refs: _transport_kernel(
            axis, mesh_axes, producer, consumer, L, *refs)
        ko, vo, landed = pl.pallas_call(
            kernel,
            out_shape=(jax.ShapeDtypeStruct(kpl.shape, kpl.dtype),
                       jax.ShapeDtypeStruct(vpl.shape, vpl.dtype),
                       jax.ShapeDtypeStruct((1, 2), jnp.int32)),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] * 4
            + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pltpu.SMEM)),
            input_output_aliases={4: 0, 5: 1},
            scratch_shapes=[pltpu.SemaphoreType.DMA((L, pmax)),
                            pltpu.SemaphoreType.DMA((L, pmax)),
                            pltpu.SemaphoreType.DMA((L, pmax)),
                            pltpu.SemaphoreType.DMA((L, pmax)),
                            pltpu.SemaphoreType.REGULAR],
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True,
                collective_id=collective_id_for(f"{name}_{axis}")),
            interpret=default_interpret(),
        )(n, src, dst, tg, kpl, vpl)
        return ko.reshape(kp.shape), vo.reshape(vp.shape), landed

    sm = ctx.shard_map(f, in_specs=(P(), P(), P(), P(), P(axis), P(axis)),
                       out_specs=(P(axis), P(axis), P(axis, None)))
    return sm(jnp.asarray(n_pages, jnp.int32).reshape(1),
              jnp.asarray(src_ids, jnp.int32),
              jnp.asarray(dst_ids, jnp.int32),
              jnp.asarray(tag, jnp.int32).reshape(1), pool_k, pool_v)


def migrate_pages(ctx: ShmemContext, pool_k: jax.Array, pool_v: jax.Array,
                  src_ids: jax.Array, dst_ids: jax.Array, n_pages: jax.Array,
                  axis: str | None = None, producer: int = 0,
                  consumer: int = 1, tag: jax.Array | int = 0):
    """Collective chunk migration over the role axis — the disaggregated
    prefill→decode handoff front over :func:`paged_transport` (argument
    and return contracts documented there)."""
    return paged_transport(ctx, pool_k, pool_v, src_ids, dst_ids, n_pages,
                           axis=axis, producer=producer, consumer=consumer,
                           tag=tag, name="page_migrate")


__all__ = ["migrate_pages", "paged_transport"]
