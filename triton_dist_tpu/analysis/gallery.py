"""Broken-kernel gallery: known-bad signal protocols sigcheck must flag.

Each kernel is a deliberately-miswired variant of the repo's push AG
pattern (allgather.py ``_ag_push_kernel``), one per finding class. The
quick tier asserts every gallery entry is flagged WITH ITS EXPECTED KIND —
if a checker change stops catching one of these, that is a checker
regression, not a cleaner gallery.

The bugs are rank-count independent (they reproduce at n=2) so the
gallery stays cheap enough for the dryrun gate.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from .capture import FakeContext
from .checker import (DEADLOCK, Finding, NONDETERMINISM, OVER_SIGNAL,
                      UNDER_SIGNAL, UNORDERED_READ)

f32 = jnp.float32
_M = 8  # rows per rank in every gallery kernel


# -- kernels -----------------------------------------------------------------

def _missing_wait_kernel(axis, mesh_axes, in_ref, out_ref, send_sems,
                         recv_sems):
    """Push AG that reads the gathered buffer WITHOUT waiting for the
    arrivals — the classic torn-read: remote puts are in flight while the
    consumer computes over their destination slots."""
    from ..shmem import device as shd
    me = shd.my_pe(axis)
    n = shd.n_pes(axis)
    m = in_ref.shape[0]
    shd.barrier_all((axis,), mesh_axes=mesh_axes)
    local = pltpu.make_async_copy(in_ref, out_ref.at[pl.ds(me * m, m)],
                                  recv_sems.at[me])
    local.start()
    rdmas = []
    for p in range(1, n):
        dst = lax.rem(me + p, n)
        pid = shd.pe_at(mesh_axes, axis, dst)
        rdmas.append(shd.putmem_nbi(out_ref.at[pl.ds(me * m, m)], in_ref,
                                    send_sems.at[dst], recv_sems.at[me],
                                    pid))
    local.wait()
    # BUG: no wait_recv on any peer slot before consuming the buffer
    out_ref[pl.ds(me * m, m)] = out_ref[pl.ds(0, m)] + 1.0
    shd.quiet(*rdmas)


def _dropped_signal_kernel(axis, mesh_axes, in_ref, out_ref, flag):
    """Arrival-counting barrier that forgets the self-arrival: every rank
    contributes n-1 signals but each waits for n — the count can never be
    reached (static starvation)."""
    from ..shmem import device as shd
    me = shd.my_pe(axis)
    n = shd.n_pes(axis)
    for p in range(1, n):
        pid = shd.pe_at(mesh_axes, axis, lax.rem(me + p, n))
        shd.signal_op(flag, 1, pid)
    # BUG: waits for n arrivals, only n-1 are ever sent
    shd.signal_wait_until(flag, n)
    out_ref[...] = in_ref[...]


def _seg_dropped_signal_kernel(axis, mesh_axes, in_ref, out_ref, flag):
    """Microbatch-segmented announcement protocol (the ISSUE 16 overlap
    wire: one counted signal per (peer, segment), consumer gates on the
    aggregate per-segment count) whose producer FORGETS the last
    microbatch's segment signal — the waits budget 2 segments per peer but
    only segment 0 is ever announced, so the per-segment gate starves
    (static under-signal)."""
    from ..shmem import device as shd
    me = shd.my_pe(axis)
    n = shd.n_pes(axis)
    segments = 2
    for p in range(1, n):
        pid = shd.pe_at(mesh_axes, axis, lax.rem(me + p, n))
        # BUG: announces segment 0 only — segment 1 (the second
        # microbatch) is never signalled to any peer
        for s in range(segments - 1):
            shd.signal_op(flag, 1, pid)
    shd.signal_wait_until(flag, segments * (n - 1))
    out_ref[...] = in_ref[...]


def _lend_dropped_last_signal_kernel(axis, mesh_axes, in_ref, out_ref,
                                     flag):
    """The lend_pages wire (ISSUE 17: lender announces one counted signal
    per page, borrower gates on the total page count) whose lender
    FORGETS the LAST page's announcement — the classic off-by-one on the
    counted protocol: pages-1 signals arrive against a wait budget of
    pages, so the borrower's delivery gate starves (static
    under-signal). The pages themselves may well have landed; the
    ANNOUNCEMENT protocol is what the checker accounts."""
    from ..shmem import device as shd
    me = shd.my_pe(axis)
    pages = 3
    lender, borrower = 0, 1
    bpid = shd.pe_at(mesh_axes, axis, borrower)

    @pl.when(me == lender)
    def _send():
        # BUG: announces pages-1 of the `pages` puts — the final page's
        # counted signal is dropped on the floor
        for _ in range(pages - 1):
            shd.signal_op(flag, 1, bpid)

    @pl.when(me == borrower)
    def _recv():
        shd.signal_wait_until(flag, pages)

    out_ref[...] = in_ref[...]


def _fold_dropped_slice_signal_kernel(axis, mesh_axes, in_ref, out_ref,
                                      flag):
    """The flash_decode_dist fold wire (ISSUE 19: every rank announces its
    page-partial slab to each peer with one counted ``signal_op``; each
    consumer's fold gates on ONE count per remote slab it folds, in
    canonical rank order) where RANK 0 forgets to announce its slab:
    every peer budgets n-1 announcement counts but only n-2 ever arrive,
    so the fold's slice gate starves waiting on rank 0's partial (static
    under-signal). The slab bytes may well have landed — the announcement
    protocol is what the checker accounts."""
    from ..shmem import device as shd
    me = shd.my_pe(axis)
    n = shd.n_pes(axis)

    @pl.when(me != 0)
    def _():
        # BUG: rank 0 skips this announce loop entirely — its partial
        # slab is never signalled to any consumer
        for p in range(1, n):
            pid = shd.pe_at(mesh_axes, axis, lax.rem(me + p, n))
            shd.signal_op(flag, 1, pid)

    # one count consumed per remote slab, in canonical fold order
    for _ in range(n - 1):
        shd.signal_wait_until(flag, 1)
    out_ref[...] = in_ref[...]


def _over_signal_kernel(axis, mesh_axes, in_ref, out_ref, flag):
    """Arrival counter whose producers double-signal: the wait consumes n-1
    but 2(n-1) arrive — the residue poisons the next call on this scratch
    (the PR-6 ledger bug class)."""
    from ..shmem import device as shd
    me = shd.my_pe(axis)
    n = shd.n_pes(axis)
    for p in range(1, n):
        pid = shd.pe_at(mesh_axes, axis, lax.rem(me + p, n))
        # BUG: inc=2 against a wait budget of 1 per peer
        shd.signal_op(flag, 2, pid)
    shd.signal_wait_until(flag, n - 1)
    out_ref[...] = in_ref[...]


def _swapped_sem_kernel(axis, mesh_axes, in_ref, out_ref, send_sems,
                        recv_sems):
    """Two puts to the right neighbor tracked by two DMA semaphores — but
    the consumer waits them in swapped order, so the first read is covered
    by the WRONG semaphore (the byte counts balance; only delivery
    attribution exposes it)."""
    from ..shmem import device as shd
    me = shd.my_pe(axis)
    n = shd.n_pes(axis)
    m = in_ref.shape[0]
    half = m // 2
    right = shd.pe_at(mesh_axes, axis, lax.rem(me + 1, n))
    shd.barrier_all((axis,), mesh_axes=mesh_axes)
    lo, hi = pl.ds(0, half), pl.ds(half, half)
    r0 = shd.putmem_nbi(out_ref.at[lo], in_ref.at[lo],
                        send_sems.at[0], recv_sems.at[0], right)
    r1 = shd.putmem_nbi(out_ref.at[hi], in_ref.at[hi],
                        send_sems.at[1], recv_sems.at[1], right)
    # BUG: sem 1 covers the HIGH half, yet it gates the low-half read
    shd.wait_recv(out_ref.at[lo], recv_sems.at[1])
    out_ref[lo] = out_ref[lo] + 1.0
    shd.wait_recv(out_ref.at[hi], recv_sems.at[0])
    out_ref[hi] = out_ref[hi] + 1.0
    shd.quiet(r0, r1)


def _wait_cycle_kernel(axis, mesh_axes, in_ref, out_ref, flag):
    """Signal-after-wait with no rank ever signalling first: every rank
    waits for its left neighbor's token before sending its own — a
    wait-before-signal cycle with sufficient total supply (each sem IS
    eventually signalled once... behind the wait)."""
    from ..shmem import device as shd
    me = shd.my_pe(axis)
    n = shd.n_pes(axis)
    right = shd.pe_at(mesh_axes, axis, lax.rem(me + 1, n))
    # BUG: everyone waits before signalling — nobody moves
    shd.signal_wait_until(flag, 1)
    shd.signal_op(flag, 1, right)
    out_ref[...] = in_ref[...]


# -- host plumbing -----------------------------------------------------------

def _dma_call(ctx: FakeContext, kernel, name: str):
    from ..ops.common import collective_id_for
    from ..utils import default_interpret
    axis = ctx.axis_names[0]
    mesh_axes = ctx.axis_names
    n = ctx.axis_size(axis)
    x = jnp.zeros((n * _M, 128), f32)

    def f(shard):
        return pl.pallas_call(
            functools.partial(kernel, axis, mesh_axes),
            out_shape=jax.ShapeDtypeStruct((n * _M, 128), f32),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA((n,)),
                            pltpu.SemaphoreType.DMA((n,))],
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True,
                collective_id=collective_id_for(f"gallery_{name}")),
            interpret=default_interpret(),
            name=name,
        )(shard)

    ctx.shard_map(f, in_specs=P(axis), out_specs=None)(x)


def _flag_call(ctx: FakeContext, kernel, name: str):
    from ..ops.common import collective_id_for
    from ..utils import default_interpret
    axis = ctx.axis_names[0]
    mesh_axes = ctx.axis_names
    n = ctx.axis_size(axis)
    x = jnp.zeros((n * _M, 128), f32)

    def f(shard):
        return pl.pallas_call(
            functools.partial(kernel, axis, mesh_axes),
            out_shape=jax.ShapeDtypeStruct((_M, 128), f32),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.REGULAR],
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True,
                collective_id=collective_id_for(f"gallery_{name}")),
            interpret=default_interpret(),
            name=name,
        )(shard)

    ctx.shard_map(f, in_specs=P(axis), out_specs=P(axis))(x)


def _lint_psum_hot_loop() -> List[Finding]:
    """Decode-style hot loop with a ``psum`` inside the scan body — the
    rank-count-dependent reduction the serving trace contract bans. Traced
    under a 2-rank axis env (a size-1 mesh would constant-fold the psum away
    before the lint could see it)."""
    from .lint import lint_determinism

    def step(x):
        def body(carry, _):
            return lax.psum(carry, "tp"), ()
        out, _ = lax.scan(body, x, None, length=4)
        return out

    return lint_determinism(step, jax.ShapeDtypeStruct((8, 128), f32),
                            op="gallery.psum_hot_loop",
                            axis_env=(("tp", 2),))


# -- the gallery -------------------------------------------------------------

@dataclasses.dataclass
class GalleryEntry:
    name: str
    expected: str                      # finding kind that MUST be reported
    run: Optional[Callable[[FakeContext], None]] = None
    lint: Optional[Callable[[], List[Finding]]] = None
    meshes: Sequence[Dict[str, int]] = ({"x": 2},)


_ENTRIES = [
    GalleryEntry("missing_wait", UNORDERED_READ,
                 run=lambda ctx: _dma_call(ctx, _missing_wait_kernel,
                                           "missing_wait")),
    GalleryEntry("dropped_signal", UNDER_SIGNAL,
                 run=lambda ctx: _flag_call(ctx, _dropped_signal_kernel,
                                            "dropped_signal")),
    GalleryEntry("seg_dropped_signal", UNDER_SIGNAL,
                 run=lambda ctx: _flag_call(ctx, _seg_dropped_signal_kernel,
                                            "seg_dropped_signal")),
    GalleryEntry("lend_dropped_last_signal", UNDER_SIGNAL,
                 run=lambda ctx: _flag_call(
                     ctx, _lend_dropped_last_signal_kernel,
                     "lend_dropped_last_signal")),
    GalleryEntry("fold_dropped_slice_signal", UNDER_SIGNAL,
                 run=lambda ctx: _flag_call(
                     ctx, _fold_dropped_slice_signal_kernel,
                     "fold_dropped_slice_signal")),
    GalleryEntry("over_signal", OVER_SIGNAL,
                 run=lambda ctx: _flag_call(ctx, _over_signal_kernel,
                                            "over_signal")),
    GalleryEntry("swapped_sem", UNORDERED_READ,
                 run=lambda ctx: _dma_call(ctx, _swapped_sem_kernel,
                                           "swapped_sem")),
    GalleryEntry("wait_cycle", DEADLOCK,
                 run=lambda ctx: _flag_call(ctx, _wait_cycle_kernel,
                                            "wait_cycle"),
                 meshes=({"x": 2}, {"x": 3})),
    GalleryEntry("psum_hot_loop", NONDETERMINISM, lint=_lint_psum_hot_loop),
]

GALLERY: Dict[str, GalleryEntry] = {e.name: e for e in _ENTRIES}
