"""Raw device-API breadth sweep: PE addressing, sub-group barriers, and
remote signals at odd mesh shapes, independent of the ops that use them.

Parity target: the reference's standalone ``test_nvshmem_api`` (598 LoC —
teams, fcollect, signal ops, broadcast as an API surface, SURVEY §4). The
ops-level tests exercise these primitives *through* protocols; this module
pins the addressing math itself — ``pe_at_group`` over non-power-of-two and
3-axis meshes is exactly where a flat-id bug would alias two devices and
corrupt a hierarchical kernel silently.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from conftest import TEST_WORLD, xfail_on_cpu
from triton_dist_tpu.ops.common import collective_id_for
from triton_dist_tpu.shmem import device as shd
from triton_dist_tpu.shmem.context import initialize_distributed
from triton_dist_tpu.utils import default_interpret


@pytest.mark.parametrize("shape,axes,group", [
    ((2, 3), ("a", "b"), ("b",)),
    ((2, 3), ("a", "b"), ("a",)),
    ((2, 3), ("a", "b"), ("a", "b")),
    ((3, 2), ("a", "b"), ("b", "a")),       # group order != mesh order
    ((2, 2, 3), ("a", "b", "c"), ("c",)),
    ((2, 2, 3), ("a", "b", "c"), ("a", "c")),
    ((2, 2, 3), ("a", "b", "c"), ("b", "a")),
])
def test_pe_at_group_flat_ids(shape, axes, group):
    """pe_at_group(index) from every device, for every group coordinate,
    against a numpy golden computed from mesh coordinates."""
    if int(np.prod(shape)) > jax.device_count():
        pytest.skip(f"mesh {shape} needs more than {jax.device_count()} "
                    "devices (smaller TDT_TEST_DEVICES run)")
    ctx = initialize_distributed(axis_names=axes, mesh_shape=shape)
    gsize = int(np.prod([shape[axes.index(a)] for a in group]))

    def f():
        ids = [shd.pe_at_group(axes, group, jnp.int32(i))
               for i in range(gsize)]
        me = shd.my_pe(axes)
        return jnp.stack(ids + [me])[None]

    got = np.asarray(jax.jit(ctx.shard_map(
        f, in_specs=(), out_specs=P(axes)))())          # [n_dev, gsize+1]

    # golden: flat id over `axes` of the device whose `group` coords are the
    # row-major unflattening of i, other coords = the caller's
    n_dev = int(np.prod(shape))
    golden = np.zeros((n_dev, gsize + 1), np.int32)
    for flat in range(n_dev):
        coords = dict(zip(axes, np.unravel_index(flat, shape)))
        golden[flat, gsize] = flat
        for i in range(gsize):
            gcoords = dict(zip(group, np.unravel_index(
                i, tuple(shape[axes.index(a)] for a in group))))
            tgt = {**coords, **gcoords}
            golden[flat, i] = int(np.ravel_multi_index(
                tuple(tgt[a] for a in axes), shape))
    np.testing.assert_array_equal(got, golden)


def test_my_pe_flattened_multi_axis():
    """my_pe/n_pes over an axis tuple = row-major flattening (major first)."""
    ctx = initialize_distributed(axis_names=("a", "b"), mesh_shape=(2, 3))

    def f():
        return jnp.stack([shd.my_pe(("a", "b")), shd.n_pes(("a", "b")),
                          shd.my_pe("b"), shd.n_pes("b")])[None]

    got = np.asarray(jax.jit(ctx.shard_map(
        f, in_specs=(), out_specs=P(("a", "b"))))())
    for flat in range(6):
        a, b = divmod(flat, 3)
        np.testing.assert_array_equal(got[flat], [flat, 6, b, 3])


def test_group_ring_put_odd_mesh():
    """One-sided put around the ring of the FLATTENED (a, b) group on a
    (2, 3) mesh — a raw-primitive version of what the hierarchical relay
    kernels do, pinning pe_at_group inside an actual DMA."""
    axes = ("a", "b")
    ctx = initialize_distributed(axis_names=axes, mesh_shape=(2, 3))
    n = 6

    def kernel(in_ref, out_ref, send_sem, recv_sem):
        me = shd.my_pe(axes)
        dst = shd.pe_at_group(axes, axes, lax.rem(me + 1, n))
        rdma = shd.putmem_nbi(out_ref, in_ref, send_sem, recv_sem, dst)
        shd.quiet(rdma)
        shd.wait_recv(out_ref, recv_sem)

    def f(x):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(()),
                            pltpu.SemaphoreType.DMA(())],
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True,
                collective_id=collective_id_for("shmem_api_ring")),
            interpret=default_interpret(),
        )(x)

    x = jnp.arange(n * 8 * 128, dtype=jnp.float32).reshape(n * 8, 128)
    y = jax.jit(ctx.shard_map(f, in_specs=P(axes), out_specs=P(axes)))(x)
    want = np.roll(np.asarray(x), 8, axis=0)
    np.testing.assert_array_equal(np.asarray(y), want)


@pytest.mark.parametrize("barrier_axes", [("b",), ("a",), ("a", "b")])
def test_subaxis_barrier_then_signal(barrier_axes):
    """barrier_all over an axis SUBSET of a (2, 3) mesh, then a remote
    signal_op to the next neighbor within that group and a consuming wait —
    the teams-like surface (reference test_nvshmem_api's team barriers +
    signal ops)."""
    axes = ("a", "b")
    ctx = initialize_distributed(axis_names=axes, mesh_shape=(2, 3))

    def kernel(out_ref, sig):
        shd.barrier_all(barrier_axes, mesh_axes=axes)
        gsz = shd.n_pes(barrier_axes)
        me_g = shd.my_pe(barrier_axes)
        nxt = shd.pe_at_group(axes, barrier_axes, lax.rem(me_g + 1, gsz))
        shd.signal_op(sig, 7, pe=nxt)
        shd.signal_wait_until(sig, 7)   # consumes the neighbor's signal
        out_ref[0] = 1

    def f():
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
            scratch_shapes=[pltpu.SemaphoreType.REGULAR],
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True,
                collective_id=collective_id_for(
                    f"shmem_api_bar_{barrier_axes}")),
            interpret=default_interpret(),
        )()

    got = np.asarray(jax.jit(ctx.shard_map(
        f, in_specs=(), out_specs=P(axes)))())
    np.testing.assert_array_equal(got, np.ones(6, np.int32))


@xfail_on_cpu("pl.semaphore_read has no MLIR lowering for platform cpu "
              "(the interpreter implements the wait, not the read)")
def test_signal_read_after_partial_consume():
    """signal_read is NON-destructive and sees the residue of a partially
    consumed count: accumulate 3, wait 2 (TPU waits consume), read -> 1,
    read again -> still 1, then drain the last arrival so the physical
    register leaves the kernel clean."""
    ctx = initialize_distributed(axis_names=("x",), mesh_shape=(TEST_WORLD,))

    def kernel(out_ref, sig):
        shd.signal_op(sig, 3)           # self-signal: deterministic count
        shd.signal_wait_until(sig, 2)   # consumes 2 of the 3
        out_ref[0] = shd.signal_read(sig)
        out_ref[1] = shd.signal_read(sig)   # non-destructive: unchanged
        shd.signal_wait_until(sig, 1)   # drain the residue
        out_ref[2] = shd.signal_read(sig)

    def f():
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((3,), jnp.int32),
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
            scratch_shapes=[pltpu.SemaphoreType.REGULAR],
            compiler_params=pltpu.CompilerParams(
                has_side_effects=True,
                collective_id=collective_id_for("shmem_api_sigread")),
            interpret=default_interpret(),
        )()[None]

    got = np.asarray(jax.jit(ctx.shard_map(
        f, in_specs=(), out_specs=P("x")))())
    np.testing.assert_array_equal(
        got, np.tile(np.array([1, 1, 0], np.int32), (TEST_WORLD, 1)))


def test_quiet_with_zero_rdmas():
    """``quiet()`` with nothing outstanding is a legal no-op — protocols
    built over a dynamic rdma list hit the empty case whenever a rank has
    no remote peers (n=1 subgroup, self-only slice)."""
    ctx = initialize_distributed(axis_names=("x",), mesh_shape=(TEST_WORLD,))

    def kernel(out_ref):
        shd.quiet()                     # zero descriptors: must not block
        shd.fence()                     # ordering no-op rides along
        out_ref[0] = 1

    def f():
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
            out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
            compiler_params=pltpu.CompilerParams(has_side_effects=True),
            interpret=default_interpret(),
        )()

    got = np.asarray(jax.jit(ctx.shard_map(
        f, in_specs=(), out_specs=P("x")))())
    np.testing.assert_array_equal(got, np.ones(TEST_WORLD, np.int32))


def test_barrier_pair_reentry():
    """Back-to-back ``barrier_pair`` on the same physical barrier register:
    each crossing must consume exactly what it signalled (signal 1 / wait 1)
    so re-entry neither deadlocks nor inherits residue from the previous
    crossing. This jax's mosaic interpreter cannot execute remote REGULAR
    signals, so the protocol is proven through the sigcheck capture layer
    (no device): the cross-rank checker simulates all interleavings and
    flags any starvation, wait cycle, or leftover count."""
    from triton_dist_tpu.analysis import sigcheck

    def run(ctx):
        def kernel(out_ref, sig):
            me = shd.my_pe("x")
            peer = me ^ 1               # even<->odd partner pairs
            for _ in range(3):          # re-entry: three crossings in a row
                shd.barrier_pair(("x",), peer)
            out_ref[0] = 1

        def f():
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
                out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
                scratch_shapes=[pltpu.SemaphoreType.REGULAR],
                compiler_params=pltpu.CompilerParams(
                    has_side_effects=True,
                    collective_id=collective_id_for(
                        "shmem_api_pair_reentry")),
                interpret=default_interpret(),
            )()

        ctx.shard_map(f, in_specs=(), out_specs=P("x"))()

    rep = sigcheck(run, op="barrier_pair_reentry",
                   meshes=({"x": 2}, {"x": 4}))
    assert rep.ok, "\n".join(str(f) for f in rep.findings)
    assert all(c > 0 for c in rep.event_counts.values())
