"""Multi-process worker driven by tests/test_multiprocess.py (and runnable
by hand: see __main__). One python process per "host", CPU backend with 2
local virtual devices each — the single-controller-per-process model a real
TPU pod uses, minus the chips (reference analog: one torchrun rank per GPU,
launch.sh:33-44 + utils.py:91-111 bootstrap).

Covers the three multi-host paths nothing else tests with
``process_count() > 1``:
- ``initialize_distributed``'s env-gated ``jax.distributed.initialize``
  (shmem/context.py) incl. the JAX_NUM_PROCESSES/JAX_PROCESS_ID forwarding,
- a pure-XLA collective over a mesh spanning both processes,
- the autotuner's cross-process MAX consensus
  (``_consensus_times`` → ``multihost_utils.process_allgather``).
"""

import os
import sys


def main() -> None:
    # env must be pinned BEFORE jax import: 2 local CPU devices per process
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2")
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from triton_dist_tpu.shmem.context import initialize_distributed
    from triton_dist_tpu.tools import contextual_autotune

    ctx = initialize_distributed(axis_names=("x",), mesh_shape=(4,))
    assert jax.process_count() == 2, jax.process_count()
    me = jax.process_index()
    sharding = NamedSharding(ctx.mesh, P("x"))

    # pure-XLA collective across both processes' devices, traced into a
    # merged per-host-track profile when the harness asks for one
    from triton_dist_tpu.utils.perf import group_profile

    prof_dir = os.environ.get("TDT_PROF_DIR")
    with group_profile("mp", do_prof=prof_dir is not None,
                       out_dir=prof_dir or "prof"):
        ones = jax.jit(lambda: jnp.ones((8, 128), jnp.float32),
                       out_shardings=sharding)()
        total = jax.jit(
            ctx.shard_map(lambda s: jax.lax.psum(jnp.sum(s), "x"),
                          in_specs=P("x"), out_specs=P()))(ones)
        np.testing.assert_allclose(np.asarray(total), 8 * 128)
    if prof_dir and me == 0:
        merged = os.path.join(prof_dir, "mp", "merged.trace.json.gz")
        assert os.path.exists(merged), f"missing merged trace {merged}"
        print("MP_PROF_MERGED", flush=True)

    # autotuned op: both configs timed on every process, consensus = MAX
    calls = []

    @contextual_autotune(configs=[2, 3], iters=1, warmup=0)
    def op(x, cfg=None):
        calls.append(cfg)
        return x * cfg

    y = op(jnp.ones((4,), jnp.float32))
    assert sorted(set(calls)) == [2, 3], calls
    picked = float(np.asarray(y)[0])
    print(f"MP_OK process={me}/{jax.process_count()} picked={picked}",
          flush=True)

    # LAST: an OVERLAP KERNEL across the process boundary (VERDICT r4 #8
    # — no Pallas protocol crossed a process boundary before). The
    # interpret-mode runtime simulates DMA/semaphores with IN-PROCESS
    # state, so a kernel whose mesh spans two processes cannot see the
    # other process's signals: the attempt DEADLOCKS (measured round 5 —
    # not an error, a hang; each interpreter waits on semaphores only the
    # other process's interpreter would satisfy). A daemon watchdog pins
    # that outcome; if a future runtime routes the cross-process slices,
    # the same probe flips to MP_AG_OK and the golden is checked.
    # os._exit afterwards: a hung interpret thread would otherwise block
    # interpreter shutdown forever.
    import threading
    import time

    def attempt():
        try:
            from triton_dist_tpu.ops import all_gather
            x = jax.jit(lambda: jnp.arange(4 * 8 * 128, dtype=jnp.float32
                                           ).reshape(4 * 8, 128),
                        out_shardings=sharding)()
            y2 = jax.jit(lambda v: all_gather(ctx, v, axis="x",
                                              method="push"))(x)
            got = np.asarray(jax.device_get(y2))
        except Exception as e:
            print(f"MP_AG_UNSUPPORTED {type(e).__name__}: {str(e)[:160]}",
                  flush=True)
            return
        try:
            np.testing.assert_allclose(
                got, np.arange(4 * 8 * 128,
                               dtype=np.float32).reshape(4 * 8, 128))
        except AssertionError as e:
            # ran but produced WRONG data — a distinct (worst) outcome
            # that must fail the test, never read as "unsupported"
            print(f"MP_AG_WRONG_RESULT {str(e)[:160]}", flush=True)
            return
        print("MP_AG_OK", flush=True)

    t = threading.Thread(target=attempt, daemon=True)
    t.start()
    t.join(timeout=20)    # a 32x128 gather that works is done in seconds
    if t.is_alive():
        print("MP_AG_UNSUPPORTED Deadlock: interpret-mode kernel "
              "semaphores are in-process state; a 2-process mesh never "
              "sees the peer's signals", flush=True)
    # process 0 hosts the coordination service and leaves LAST: a peer whose
    # service goes away before its own exit is terminated by jax's client
    # ("Socket closed", exit 1: one tier-1 run in eight here, PR 42)
    try:
        from jax._src.distributed import global_state
        if me:
            global_state.client.key_value_set(f"mp_worker_bye/{me}", "1")
        else:
            for peer in range(1, jax.process_count()):
                global_state.client.blocking_key_value_get(
                    f"mp_worker_bye/{peer}", 60_000)
            time.sleep(0.5)     # the peer's os._exit follows its set
    except Exception as e:      # a dead peer fails the test by its own code
        print(f"MP_EXIT_HANDSHAKE {type(e).__name__}: {str(e)[:160]}",
              flush=True)
    os._exit(0)


if __name__ == "__main__":
    # standalone: python tests/mp_worker.py <process_id> <num_processes> <addr>
    if len(sys.argv) == 4:
        os.environ["JAX_PROCESS_ID"] = sys.argv[1]
        os.environ["JAX_NUM_PROCESSES"] = sys.argv[2]
        os.environ["JAX_COORDINATOR_ADDRESS"] = sys.argv[3]
    main()
