"""Test bootstrap: a 12-device virtual CPU mesh, and the suite's one watchdog.

The distributed kernels run in Pallas TPU interpret mode on CPU devices —
this is the single-process cluster simulator the reference lacks (its tests
need real GPUs + torchrun; see SURVEY.md §4). The suite always runs on the
CPU simulator: jax is re-pointed at the virtual CPU platform (dropping any
cached backend) before any test imports run, whatever the environment's
default platform is.

How to run (the one place that says it; README.md points here):

- tier 1, the gate:  ``pytest tests/ -q -m 'not slow' -n 6 --dist load``
  (the driver allows 1470 s; the budget is three quarters of that: since
  PR 42 this sandbox's 8 cores take 1,080-1,220 s and 6,290-6,760
  worker-seconds where PR 41's tree took 1,326-1,558 and 7,561-8,902 in the
  same hours; the driver's machine took 1,374 s on PR 41's. ``--dist
  loadfile``, which the driver ran before, does as well: no test leans on
  another of its file having run in the same process.
  ``FIXTURE_HEAVY_FIRST`` below says which files go first, and why)
- the slow tier:     ``pytest tests/ -q -m slow`` (the full bit-identity
  matrices and dense crash sweeps; hours on the interpreter)
- one file:          ``pytest tests/test_chaos.py -q``

Every test, and every module- or session-scoped fixture's set-up, runs
under ONE wall-clock watchdog: ``WATCHDOG_S`` = 120 s (``_wall_limit``
below). There is no switch to lift or change it; only a test marked
``slow`` runs without it. A tier-1 test or fixture needs less than half of
it alone on the machine and up to 1.7 x that beside five busy workers (a
fixture that is a minute of interpreter is split: its programs' compile, its
golden run and its disturbed run a fixture each), so only a real hang
reaches the limit; one that Python
cannot get out of (every thread in a futex) ends its worker ``HARD_S`` = 30 s
later: that test is lost, the run goes on.
"""

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from triton_dist_tpu.utils.env import force_virtual_cpu_devices  # noqa: E402

_N_DEVICES = int(os.environ.get("TDT_TEST_DEVICES", "12"))
force_virtual_cpu_devices(_N_DEVICES)

# Per-run XLA compile cache: many tests build fresh kernels and programs
# that lower to byte-identical HLO under jax.jit objects of their own (the
# ENGINES of one configuration and shape share theirs since PR 42:
# serving/programs.py). A content-keyed
# persistent cache dedupes those XLA compiles within one suite run — it
# does NOT affect the compile-count guards, which count trace-cache
# entries, not XLA compiles. Fresh temp dir per run: nothing persists
# across runs, so the first run's numbers are every run's numbers. The
# 0.3 s threshold keeps the flood of tiny eager-op compiles out of the
# cache (caching those costs more in serialization than it saves).
import tempfile  # noqa: E402

_cache_dir = tempfile.mkdtemp(prefix="tdt_xla_cache_")
jax.config.update("jax_compilation_cache_dir", _cache_dir)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)

assert jax.device_count() == _N_DEVICES, (
    f"expected {_N_DEVICES} virtual CPU devices, got {jax.devices()}"
)

# Most tests use a 4-way mesh for speed; TEST_WORLD_WIDE exercises the
# driver's exact 8-way configuration (tests/test_eight_way.py, and the
# full-participation 8-of-8 sweep in test_full_participation.py via
# TDT_TEST_DEVICES=8). The default keeps 12 devices so the wide tests also
# cover the participants-<-devices subset shape users hit on real pods.
TEST_WORLD = 4
TEST_WORLD_WIDE = 8


# The tests that need what the CPU interpreter cannot do. The mark reads the
# platform, so on a backend that can, they run and must pass (strict). This
# suite never sees one: the lines above pin the CPU platform, so here the
# condition always holds and the mark only keeps the ten out of the failures.
def xfail_on_cpu(reason):
    return pytest.mark.xfail(
        jax.default_backend() == "cpu", strict=True,
        raises=NotImplementedError,
        reason=reason + " (conftest pins the CPU platform: always xfail here)")


# ------------------------------------------------- shared replay ingredients
# The serving-tier files replay ONE seeded trace through different engines
# and hold every run to the fault-free golden's tokens. The interpreter costs
# 0.5-2 s an engine step, so the trace is as short as the mechanism under
# test allows (with an assertion that the mechanism fired);
# `seeded_trace(n)` is a prefix of `seeded_trace(m)` for n < m.
N_REQUESTS = 8    # the trace: fewest that still force a preemption on the
#                   9-page pool (asserted wherever a golden is made)
N4_REQUESTS = 4   # its first four, for every further replay across chips (2 s
#                   an interpreter step and more): a request's tokens are a
#                   function of the request alone -- THE contract -- so such a
#                   run is held to the golden's rids 0-3
N4_PAGES = 6      # the pool on which those four STILL preempt (12 steps, not
#                   the whole trace's 18): what a sharded replay of the four is
#                   given, with the preemption asserted


def assert_replay_identical(tokens, gold, n):
    """EVERY one of the trace's first ``n`` requests finished, each with the
    golden's tokens: a run that finished only some of them fails."""
    assert set(tokens) == set(range(n)), \
        f"finished rids {sorted(tokens)}, expected 0..{n - 1}"
    bad = [r for r in range(n) if tokens[r] != gold[r]]
    assert not bad, f"token streams diverged from the golden: rids {bad}"


def serve_noting_victims(eng, reqs, fence=False):
    """Submit ``reqs`` [(prompt, max_new_tokens)] and step ``eng`` until idle,
    every chunk fenced on the host or (as the engine does) only a prompt's
    last. Returns (tokens a request, the control plane's digest after every
    step, whether ``_grow`` preempted a slot mid-prefill in the very step
    that committed a chunk nobody waited for, the counters, whether one such
    victim's chunk had been launched ahead, in the step before)."""
    import jax
    if fence:
        chunk_step = eng._chunk_step
        eng._chunk_step = lambda *a: jax.block_until_ready(chunk_step(*a))
    preempt, victims = eng._preempt, []
    counters = eng.metrics.counters
    counts = lambda: (counters["chunks_not_awaited"],       # noqa: E731
                      counters["chunks_prelaunched"])

    def spy(slot):
        req = eng.sched.slots[slot]
        victims.append((req.state.value, req.prefill_cursor, *counts()))
        preempt(slot)

    eng._preempt = spy
    rids = [eng.submit(prompt, n) for prompt, n in reqs]
    digests, hit, hit_ahead = [], False, False
    while True:
        (before, ahead), seen = counts(), len(victims)
        if not eng.step():
            break
        digests.append(eng.control_digest())
        mid = [v for v in victims[seen:] if v[0] == "prefilling" and v[1] > 0
               and v[2] == before + 1]
        hit |= bool(mid)
        hit_ahead |= any(v[3] == ahead + 1 for v in mid)
    done = {r.rid: list(r.generated) for r in eng._finished}
    return [done[r] for r in rids], digests, hit, counters, hit_ahead


def seeded_trace(n, staggered=False):
    """[(arrival step, prompt, max_new_tokens)]: prompts of 3..16 tokens (one
    to two pages of 8), 2..5 new tokens. Bursty (two arrivals a step, so a
    9-page pool must preempt) or ``staggered`` (one every other step)."""
    rng = np.random.RandomState(77)
    out = []
    for i in range(n):
        plen = int(rng.randint(3, 17))
        mnt = int(rng.randint(2, 6))
        prompt = rng.randint(1, 128, size=plen).tolist()
        out.append((2 * i if staggered else i // 2, prompt, mnt))
    return out


@pytest.fixture(scope="session")
def moe_model():
    """Micro MoE: smallest shape that exercises every sharded path
    (d_model=128 is the A2A wire-lane floor; 2 KV heads so GQA grouping
    is real; 4 experts / topk 2 so EP dispatch actually routes)."""
    from triton_dist_tpu.models.llama import LlamaConfig
    from triton_dist_tpu.models.moe import MoEConfig, init_moe_params
    cfg = MoEConfig(base=LlamaConfig(vocab_size=128, d_model=128,
                                     n_layers=1, n_heads=4, n_kv_heads=2,
                                     d_ff=128, max_seq_len=128,
                                     dtype=jnp.float32),
                    num_experts=4, topk=2, moe_d_ff=64)
    return cfg, init_moe_params(jax.random.PRNGKey(0), cfg)


# the sharded replays' one shape: 4 slots over a 9-page pool of 8-token pages
# (tight: growth-driven preemption is forced, not incidental), chunk 8, and
# the wire pinned to fp8, never "auto" (auto resolves per rank count; a pinned
# wire makes every mesh size quantize identically)
SHARDED_KW = dict(num_slots=4, page_size=8, num_pages=9, pages_per_seq=4,
                  prefill_chunk=8, wire_dtype=jnp.float8_e4m3fn)


def sharded_engine(moe_model, tp, sp, ep, **kw):
    from triton_dist_tpu.serving import ShardedServingEngine, serving_mesh
    cfg, params = moe_model
    return ShardedServingEngine(params, cfg, serving_mesh(tp, sp, ep),
                                **{**SHARDED_KW, **kw})


@pytest.fixture(scope="session")
def micro_model():
    """One-layer d_model=32 float32 Llama for the colocated and disagg
    replays: the sweeps rerun the trace many times, so per-step cost
    dominates the budget."""
    from triton_dist_tpu.models.llama import LlamaConfig, init_params
    cfg = LlamaConfig(vocab_size=128, d_model=32, n_layers=1, n_heads=2,
                      n_kv_heads=1, d_ff=64, max_seq_len=64,
                      dtype=jnp.float32)
    return cfg, init_params(jax.random.key(1), cfg)


@pytest.fixture
def own_programs(monkeypatch):
    """``own_programs()``: engines built from here on trace their own
    programs, whatever the process has built (``serving.programs``' memo is
    replaced by an empty one until the test ends)."""
    from triton_dist_tpu.serving import programs
    return lambda: monkeypatch.setattr(programs, "_MEMO", {})


# ----------------------------------------------------- crash/recover harness
RECOVERY_MAX_STEPS = 600  # far above any legitimate run length


def crash_then_recover(mk_engine, arrivals, crash_step, checkpoint_every=8):
    """The whole crash-consistency cycle at one crash point: journaled run
    crashes at ``crash_step`` (returns None if the trace finished first —
    nothing to recover), then a FRESH engine of the same configuration
    restores from the journal and serves the not-yet-journaled remainder.
    Returns the recovered {rid: tokens} union."""
    from triton_dist_tpu.serving import ControlJournal
    from triton_dist_tpu.shmem import FaultPlan
    from triton_dist_tpu.shmem.faults import InjectedCrash
    journal = ControlJournal()
    eng = mk_engine(journal=journal, checkpoint_every=checkpoint_every,
                    fault_plan=FaultPlan(seed=3, crash_at=(crash_step,)))
    try:
        eng.run(max_steps=RECOVERY_MAX_STEPS, arrivals=arrivals)
        return None                      # ran to completion — no crash
    except InjectedCrash:
        pass
    # the journal is the durable artifact; everything else is rebuilt
    done = sum(1 for e in journal.entries if e["kind"] == "submit")
    eng2 = mk_engine(journal=journal, checkpoint_every=checkpoint_every)
    res = eng2.run(max_steps=RECOVERY_MAX_STEPS, arrivals=arrivals[done:],
                   recover=True)
    assert eng2.metrics.counters["restores"] == 1
    return res


def journaled_steps(mk_engine, arrivals):
    """Total step count of the fault-free journaled run (the sweep's
    crash-point domain), its result (the golden) and its journal."""
    from triton_dist_tpu.serving import ControlJournal
    journal = ControlJournal()
    eng = mk_engine(journal=journal, checkpoint_every=8)
    res = eng.run(max_steps=RECOVERY_MAX_STEPS, arrivals=arrivals)
    return eng._steps, res, journal


# ---------------------------------------------------------------- watchdog
# The engines' own step-space stall watchdogs (MAX_STEPS, EngineStallError)
# are product code and catch a livelock inside the contract; this catches
# whatever is left, outside it: a wedged collective, a deadlocked callback.
import contextlib  # noqa: E402
import faulthandler  # noqa: E402
import signal  # noqa: E402
import time  # noqa: E402

from _pytest.faulthandler import fault_handler_stderr_fd_key  # noqa: E402

WATCHDOG_S = 120
HARD_S = 30      # past the limit, for a main thread that never came back
_ends = []       # time.monotonic() ends of the limits in force, innermost last


def _arm_hard_stop(fd):
    """The handler below runs only once the main thread is back in Python. A
    deadlock of the Pallas interpreter's callback threads under jax's
    dispatch (test_hierarchical.py::test_dispatch_combine_2d_fp8_aligned_cap
    in 3 of 5 whole runs of PR 24's third session, before it got a child
    process of its own: every thread in a futex, the alarm pending for 15
    minutes) never comes back, and one such worker held the whole run to the
    driver's limit. So ``HARD_S`` after the limit faulthandler's own
    thread dumps every stack and ENDS THE PROCESS: xdist reports the test its
    worker died in as failed and starts another worker for the rest. One test
    lost, not the run."""
    faulthandler.cancel_dump_traceback_later()
    if _ends:
        faulthandler.dump_traceback_later(
            max(1.0, _ends[-1] + HARD_S - time.monotonic()), exit=True,
            file=fd)


@contextlib.contextmanager
def _wall_limit(what, fd):
    """SIGALRM first: a test that is merely slow, or hangs where Python can
    still run a handler, FAILS with its own name and the limit, and the
    worker lives. The handler dumps every thread's stack before it raises,
    so the log says WHERE even if the raise then takes the process down
    (raising while the interpreter's callback threads wait on each other can
    abort it). No tier-1 test comes near the limit, so only a real hang pays
    either price."""
    def boom(signum, frame):
        faulthandler.dump_traceback(file=fd, all_threads=True)
        raise TimeoutError(
            f"watchdog: {what} exceeded the suite's {WATCHDOG_S}s wall limit")

    old = signal.signal(signal.SIGALRM, boom)
    outer = signal.alarm(WATCHDOG_S)
    _ends.append(time.monotonic() + WATCHDOG_S)
    _arm_hard_stop(fd)
    try:
        yield
    finally:
        signal.alarm(outer)          # 0 unless nested: then the outer's rest
        signal.signal(signal.SIGALRM, old)
        _ends.pop()
        _arm_hard_stop(fd)           # likewise: the outer's rest, or none


def _limited(item, what):
    """The `slow` tier is hours by design: its tests, and the fixtures
    they are first to ask for, run unlimited."""
    if item.get_closest_marker("slow"):
        return contextlib.nullcontext()
    return _wall_limit(what, item.config.stash.get(
        fault_handler_stderr_fd_key, sys.__stderr__.fileno()))


# ---------------------------------------------------------------- order
# ``--dist load`` deals CONSECUTIVE tests to a worker, a sixth of a quarter
# of the suite at first (40 of 964), then runs of 30-60 as the queue runs
# down and two at a time at the end: a late file's tests land on all six
# workers, and each of them builds the file's module fixtures and traces its
# engines' programs again (a worker has its own ``serving.programs`` memo).
# So the files go in this order, the rest after them by name:
# 1. the five family files, whose module fixtures are a minute of
#    interpreter and more (the driver's run of PR 41: test_window_moe.py 880
#    worker-seconds where one process takes 388);
# 2. the files that build engines, those of ONE model and shape side by
#    side (``micro_model``, then ``moe_model`` on a mesh, then the tiny
#    model), so that a worker meets a shape many times and traces it once
#    (PR 42: test_serving.py dealt late cost 469 worker-seconds of which a
#    second trace in every worker was most);
# 3. the files whose single tests take a minute and more, so that none of
#    them starts when the queue is empty and five workers stand idle.
# The order is the same in every worker (xdist refuses a run whose workers
# collected differently) and no test leans on it.
FIXTURE_HEAVY_FIRST = (
    "test_sink_window_moe.py", "test_window_moe.py",
    "test_linear_attn_moe.py", "test_short_conv_moe.py", "test_looped_lm.py",
    "test_latent_moe.py",
    "test_hybrid_ssm.py",
    "test_recovery.py", "test_chaos.py", "test_slo.py",
    "test_recovery_mesh.py", "test_sharded_serving.py",
    "test_overlap_serving.py", "test_speculate.py", "test_cluster.py",
    "test_long_context.py", "test_prefix_cache.py", "test_serving.py",
    "test_disagg.py", "test_pool_in_place.py", "test_aot_artifact.py",
    "test_autoscale.py", "test_lending.py",
    "test_aot_topology.py", "test_train.py", "test_tools.py",
    "test_ring_attention.py", "test_hierarchical.py",
    "test_sync_hardening.py")


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(FIXTURE_HEAVY_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))


@pytest.fixture(autouse=True)
def watchdog(request):
    with _limited(request.node, request.node.nodeid):
        yield


@pytest.hookimpl(wrapper=True)
def pytest_fixture_setup(fixturedef, request):
    """Module/session fixtures (golden runs, engines) set up BEFORE the
    autouse fixture above: hold them to the same limit by the same code."""
    if fixturedef.scope == "function":
        return (yield)
    item = request._pyfuncitem          # the test that asked first
    with _limited(item, f"fixture {fixturedef.argname!r} of {item.nodeid}"):
        return (yield)
