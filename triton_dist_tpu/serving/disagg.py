"""Disaggregated prefill/decode serving: KV page migration over the
one-sided shmem layer (ISSUE 6 tentpole; ROADMAP item 2).

The colocated ``ServingEngine`` time-slices ONE worker between chunked
prefill and decode, so a long prompt still steals step time from every
decoding request. This module splits the two roles across a 2-entry mesh
axis (default ``"role"``) and applies the paper's producer/consumer
thesis to the handoff:

- role 0, the **prefill worker**, owns a prompt queue and runs
  ``prefill_chunk_paged`` — at most one chunk per engine step, exactly
  like the colocated engine. As each chunk FINALIZES pages (a page is
  final once the cursor passes its last token, or at the final chunk),
  the migration kernel (``ops.page_migrate``) pushes them with one
  ``putmem_nbi`` per (layer, page) into the decode worker's pool at
  pre-reserved destination ids, then fires ONE counted ``signal_op`` per
  chunk (+n pages). PR 4's chunk cursor and ``KVPagePool.free_tail`` make
  the chunk the natural migration unit: a mid-prefill preemptee keeps its
  filled pages AND its already-migrated pages — nothing is re-sent, the
  resumed prefill migrates only what it newly finalizes.
- role 1, the **decode worker**, never sees a prompt token. Its
  ``KVPagePool`` hands out the destination pages at ADMISSION time
  ("remote reservation" — the prefill worker knows every chunk's
  destination before it runs), its block-table rows expose only the
  landed PREFIX of each request's pages (``KVPagePool.landed_row``), and
  a slot flips to ACTIVE the step the signals covering its prompt pages
  have all fired — signal-gated admission: no barrier, and the wait path
  is the in-kernel ``signal_wait_until``/``wait_recv`` chain, not a host
  round-trip. Only the FIRST TOKEN (one int, argmaxed on the prefill
  device by the final chunk) rides the host control plane.

Metrics isolation is the point: the decode worker's
``step_prefill_tokens`` is identically 0 and its per-step stall no
longer contains prefill work at all — decode ITL is independent of peer
prompt length (pinned by test in token/step space, where CPU-host noise
cannot fake it).

Determinism/bit-identity: migration is an exact page copy, the first
token is computed by the same fused chunk argmax, and decode runs the
same ``decode_multistep_paged`` program over the same page contents — so
per-request outputs are bit-identical to the colocated chunked engine,
including across preemptions on either worker (tests/test_disagg.py).

Topology: one driver process, SPMD over the role axis — every device
program (chunk, decode, migrate) is one ``shard_map`` program both roles
enter; the off-role shard runs the same program on PARKED inputs
(prompt_len 0 / limit 0 rows write only to its own reserved scratch
page). This is the interpret-mesh/TDT_SERIAL form of the two-process
deployment (see docs/serving.md for the launch recipe and the
``MP_AG_UNSUPPORTED`` CPU caveat).
"""

from __future__ import annotations

import json
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.models.llama import (LlamaConfig, init_page_pool,
                                          require_config)
from triton_dist_tpu.serving import checkpoint as ckpt_mod
from triton_dist_tpu.serving import programs
from triton_dist_tpu.serving.deadline import (Backoff, Deadline,
                                              EngineStallError)
from triton_dist_tpu.serving.engine import (class_label, mark_prefill_start,
                                            record_first_token)
from triton_dist_tpu.serving.journal import ControlJournal
from triton_dist_tpu.serving.kv_pool import (KVPagePool, PageLedgerError,
                                             _fnv1a)
from triton_dist_tpu.serving.metrics import ServingMetrics
from triton_dist_tpu.serving.prefix_cache import PrefixCache
from triton_dist_tpu.serving.scheduler import (AdmissionRejected,
                                               ContinuousBatchingScheduler,
                                               Request, RequestState,
                                               SLOPolicy, TtlExpired)
from triton_dist_tpu.shmem import faults
from triton_dist_tpu.shmem.context import (ShmemContext,
                                           initialize_distributed)

PREFILL_ROLE = 0
DECODE_ROLE = 1


class MigrationSignalTimeout(RuntimeError):
    """A completed prefill's covering signals never arrived within the
    whole recovery ladder's budget (deadline + every retry rung). Either
    the transport dropped signals/pages repeatedly, the peer is dead, or
    a chunk was never sent — the message names the request, the per-chunk
    expected/landed counts and covered/missing pages (the ledger dump),
    so the operator can tell which. Since ISSUE 7 this is a PER-REQUEST
    failure reason (``Request.failure``), not an engine-wide crash."""


class SignalProtocolError(RuntimeError):
    """Over-signal: a chunk's landed count exceeded the number of pages
    the chunk was ever expected to deliver. A duplicated (or forged)
    signal increment is a protocol violation — before ISSUE 7 it
    silently inflated the count and could expose pages whose delivery
    was never actually confirmed; now it poisons exactly the affected
    request (degrade or fail), never the engine. Carries the ledger
    dump."""


class ChunkSignalLedger:
    """Host mirror of the per-chunk signal protocol.

    The KERNEL is the source of truth — ``landed`` counts come from the
    migration kernel's consumer-side report, which is ordered after every
    ``wait_recv`` of the chunk (ops/page_migrate.py) — the ledger only
    aggregates those reports per (request, chunk) so the scheduler can ask
    "which pages are covered?" without touching the device. Out-of-order
    chunk delivery is tolerated by construction: coverage is the union
    over COMPLETE chunks (landed >= expected), whatever order they
    completed in. Re-``expect``-ing a chunk (preemption restart or a
    deadline-triggered retry re-sends it) resets its count AND bumps its
    generation — the pages must land again before they count, and a
    report stamped with an older generation (a delayed delivery from a
    superseded attempt) is discarded as stale rather than double-counted.
    Over-signal (landed > expected within one generation) raises
    ``SignalProtocolError`` — a duplicate increment must never silently
    widen coverage.
    """

    def __init__(self):
        # rid -> {chunk_idx: [expected dst ids (tuple), landed count,
        #                     src ids (tuple, retry source), generation]}
        self._chunks: dict[int, dict[int, list]] = {}

    def expect(self, rid: int, chunk_idx: int, dst_ids,
               src_ids=(), generation: int = 0) -> None:
        self._chunks.setdefault(rid, {})[chunk_idx] = [
            tuple(int(p) for p in dst_ids), 0,
            tuple(int(p) for p in src_ids), int(generation)]

    def landed(self, rid: int, chunk_idx: int, count: int,
               generation: int = 0) -> bool:
        """Feed one kernel-reported landed count. Returns False (and
        counts nothing) when ``generation`` is stale — the chunk has been
        re-armed by a retry since this report's send was issued. Raises
        ``SignalProtocolError`` on over-signal."""
        ent = self._chunks.get(rid, {}).get(chunk_idx)
        if ent is None:
            raise KeyError(
                f"signal for unknown chunk {chunk_idx} of request {rid}")
        if int(generation) != ent[3]:
            return False
        ent[1] += int(count)
        if ent[1] > len(ent[0]):
            raise SignalProtocolError(
                f"over-signal on chunk {chunk_idx} of request {rid}: "
                f"{ent[1]} landed signals for {len(ent[0])} expected pages "
                f"(generation {ent[3]}) — a signal increment was "
                f"duplicated or forged. Ledger: {self.describe(rid)}")
        return True

    def chunk_complete(self, rid: int, chunk_idx: int) -> bool:
        ent = self._chunks.get(rid, {}).get(chunk_idx)
        return ent is not None and ent[1] >= len(ent[0])

    def covered(self, rid: int) -> set[int]:
        """Page ids whose delivery is fully signalled: the union over
        complete chunks. A chunk at 2/3 signals covers NOTHING — partial
        coverage cannot distinguish which pages landed."""
        out: set[int] = set()
        for ids, got, *_ in self._chunks.get(rid, {}).values():
            if got >= len(ids):
                out.update(ids)
        return out

    def expected(self, rid: int) -> set[int]:
        out: set[int] = set()
        for ids, *_ in self._chunks.get(rid, {}).values():
            out.update(ids)
        return out

    def complete(self, rid: int) -> bool:
        chunks = self._chunks.get(rid, {})
        return all(got >= len(ids) for ids, got, *_ in chunks.values())

    def incomplete_chunks(self, rid: int) -> list[tuple[int, tuple, tuple]]:
        """(chunk_idx, src_ids, dst_ids) of every chunk still short of
        full coverage — the retry work list. Chunks whose send recorded
        no source ids (pre-retention sends) are still listed; the caller
        decides whether their sources survive."""
        return [(ci, ent[2], ent[0])
                for ci, ent in sorted(self._chunks.get(rid, {}).items())
                if ent[1] < len(ent[0])]

    def generation(self, rid: int, chunk_idx: int) -> int | None:
        ent = self._chunks.get(rid, {}).get(chunk_idx)
        return None if ent is None else ent[3]

    def rids(self):
        return list(self._chunks.keys())

    def chunk_items(self, rid: int):
        """(chunk_idx, expected dst ids, landed count) triples — the
        audit interface ``KVPagePool.check(ledger=...)`` consumes."""
        return [(ci, ent[0], ent[1])
                for ci, ent in sorted(self._chunks.get(rid, {}).items())]

    def reset(self, rid: int) -> None:
        self._chunks.pop(rid, None)

    def describe(self, rid: int) -> str:
        """The ledger dump (ISSUE 7 satellite): per-chunk expected vs
        landed counts plus which pages are covered/missing — every typed
        failure reason embeds this, so a field report is actionable
        without a debugger."""
        chunks = self._chunks.get(rid, {})
        if not chunks:
            return "no chunks recorded"
        per_chunk = ", ".join(
            f"chunk {ci}: {got}/{len(ids)} signals gen {gen} "
            f"(pages {list(ids)})"
            for ci, (ids, got, _src, gen) in sorted(chunks.items()))
        covered = self.covered(rid)
        missing = sorted(self.expected(rid) - covered)
        return (f"{per_chunk}; covered pages {sorted(covered)}, "
                f"missing {missing}")


class PageMigrationChannel:
    """The prefill worker's sending half: guards, launches the migration
    kernel for one chunk's finalized pages, and feeds the ledger from the
    kernel's consumer-side landed report.

    Fault injection (ISSUE 7) is consulted HERE, per send event — this is
    the host-tier twin of the trace-time device hooks: on CPU the
    interpret-mode kernel elides the remote ``signal_op`` (delivery rides
    the DMA recv semaphores), so the only place a CPU chaos test can
    observe a lost/duplicated/late *signal* is the report path between
    the kernel and the ledger. A drop loses the landed report (the pages
    may well be there — the protocol must not believe it until a signal
    says so), a dup doubles the counted increment, a delay buffers the
    report for k engine steps (delivered by ``tick``), and a dead peer
    suppresses the launch entirely — nothing lands, nothing reports.
    Every attempt of every chunk gets a monotonically increasing attempt
    number, stamped into the kernel send as its generation tag and
    echoed back in the landed report (ops/page_migrate.py)."""

    def __init__(self, launch, pmax: int, reserved: int,
                 metrics: ServingMetrics, consumer: int = DECODE_ROLE,
                 plan: "faults.FaultPlan | None" = None, clock=None):
        self.ledger = ChunkSignalLedger()
        self._launch = launch          # jitted migrate_pages closure
        self.pmax = pmax
        self.reserved = reserved
        self.metrics = metrics
        self.consumer = consumer
        self.plan = plan
        self._clock = clock or (lambda: 0)   # engine-step supplier
        self._attempt: dict[tuple[int, int], int] = {}
        # delayed landed reports: (deliver_at_step, rid, chunk, count, gen)
        self._delayed: list[tuple[int, int, int, int, int]] = []

    def _active_plan(self):
        return self.plan if self.plan is not None else faults.active_plan()

    def forget(self, rid: int) -> None:
        """Drop attempt counters for a request leaving the system
        (finished/failed). Its ledger entries are reset separately; any
        still-buffered delayed report for it is delivered to a missing
        entry and discarded as stale."""
        for key in [k for k in self._attempt if k[0] == rid]:
            del self._attempt[key]

    def send_chunk(self, rid: int, chunk_idx: int, src_ids, dst_ids,
                   pool_k, pool_v):
        """Push one chunk's pages; returns the threaded pools. The id
        arrays are padded to the compiled ``pmax`` width (one program for
        every chunk size); padding is never dereferenced by the kernel.
        Re-sending the same chunk (preemption restart or deadline retry)
        bumps its attempt number/generation."""
        n = len(src_ids)
        assert n == len(dst_ids), (src_ids, dst_ids)
        assert 0 < n <= self.pmax, (n, self.pmax)
        for p in (*src_ids, *dst_ids):
            if p < self.reserved:
                raise PageLedgerError(
                    f"refusing to migrate reserved scratch page {p} "
                    f"(request {rid}) — scratch is engine-local parking")
        attempt = self._attempt.get((rid, chunk_idx), -1) + 1
        self._attempt[(rid, chunk_idx)] = attempt
        self.ledger.expect(rid, chunk_idx, dst_ids, src_ids=src_ids,
                           generation=attempt)
        plan = self._active_plan()
        now = self._clock()
        if plan is not None and plan.peer_dead(now):
            # dead link: the launch never happens — no pages move, no
            # report arrives, and the ledger stays at 0/n until the
            # consumer-side deadline walks the recovery ladder
            self.metrics.inc("faults_injected")
            return pool_k, pool_v
        src = np.zeros(self.pmax, np.int32)
        dst = np.zeros(self.pmax, np.int32)
        src[:n] = src_ids
        dst[:n] = dst_ids
        t0 = time.perf_counter()
        pool_k, pool_v, landed = self._launch(
            jnp.asarray(src), jnp.asarray(dst),
            jnp.asarray([n], np.int32), jnp.asarray([attempt], np.int32),
            pool_k, pool_v)
        row = np.asarray(landed)[self.consumer]
        got, echoed = int(row[0]), int(row[1])
        assert echoed == attempt, (
            f"migrate kernel echoed tag {echoed} for send attempt "
            f"{attempt} (rid {rid} chunk {chunk_idx})")
        dt = time.perf_counter() - t0
        self.metrics.inc("migrate_chunks")
        self.metrics.observe("migrate_s", dt)
        action, k = (("ok", 0) if plan is None
                     else plan.signal_action(rid, chunk_idx, attempt))
        if action == "drop":
            # the signal never arrives: pages moved, the protocol must
            # not (and does not) believe it
            self.metrics.inc("faults_injected")
            return pool_k, pool_v
        if action == "delay":
            self.metrics.inc("faults_injected")
            self._delayed.append((now + k, rid, chunk_idx, got, attempt))
            return pool_k, pool_v
        if action == "dup":
            self.metrics.inc("faults_injected")
            got *= 2                   # duplicated increment — over-signal
        if self.ledger.landed(rid, chunk_idx, got, generation=attempt):
            self.metrics.inc("pages_migrated", min(got, n))
        return pool_k, pool_v

    def tick(self, now: int) -> list[tuple[int, Exception]]:
        """Deliver delayed landed reports whose time has come. Returns
        the (rid, error) pairs of any report that tripped a protocol
        error on delivery — the engine routes those into the affected
        request's failure domain. Reports for unknown chunks (the
        request finished/failed/was re-armed meanwhile) and stale
        generations are discarded and counted as ``stale_signals``."""
        if not self._delayed:
            return []
        due = [d for d in self._delayed if d[0] <= now]
        self._delayed = [d for d in self._delayed if d[0] > now]
        poisoned: list[tuple[int, Exception]] = []
        for _, rid, chunk_idx, got, gen in due:
            try:
                fresh = self.ledger.landed(rid, chunk_idx, got,
                                           generation=gen)
            except KeyError:
                fresh = False
            except SignalProtocolError as e:
                poisoned.append((rid, e))
                continue
            if fresh:
                self.metrics.inc("pages_migrated", got)
            else:
                self.metrics.inc("stale_signals")
        return poisoned


class DisaggServingEngine:
    """Continuous-batching serving with prefill and decode on separate
    workers, KV handed off by page migration (module docstring).

    ``num_pages``/``page_size`` size EACH role's pool (plus one scratch
    page per role). ``num_slots`` is the decode batch width;
    ``num_prefill_slots`` bounds concurrent chunked prefills.
    ``prefill_chunk`` sizes a chunk, which is also the migration unit.

    Recovery ladder (ISSUE 7): a MIGRATING request's wait for covering
    signals runs against a ``Deadline`` of ``signal_deadline_steps``
    decode-worker steps. On expiry the engine RETRIES — re-issues the
    ``migrate_pages`` send for every incomplete chunk (the prefill worker
    RETAINS its source pages through MIGRATING precisely so the bytes
    still exist to re-send) — with exponential backoff over at most
    ``max_retries`` rungs. When the rungs run dry (or the sources are
    gone, or a chunk was never sent, or the ledger detected over-signal)
    the request DEGRADES: the decode worker re-prefills the prompt
    locally into its own reserved pages using the same compiled chunk
    program (real inputs in the DECODE_ROLE row — the PR-6 preemption
    fallback run in place, without bouncing through the possibly-dead
    peer), up to ``max_degradations`` times. Only with
    ``allow_degradation=False`` (a decode worker genuinely unable to
    prefill) or the degradation budget spent does the request become
    ``FAILED`` — with a typed reason carrying the ledger dump — while
    the engine and every other request keep running. ``engine.run`` adds
    a global progress watchdog (``stall_deadline_steps``, auto-sized
    above the whole ladder budget) raising ``EngineStallError`` so no
    residual bug can ever present as a hang. ``fault_plan`` injects a
    seeded :class:`~triton_dist_tpu.shmem.faults.FaultPlan` into the
    migration channel (tests/test_chaos.py drives this).

    Request lifecycle: QUEUED (prefill queue) → PREFILLING (prefill slot;
    decode-side pages reserved; chunks run and migrate) → MIGRATING
    (prefill done, first token in hand, prefill-side pages RETAINED as
    the retry source; waiting for a decode slot + covering signals) →
    ACTIVE (decoding; prefill-side pages released on the flip) →
    FINISHED, with the FAILED terminal only at the bottom of the ladder.
    A decode-side victim loses its pages AND its migrated KV: it requeues
    at the FRONT of the prefill queue and re-prefills from scratch —
    greedy determinism regenerates identical tokens. A prefill-side
    victim (``force_preempt_prefill``) keeps its filled + migrated pages
    and resumes at its chunk cursor.
    """

    def __init__(self, params: dict, cfg: LlamaConfig,
                 ctx: ShmemContext | None = None, axis: str = "role",
                 num_slots: int = 4, num_prefill_slots: int = 2,
                 page_size: int = 16, num_pages: int = 64,
                 pages_per_seq: int = 8, prefill_chunk: int = 16,
                 decode_horizon: int = 1, eos_id: int | None = None,
                 ffn=None, signal_deadline_steps: int = 16,
                 max_retries: int = 3, allow_degradation: bool = True,
                 max_degradations: int = 1,
                 stall_deadline_steps: int | None = None,
                 wall_deadline_s: float | None = None,
                 fault_plan: "faults.FaultPlan | None" = None,
                 metrics: ServingMetrics | None = None,
                 metrics_decode: ServingMetrics | None = None,
                 journal: ControlJournal | None = None,
                 checkpoint_every: int | None = None,
                 queue_cap: int | None = None,
                 ttl_steps: int | None = None,
                 prefix_cache: bool = False,
                 slo: SLOPolicy | None = None,
                 artifact=None, artifact_key: str | None = None):
        require_config(cfg, LlamaConfig, "DisaggServingEngine")
        assert prefill_chunk >= 1 and decode_horizon >= 1
        assert signal_deadline_steps >= 1 and max_retries >= 0
        assert checkpoint_every is None or checkpoint_every >= 1
        assert queue_cap is None or queue_cap >= 1
        assert ttl_steps is None or ttl_steps >= 1
        assert checkpoint_every is None or journal is not None, (
            "checkpoint_every needs a journal to record into")
        if ctx is None:
            ctx = initialize_distributed(axis_names=(axis,), mesh_shape=(2,))
        assert ctx.axis_size(axis) == 2, (
            f"disaggregation needs exactly 2 ranks on axis {axis!r}")
        self.ctx = ctx
        self.axis = axis
        self.params = params
        self.cfg = cfg
        self.page_size = page_size
        self.pages_per_seq = pages_per_seq
        self.num_slots = num_slots
        self.prefill_chunk = prefill_chunk
        self.decode_horizon = decode_horizon
        self.eos_id = eos_id
        self.signal_deadline_steps = signal_deadline_steps
        self.max_retries = max_retries
        self.allow_degradation = allow_degradation
        self.max_degradations = max_degradations
        self.wall_deadline_s = wall_deadline_s
        # the whole ladder's worst-case wait for ONE request: the initial
        # deadline plus every backoff rung — the stall watchdog must sit
        # safely above it, or legitimate ladder waits would trip it
        ladder = signal_deadline_steps * (2 ** (max_retries + 1) - 1)
        self._stall_steps = (stall_deadline_steps if stall_deadline_steps
                             is not None else max(256, 4 * ladder))
        # TTFT lives on the prefill worker's panel, ITL on the decode
        # worker's — the isolation the disaggregation exists to provide
        self.metrics = metrics or ServingMetrics()
        self.metrics_decode = metrics_decode or ServingMetrics()

        # ONE symmetric pool pair: each role owns an identical local
        # [L, P+1, Hkv, ps, D] shard (id 0 reserved as that role's scratch
        # page); the migration kernel's remote refs resolve into the peer
        # shard by construction.
        ref = init_page_pool(cfg, 1, page_size)      # shape/dtype template
        local = (cfg.n_layers, num_pages + 1) + ref["k"].shape[2:]
        self.pool_k = ctx.create_symm_tensor(local, ref["k"].dtype, axis=axis)
        self.pool_v = ctx.create_symm_tensor(local, ref["v"].dtype, axis=axis)
        self.alloc_p = KVPagePool(num_pages + 1, page_size, reserved=1)
        self.alloc_d = KVPagePool(num_pages + 1, page_size, reserved=1)
        # prefix cache (ISSUE 13) lives on the PREFILL pool only: hits
        # skip the chunk compute but every page still migrates, so the
        # decode worker never needs to know a prefix was cached. Adopted
        # pages must be solely owned (check_migratable's refcount clause),
        # so adoption stops at the first matched page another live
        # request still references.
        self.prefix_cache = (PrefixCache(self.alloc_p, page_size)
                             if prefix_cache else None)
        # the bounded admission queue (ISSUE 9) guards the PREFILL worker's
        # intake — that is where fresh arrivals wait; preemption requeues
        # (front=True) are exempt by scheduler construction
        # SLO policy (ISSUE 14) attaches to the PREFILL scheduler — that is
        # the only admission point; the decode scheduler stays policy-free
        # and its class-aware victim ordering reads the shed_level stamp
        # each request carries
        self.slo = slo
        self.sched_p = ContinuousBatchingScheduler(num_prefill_slots,
                                                   queue_cap=queue_cap,
                                                   policy=slo)
        self.sched_d = ContinuousBatchingScheduler(num_slots)
        # crash consistency (ISSUE 9): journal + checkpoint cadence + the
        # overload knobs, mirroring ServingEngine's control surface
        self.journal = journal
        self.checkpoint_every = checkpoint_every
        self.ttl_steps = ttl_steps
        self._fault_plan = fault_plan
        self._journal_muted = False
        self._replaying = False
        self._incarnation = 0
        self._last_ckpt_step = -1
        self._rejected: list[Request] = []
        self._handoff: deque[Request] = deque()   # MIGRATING, no slot yet
        self._dslot: dict[int, int] = {}          # rid -> decode slot
        self._wait_steps: dict[int, int] = {}     # rid -> signal-wait steps
        # recovery ladder state (ISSUE 7): per-MIGRATING-request deadline
        # + backoff; requests whose ledger tripped a protocol error
        # (poisoned coverage — degrade/fail on sight, never retry); rids
        # currently re-prefilling LOCALLY on the decode worker
        self._recovery: dict[int, tuple[Deadline, Backoff]] = {}
        self._poisoned: dict[int, Exception] = {}
        self._local_prefill: set[int] = set()
        self._finished: list[Request] = []
        self._failed: list[Request] = []
        self._next_rid = 0
        self._steps = 0

        # decode-worker slot mirrors (control plane); the [2, B] stacked
        # device arrays are authoritative between dispatches — row
        # PREFILL_ROLE is permanently parked (zeros → scratch page)
        B = num_slots
        self._token = np.zeros(B, np.int32)
        self._pos = np.zeros(B, np.int32)
        self._bt = np.zeros((B, pages_per_seq), np.int32)
        self._z_row = np.zeros(B, np.int32)
        self._z_bt = np.zeros((B, pages_per_seq), np.int32)
        # uploads are placed with the stacked-role sharding up front so the
        # decode program sees ONE argument signature from the very first
        # dispatch (host-upload steps and steady-state feedback steps would
        # otherwise compile two variants — the compile guard pins this)
        self._up = lambda a: ctx.shard(jnp.asarray(a), P(axis))
        self._token_dev = self._up(np.stack([self._z_row, self._token]))
        self._pos_dev = self._up(np.stack([self._z_row, self._pos]))
        self._bt_dev = self._up(np.stack([self._z_bt, self._bt]))
        self._dirty = False

        # widest possible per-chunk migration: a C-token chunk can
        # finalize at most C//ps whole pages plus the straddle page it
        # completes plus the final chunk's partial last page — and a
        # RETRY may need to re-send a whole prompt's pages in one call
        pmax = max(prefill_chunk // page_size + 2, pages_per_seq)

        # -- the three device programs (each ONE compiled SPMD program
        # both roles enter), built once a process for this configuration,
        # mesh and these shapes (serving/programs.py) ----
        self._chunk_step, self._dec_step, self._migrate, lint = \
            programs.disagg_programs(
                cfg, decode_horizon, eos_id, ffn, ctx, axis,
                (PREFILL_ROLE, DECODE_ROLE), prefill_chunk, B, pages_per_seq,
                pmax, programs.signature((self.pool_k, self.pool_v)),
                programs.signature(params))

        # AOT artifact seeding (ISSUE 15): replace all three SPMD programs
        # with the artifact's deserialized executables BEFORE the channel
        # captures the migrate launch — zero fresh traces from cold start
        # to first token (compile_stats reports aot_programs)
        self._aot_artifact = artifact
        if artifact is not None:
            self._aot_key = artifact_key or "disagg"
            self._chunk_step = artifact.program(self._aot_key, "chunk")
            self._dec_step = artifact.program(self._aot_key, "decode")
            self._migrate = artifact.program(self._aot_key, "migrate")

        programs.lint_if_asked(lint, self)

        self.channel = PageMigrationChannel(
            self._migrate, pmax, reserved=1, metrics=self.metrics,
            consumer=DECODE_ROLE, plan=fault_plan,
            clock=lambda: self._steps)

    # -- request intake (prefill worker) ----------------------------------
    def _ttl_for(self, req: Request) -> int | None:
        """Class TTL override (ISSUE 14) beats the engine-wide knob."""
        spec = self.sched_p.class_spec(req)
        if spec is not None and spec.ttl_steps is not None:
            return spec.ttl_steps
        return self.ttl_steps

    def submit(self, prompt, max_new_tokens: int, rid: int | None = None,
               tenant: str | None = None, cls: str | None = None) -> int:
        prompt = tuple(int(t) for t in np.asarray(prompt).reshape(-1))
        assert prompt and max_new_tokens >= 1
        total = len(prompt) + max_new_tokens - 1
        need = -(-total // self.page_size)
        assert need <= self.pages_per_seq, (
            f"request needs {need} pages > pages_per_seq "
            f"{self.pages_per_seq}")
        assert need <= self.alloc_d.num_pages - self.alloc_d.reserved, (
            f"request needs {need} pages > decode pool size")
        if rid is None:
            rid = self._next_rid
        self._next_rid = max(self._next_rid, rid + 1)
        req = Request(rid=rid, prompt=prompt, max_new_tokens=max_new_tokens,
                      eos_token=self.eos_id, submit_step=self._steps,
                      submit_time=time.perf_counter())
        self.sched_p.stamp(req, tenant=tenant, cls=cls)
        self.metrics.inc("requests_submitted")
        self.metrics.inc_class("requests_submitted", class_label(req))
        # bounded admission (ISSUE 9): shed fresh arrivals at capacity —
        # journal replay bypasses the cap (the WAL holds the authoritative
        # accept/reject decisions). Per-class caps (ISSUE 14) shed batch
        # while chat still admits.
        if self.sched_p.at_capacity_for(req.cls) and not self._replaying:
            spec = self.sched_p.class_spec(req)
            cap = (spec.queue_cap if spec is not None
                   and spec.queue_cap is not None
                   and not self.sched_p.at_capacity
                   else self.sched_p.queue_cap)
            req.state = RequestState.REJECTED
            req.failure = AdmissionRejected(
                f"admission queue full for class {req.cls!r} (cap {cap}) — "
                f"request {rid} rejected")
            self._rejected.append(req)
            self.metrics.inc("rejections")
            self.metrics.inc_class("rejections", class_label(req))
            self._jlog("reject", rid=rid, reason=str(req.failure),
                       tenant=req.tenant, cls=req.cls)
            return rid
        ttl = self._ttl_for(req)
        if ttl is not None:
            req.deadline = Deadline(ttl, req.submit_step)
        self.sched_p.submit(req)
        self._jlog("submit", rid=rid, prompt=list(prompt),
                   max_new_tokens=max_new_tokens,
                   tenant=req.tenant, cls=req.cls)
        return rid

    # -- prefill worker ----------------------------------------------------
    def _can_hold(self, req: Request) -> bool:
        """Admission needs BOTH sides: prefill pages to compute into (a
        mid-prefill preemptee kept its filled ones) and the decode-side
        reservation (kept across prefill preemptions)."""
        need = -(-len(req.prompt) // self.page_size)
        need_p = need - len(self.alloc_p.pages_of(req.rid))
        need_d = need - len(self.alloc_d.pages_of(req.rid))
        # refcount-0 cached pages are reclaimable capacity on the prefill
        # side (no hit discount: adoption trades evictable for owed 1:1,
        # so the bound stays valid whether or not the prompt hits)
        avail_p = self.alloc_p.free_pages + (
            self.prefix_cache.evictable if self.prefix_cache else 0)
        return (avail_p >= max(need_p, 0)
                and self.alloc_d.free_pages >= max(need_d, 0))

    def _cache_adopt(self, req: Request) -> None:
        """Match the prompt against the prefix index and adopt the
        longest SOLELY-ADOPTABLE prefix of the hit: every adopted page
        must be refcount-0 (on the cached LRU list) so that after
        ``acquire`` it is solely owned and ``check_migratable`` accepts
        it. A matched page another live request still references
        truncates the adoption there — correctness never depends on the
        truncation, the chunks just recompute."""
        cache = self.prefix_cache
        if (cache is None or req.prefill_cursor > 0
                or self.alloc_p.holds(req.rid)):
            return
        hit = cache.match(req.prompt)
        solo = []
        for p in hit:
            if self.alloc_p.refcount(p) != 0:
                break
            solo.append(p)
        if not solo:
            self.metrics.inc("prefix_misses")
            return
        self.alloc_p.acquire(req.rid, solo)
        req.cache_hit_tokens = len(solo) * self.page_size
        self.metrics.inc("prefix_hits")
        self.metrics.inc("prefix_hit_tokens", req.cache_hit_tokens)

    def _admit_prefill(self, slot: int, req: Request) -> None:
        self._cache_adopt(req)
        sp = len(req.prompt)
        need = -(-sp // self.page_size)
        have_p = len(self.alloc_p.pages_of(req.rid))
        if need > have_p:
            short = (need - have_p) - self.alloc_p.free_pages
            if short > 0 and self.prefix_cache is not None:
                self.metrics.inc("prefix_evictions",
                                 self.prefix_cache.evict(short))
            got = self.alloc_p.alloc(req.rid, need - have_p)
            assert got is not None, "admissible() guaranteed the pages"
        # remote reservation: the decode worker's pages for this prompt
        # are fixed NOW, so every later chunk knows its destination ids
        # without a round-trip — and landed KV survives prefill-side
        # preemption because the reservation does
        have_d = len(self.alloc_d.pages_of(req.rid))
        if need > have_d:
            got = self.alloc_d.alloc(req.rid, need - have_d)
            assert got is not None, "admissible() guaranteed the pages"
        self.sched_p.activate(slot, req)
        self._jlog("admit", rid=req.rid, slot=slot)
        req.state = RequestState.PREFILLING
        mark_prefill_start(req, self.metrics, self._steps)
        self.metrics.inc("prefills")

    def _migrate_finalized(self, req: Request, start: int,
                           cursor_new: int) -> None:
        """Send exactly the pages this chunk FINALIZED: whole pages whose
        last token the cursor just passed, plus (on the final chunk) the
        partial last page. Derived from the cursor, so each page is sent
        exactly once per prefill attempt and a cursor-resumed preemptee
        never re-sends what it migrated before the eviction."""
        ps = self.page_size
        sp = len(req.prompt)
        done_before = start // ps
        done_after = (-(-sp // ps) if cursor_new >= sp
                      else cursor_new // ps)
        if done_after <= done_before:
            return
        src = self.alloc_p.pages_of(req.rid)[done_before:done_after]
        dst = self.alloc_d.pages_of(req.rid)[done_before:done_after]
        self.alloc_p.check_migratable(req.rid, src)
        self.alloc_d.check_migratable(req.rid, dst)
        chunk_idx = start // self.prefill_chunk
        self.pool_k, self.pool_v = self.channel.send_chunk(
            req.rid, chunk_idx, src, dst, self.pool_k, self.pool_v)
        # the migration attempt rides the journal (ISSUE 9): a restarted
        # decode worker re-admits migrated requests through the rebuilt
        # ledger instead of failing them — the journal records that the
        # attempt happened, the ledger decides whether it still counts
        self._jlog("migrate", rid=req.rid, chunk=chunk_idx,
                   pages=len(src), attempt=self.channel._attempt.get(
                       (req.rid, chunk_idx), 0))

    def _oldest_local_prefill(self) -> tuple[int, Request] | None:
        """Oldest (by admission ticket) degraded request re-prefilling
        locally on the decode worker — the DECODE_ROLE row's candidate
        for this step's chunk dispatch."""
        best = None
        for rid in self._local_prefill:
            slot = self._dslot[rid]
            r = self.sched_d.slots[slot]
            if r is None:
                continue
            if best is None or r.admitted_seq < best[1].admitted_seq:
                best = (slot, r)
        return best

    def _dispatch_chunks(self) -> int:
        """At most ONE chunk per WORKER per step (Sarathi co-scheduling,
        same policy as the colocated engine), in a single dispatch of the
        role-symmetric chunk program: the PREFILL_ROLE row advances the
        oldest PREFILLING prefill slot; the DECODE_ROLE row — parked in
        healthy operation — carries a DEGRADED request's local re-prefill
        chunk (ISSUE 7): same compiled program, real tokens/block-table
        in the decode row, writing straight into the decode worker's own
        reserved pages. That is what makes degradation free of new
        compiles AND free of the possibly-dead peer.

        The prefill row's final chunk hands the request off as MIGRATING
        with its device-argmaxed first token on the host control plane;
        its prefill-side pages are RETAINED (the retry source) until the
        decode side confirms coverage. The decode row's final chunk flips
        its request straight to ACTIVE — the KV and first token were
        recomputed locally, no signals to wait for. Returns PREFILL-row
        prompt tokens processed (the decode row's tokens are accounted
        separately as degraded_prefill_tokens — the decode worker's
        step_prefill_tokens isolation invariant only covers healthy
        operation)."""
        slot_p, req_p = None, None
        for i, r in enumerate(self.sched_p.slots):
            if (r is not None and r.state is RequestState.PREFILLING
                    and (req_p is None
                         or r.admitted_seq < req_p.admitted_seq)):
                slot_p, req_p = i, r
        local = self._oldest_local_prefill()
        if slot_p is None and local is None:
            return 0
        C = self.prefill_chunk
        # cache-hit fast path (ISSUE 13): a chunk fully inside the
        # adopted prefix skips the device compute — its pages already
        # hold that KV — but still advances the cursor and still
        # migrates, so the decode worker stays cache-oblivious. A chunk
        # that straddles the hit boundary recomputes in full (a
        # bit-identical rewrite into solely-owned pages, by greedy
        # determinism), and the FINAL chunk always computes: its fused
        # argmax produces the first token.
        skip_p = (req_p is not None
                  and req_p.prefill_cursor + C <= req_p.cache_hit_tokens
                  and req_p.prefill_cursor + C < len(req_p.prompt))
        tok_np = None
        dt = 0.0
        if not (skip_p and local is None):
            toks = np.zeros((2, C), np.int32)
            starts = np.zeros(2, np.int32)
            plens = np.zeros(2, np.int32)
            bt = np.zeros((2, self.pages_per_seq), np.int32)
            if req_p is not None and not skip_p:
                part = req_p.prompt[req_p.prefill_cursor:
                                    req_p.prefill_cursor + C]
                toks[PREFILL_ROLE, :len(part)] = part
                starts[PREFILL_ROLE] = req_p.prefill_cursor
                plens[PREFILL_ROLE] = len(req_p.prompt)
                bt[PREFILL_ROLE] = np.asarray(self.alloc_p.block_table_row(
                    req_p.rid, self.pages_per_seq), np.int32)
            if local is not None:
                slot_d, req_d = local
                part_d = req_d.prompt[req_d.prefill_cursor:
                                      req_d.prefill_cursor + C]
                toks[DECODE_ROLE, :len(part_d)] = part_d
                starts[DECODE_ROLE] = req_d.prefill_cursor
                plens[DECODE_ROLE] = len(req_d.prompt)
                bt[DECODE_ROLE] = np.asarray(self.alloc_d.block_table_row(
                    req_d.rid, self.pages_per_seq), np.int32)
            t0 = time.perf_counter()
            tok_dev, self.pool_k, self.pool_v = self._chunk_step(
                self.params, jnp.asarray(toks), jnp.asarray(starts),
                jnp.asarray(plens), self.pool_k, self.pool_v,
                jnp.asarray(bt))
            tok_np = np.asarray(tok_dev)                # fence + maybe toks
            dt = time.perf_counter() - t0

        ptoks = 0
        if req_p is not None:
            sp = len(req_p.prompt)
            start = req_p.prefill_cursor
            ptoks = min(C, sp - start)
            cursor_new = min(start + C, sp)
            req_p.prefill_cursor = cursor_new
            if skip_p:
                self.metrics.inc("prefix_skipped_chunks")
            else:
                self.metrics.inc("prefill_chunks")
                self.metrics.observe("prefill_stall_s", dt)
            self._jlog("chunk", rid=req_p.rid, cursor=cursor_new)
            try:
                self._migrate_finalized(req_p, start, cursor_new)
            except SignalProtocolError as e:
                self._poison(slot_p, req_p, e)
            if req_p.state is RequestState.PREFILLING and cursor_new >= sp:
                # prefill complete: the request leaves this worker's
                # SCHEDULER, but its pages stay owned — they are the
                # retry source until the decode side confirms coverage
                # (released on the ACTIVE flip / degradation / failure).
                # skip_p can't be set here (final chunks always compute),
                # so tok_np is real.
                if self.prefix_cache is not None:
                    self.prefix_cache.insert(
                        req_p.prompt,
                        self.alloc_p.pages_of(req_p.rid)[
                            :sp // self.page_size])
                    if req_p.first_token_time is None:
                        self.metrics.observe(
                            "ttft_cached_s" if req_p.cache_hit_tokens
                            else "ttft_cold_s",
                            time.perf_counter() - req_p.submit_time)
                req_p.first_token = int(tok_np[PREFILL_ROLE])
                record_first_token(req_p, self.metrics, self._steps)
                self.metrics.inc("tokens_generated")
                self.metrics.inc("handoffs")
                self.sched_p.remove(slot_p)
                req_p.state = RequestState.MIGRATING
                self._jlog("handoff", rid=req_p.rid)
                if req_p.rid not in self._dslot:
                    self._handoff.append(req_p)

        if local is not None:
            sp_d = len(req_d.prompt)
            start_d = req_d.prefill_cursor
            req_d.prefill_cursor = min(start_d + C, sp_d)
            self.metrics_decode.observe("degraded_prefill_tokens",
                                        min(C, sp_d - start_d))
            if req_d.prefill_cursor >= sp_d:
                self._complete_local_prefill(slot_d, req_d,
                                             int(tok_np[DECODE_ROLE]))
        return ptoks

    def _complete_local_prefill(self, slot: int, req: Request,
                                tok0: int) -> None:
        """A degraded request's local re-prefill finished: flip straight
        to ACTIVE. The first token was recomputed by the same fused chunk
        argmax (bit-identical to the remote one by greedy determinism);
        no handoff is counted — this request never completed one."""
        rid = req.rid
        self._local_prefill.discard(rid)
        self.metrics_decode.observe(
            "degraded_ttft_s", time.perf_counter() - req.submit_time)
        req.state = RequestState.ACTIVE
        req.generated.append(tok0)
        self.metrics_decode.inc("tokens_generated")
        self._token[slot] = tok0
        self._pos[slot] = len(req.prompt)
        self._bt[slot] = np.asarray(self.alloc_d.block_table_row(
            rid, self.pages_per_seq), np.int32)
        self._dirty = True
        if req.done:
            self._finish_decode(slot)

    def force_preempt_prefill(self) -> int | None:
        """Forced mid-prefill preemption on the PREFILL worker (test/ops
        hook): evict the youngest PREFILLING slot. Filled prefill pages
        survive via ``free_tail`` (cursor resume), and the decode-side
        reservation plus already-MIGRATED pages are untouched — the
        resumed prefill migrates only what it newly finalizes. Returns
        the evicted slot, or None when nothing is prefilling."""
        victim = self.sched_p.pick_victim()
        if victim is None:
            return None
        self._preempt_prefill(victim)
        return victim

    def _preempt_prefill(self, slot: int) -> None:
        req = self.sched_p.slots[slot]
        if req.prefill_cursor > 0:
            filled = -(-req.prefill_cursor // self.page_size)
            if filled < len(self.alloc_p.pages_of(req.rid)):
                self.alloc_p.free_tail(req.rid, keep=filled)
                # adopted pages past the kept prefix were just released
                # (back to the cached list — still indexed): the resumed
                # prefill re-allocs FRESH pages there, so the skip window
                # must shrink to what the kept pages actually cover, or
                # empty pages would migrate as if they held the prefix
                req.cache_hit_tokens = min(req.cache_hit_tokens,
                                           filled * self.page_size)
            else:
                # no unfilled tail to reclaim: full restart. The decode
                # reservation keeps its ids, so the restarted prefill
                # re-migrates to the SAME destinations (idempotent —
                # identical recomputed contents, re-counted signals).
                self.alloc_p.free_seq(req.rid)
                req.prefill_cursor = 0
                req.cache_hit_tokens = 0
        else:
            self.alloc_p.free_seq(req.rid)
            req.prefill_cursor = 0
            req.cache_hit_tokens = 0
        self.sched_p.evict(slot)
        self.metrics.inc("preemptions")
        self._jlog("preempt", rid=req.rid, slot=slot, worker="prefill")

    # -- decode worker -----------------------------------------------------
    def _seat_decode_slots(self) -> None:
        while self._handoff:
            slot = self.sched_d.free_slot()
            if slot is None:
                return
            req = self._handoff.popleft()
            self.sched_d.place(slot, req)
            self._dslot[req.rid] = slot

    def _check_signal_gate(self, slot: int, covered: set[int]) -> None:
        """The landmine invariant (ISSUE 6 acceptance): a MIGRATING slot's
        block-table row may expose ONLY pages whose delivery signal has
        fired. ``landed_row`` guarantees this by construction; this check
        makes any future regression loud instead of a silent garbage
        read."""
        for p in self._bt[slot]:
            p = int(p)
            if p >= self.alloc_d.reserved and p not in covered:
                raise RuntimeError(
                    f"signal-gate violation: decode block table exposes "
                    f"page {p} before its delivery signal fired")

    def _patch_and_admit(self) -> None:
        """Block-table patching + signal-gated admission, in slot order
        (deterministic). A MIGRATING slot's row tracks the landed prefix
        each step; the slot flips to ACTIVE the step its prompt pages are
        fully covered — the admission gate is the LEDGER (fed only by the
        kernel's post-wait landed reports), never a host-side clock.

        The wait is DEADLINED (ISSUE 7): expiry walks the recovery
        ladder — re-send the incomplete chunks with exponential backoff,
        then degrade to decode-local re-prefill, then (and only then)
        fail THIS request with a typed reason. The engine never raises
        out of here for a transport fault."""
        for slot in range(self.num_slots):
            req = self.sched_d.slots[slot]
            if req is None or req.state is not RequestState.MIGRATING:
                continue
            rid = req.rid
            if rid in self._poisoned:
                # coverage was voided by a protocol error: nothing the
                # ledger says about this request can be trusted, so the
                # retry rungs are skipped entirely
                self._degrade_or_fail(slot, req, self._poisoned.pop(rid))
                continue
            covered = self.channel.ledger.covered(rid)
            row = np.asarray(self.alloc_d.landed_row(
                rid, covered, self.pages_per_seq), np.int32)
            if not np.array_equal(row, self._bt[slot]):
                self._bt[slot] = row
                self._dirty = True
            self._check_signal_gate(slot, covered)
            sp = len(req.prompt)
            need = set(self.alloc_d.pages_of(rid)[:-(-sp // self.page_size)])
            if req.first_token is not None and need <= covered:
                self.metrics_decode.observe(
                    "migrate_wait_steps", self._wait_steps.pop(rid, 0))
                if req.retries:
                    # the ladder's retry rung earned this handoff
                    self.metrics_decode.observe(
                        "recovered_ttft_s",
                        time.perf_counter() - req.submit_time)
                self._recovery.pop(rid, None)
                if self.alloc_p.holds(rid):
                    # coverage confirmed: the retry source has served its
                    # purpose — release the prefill-side copies
                    self.alloc_p.free_seq(rid)
                req.state = RequestState.ACTIVE
                req.generated.append(req.first_token)
                self.metrics_decode.inc("handoffs")
                self._token[slot] = req.first_token
                self._pos[slot] = sp
                self._bt[slot] = np.asarray(self.alloc_d.block_table_row(
                    rid, self.pages_per_seq), np.int32)
                self._dirty = True
                if req.done:      # max_new_tokens == 1 or tok0 == eos_id
                    self._finish_decode(slot)
                continue
            self._wait_steps[rid] = self._wait_steps.get(rid, 0) + 1
            rec = self._recovery.get(rid)
            if rec is None:
                rec = (Deadline(self.signal_deadline_steps, self._steps,
                                wall_s=self.wall_deadline_s),
                       Backoff(self.signal_deadline_steps,
                               max_retries=self.max_retries))
                self._recovery[rid] = rec
            deadline, backoff = rec
            if not deadline.expired(self._steps):
                continue
            budget = backoff.next_budget()
            retried = False
            if budget is not None:
                try:
                    retried = self._retry_migration(req)
                except SignalProtocolError as e:
                    self._degrade_or_fail(slot, req, e)
                    continue
            if retried:
                deadline.rearm(budget, self._steps)
                continue
            missing = sorted(need - covered)
            self._degrade_or_fail(slot, req, MigrationSignalTimeout(
                f"request {rid} waited {self._wait_steps.get(rid, 0)} "
                f"decode steps (deadline {self.signal_deadline_steps}, "
                f"{backoff.attempt} retry rung(s) spent) for migration "
                f"signals covering pages {missing}; ledger: "
                f"{self.channel.ledger.describe(rid)}. A signal or page "
                "delivery was lost (or a chunk was never sent)."))

    # -- recovery ladder (ISSUE 7) ----------------------------------------
    def _retry_migration(self, req: Request) -> bool:
        """Rung 1: re-issue the ``migrate_pages`` send for every chunk
        still short of coverage. Possible only while the prefill-side
        source pages survive (they are retained through MIGRATING for
        exactly this) and every missing page belongs to a chunk that WAS
        sent — an unsent chunk or freed sources cannot be retried, the
        caller moves straight down the ladder. Returns True when a
        re-send was actually issued."""
        rid = req.rid
        if not self.alloc_p.holds(rid):
            return False
        incomplete = self.channel.ledger.incomplete_chunks(rid)
        if not incomplete:
            # complete per-chunk coverage yet an uncovered needed page:
            # some chunk was never sent at all — re-sending fixes nothing
            return False
        src_owned = set(self.alloc_p.pages_of(rid))
        for _, src_ids, _ in incomplete:
            if not src_ids or not set(src_ids) <= src_owned:
                return False
        for ci, src_ids, dst_ids in incomplete:
            self.pool_k, self.pool_v = self.channel.send_chunk(
                rid, ci, list(src_ids), list(dst_ids),
                self.pool_k, self.pool_v)
            self._jlog("migrate", rid=rid, chunk=ci, pages=len(src_ids),
                       attempt=self.channel._attempt.get((rid, ci), 0),
                       retry=True)
        req.retries += 1
        self.metrics_decode.inc("retries")
        return True

    def _degrade_or_fail(self, slot: int, req: Request,
                         exc: Exception) -> None:
        """Rung 2 vs the terminal: local re-prefill while the degradation
        budget and capability allow, typed per-request failure after."""
        if (self.allow_degradation
                and req.degradations < self.max_degradations):
            self._degrade(slot, req)
        else:
            self._fail_decode(slot, req, exc)

    def _degrade(self, slot: int, req: Request) -> None:
        """Rung 2: decode-local re-prefill (the PR-6 preemption fallback
        run IN PLACE). The request keeps its decode slot and its decode-
        side page reservation; the prompt KV is recomputed by the same
        compiled chunk program with real inputs in the DECODE_ROLE row
        (``_dispatch_chunks``), so the possibly-dead peer is out of the
        loop entirely. All migrated coverage is voided — the locally
        computed pages are the only ones trusted from here on."""
        rid = req.rid
        req.degradations += 1
        self.metrics_decode.inc("degradations")
        self.channel.ledger.reset(rid)
        self._recovery.pop(rid, None)
        self._wait_steps.pop(rid, None)
        self._poisoned.pop(rid, None)
        if self.alloc_p.holds(rid):
            self.alloc_p.free_seq(rid)   # source copies are useless now
        req.state = RequestState.PREFILLING
        req.prefill_cursor = 0
        self._local_prefill.add(rid)
        self._park(slot)

    def _fail_decode(self, slot: int, req: Request, exc: Exception) -> None:
        """The ladder's terminal: THIS request fails, typed, with the
        ledger dump riding on ``exc`` — the engine and every other
        request keep running (per-request failure domain)."""
        rid = req.rid
        self.sched_d.remove(slot)
        req.state = RequestState.FAILED
        req.failure = exc
        if self.alloc_p.holds(rid):
            self.alloc_p.free_seq(rid)
        self.alloc_d.free_seq(rid)
        self.channel.ledger.reset(rid)
        self.channel.forget(rid)
        self._recovery.pop(rid, None)
        self._wait_steps.pop(rid, None)
        self._poisoned.pop(rid, None)
        self._local_prefill.discard(rid)
        del self._dslot[rid]
        self._park(slot)
        self._failed.append(req)
        self.metrics_decode.inc("failed_requests")
        self._jlog("fail", rid=rid, error_type=type(exc).__name__,
                   reason=str(exc).splitlines()[0])

    def _poison(self, slot: int, req: Request, exc: Exception) -> None:
        """A protocol error surfaced while the request still sits on the
        PREFILL worker: void all coverage now; the ladder's degrade/fail
        decision runs when (if) the request reaches a decode slot —
        unless degradation is impossible, in which case it fails right
        here rather than limping through a doomed migration."""
        rid = req.rid
        self.channel.ledger.reset(rid)
        if (self.allow_degradation
                and req.degradations < self.max_degradations):
            self._poisoned[rid] = exc
            return
        self.sched_p.remove(slot)
        req.state = RequestState.FAILED
        req.failure = exc
        if self.alloc_p.holds(rid):
            self.alloc_p.free_seq(rid)
        if self.alloc_d.holds(rid):
            self.alloc_d.free_seq(rid)
        self.channel.forget(rid)
        self._failed.append(req)
        self.metrics_decode.inc("failed_requests")
        self._jlog("fail", rid=rid, error_type=type(exc).__name__,
                   reason=str(exc).splitlines()[0])

    def _finish_decode(self, slot: int) -> None:
        req = self.sched_d.finish(slot)
        self.alloc_d.free_seq(req.rid)
        if self.alloc_p.holds(req.rid):
            self.alloc_p.free_seq(req.rid)
        self.channel.ledger.reset(req.rid)
        self.channel.forget(req.rid)
        self._recovery.pop(req.rid, None)
        self._wait_steps.pop(req.rid, None)
        self._poisoned.pop(req.rid, None)
        self._local_prefill.discard(req.rid)
        del self._dslot[req.rid]
        req.finish_step = self._steps
        self._park(slot)
        self._finished.append(req)
        self.metrics_decode.inc("requests_finished")
        self.metrics_decode.inc_class("requests_finished", class_label(req))
        # finished tokens ride the journal so post-checkpoint finishes
        # survive a crash without re-running the request; the terminal
        # metadata rides along so the restored record stays faithful
        self._jlog("finish", rid=req.rid, tokens=list(req.generated),
                   submit_step=req.submit_step,
                   first_token_step=req.first_token_step,
                   preemptions=req.preemptions)

    def _preempt_decode(self, slot: int) -> None:
        """Decode-side eviction loses the migrated KV with the pages: the
        victim restarts as a fresh prefill (FRONT of the prefill queue) —
        determinism regenerates identical tokens. ``remove`` (not
        ``evict``): the requeue target is the PEER scheduler. A MIGRATING
        victim also drops its retained prefill-side retry source and any
        in-flight recovery state; a locally-re-prefilling victim rejoins
        the normal remote pipeline."""
        req = self.sched_d.remove(slot)
        req.state = RequestState.QUEUED
        req.preemptions += 1
        req.generated.clear()
        req.prefill_cursor = 0
        req.first_token = None
        req.cache_hit_tokens = 0
        self.alloc_d.free_seq(req.rid)
        if self.alloc_p.holds(req.rid):
            self.alloc_p.free_seq(req.rid)
        self.channel.ledger.reset(req.rid)
        self._recovery.pop(req.rid, None)
        self._wait_steps.pop(req.rid, None)
        self._poisoned.pop(req.rid, None)
        self._local_prefill.discard(req.rid)
        del self._dslot[req.rid]
        self.sched_p.submit(req, front=True)
        self._park(slot)
        self.metrics_decode.inc("preemptions")
        self._jlog("preempt", rid=req.rid, slot=slot, worker="decode")

    def _park(self, slot: int) -> None:
        self._token[slot] = 0
        self._pos[slot] = 0
        self._bt[slot] = 0
        self._dirty = True

    # -- one driver iteration ---------------------------------------------
    @property
    def idle(self) -> bool:
        return (self.sched_p.idle and not self._handoff
                and all(s is None for s in self.sched_d.slots))

    def step(self) -> bool:
        """One step of BOTH workers. Thin wrapper (ISSUE 9): TTL expiry
        sweep before the iteration, checkpoint cadence after a productive
        one — mirroring ``ServingEngine.step``."""
        self.sched_p.tick(self._steps)
        self._expire_queued()
        progressed = self._step_impl()
        self.metrics.counters["quota_throttled"] = \
            self.sched_p.quota_throttled
        if progressed:
            self._maybe_checkpoint()
        return progressed

    def _expire_queued(self) -> None:
        for req in self.sched_p.expire(self._steps):
            ttl = self._ttl_for(req)
            req.failure = TtlExpired(
                f"request {req.rid} (class {req.cls!r}) queued past its "
                f"TTL ({ttl} steps from step {req.submit_step}) "
                "without admission")
            self._rejected.append(req)
            self.metrics.inc("expirations")
            self.metrics.inc_class("expirations", class_label(req))
            self._jlog("expire", rid=req.rid, reason=str(req.failure),
                       tenant=req.tenant, cls=req.cls)

    def _step_impl(self) -> bool:
        """One step of BOTH workers (single-driver SPMD: each device
        program below is entered by both roles). Returns False when fully
        idle."""
        if self.idle:
            return False

        # ---- prefill worker: admissions + ≤1 chunk + migration ----------
        while True:
            adm = self.sched_p.admissible(self._can_hold)
            if adm is None:
                break
            self._admit_prefill(*adm)
        ptoks = self._dispatch_chunks()
        self.metrics.observe("step_prefill_tokens", ptoks)

        # ---- decode worker: seating, patching, gated admission ----------
        t_d = time.perf_counter()
        # deliver any fault-delayed landed reports BEFORE gating, so a
        # late signal can still admit this step; a report that arrives
        # poisoned (over-signal) voids its request's coverage instead of
        # crashing the engine — the ladder decides its fate at seat time
        for rid, exc in self.channel.tick(self._steps):
            self._poisoned.setdefault(rid, exc)
        self._seat_decode_slots()
        self._patch_and_admit()

        limits = np.zeros(self.num_slots, np.int32)
        for slot in range(self.num_slots):
            req = self.sched_d.slots[slot]
            if req is None or req.state is not RequestState.ACTIVE:
                continue
            pos = int(self._pos[slot])
            while not self.alloc_d.ensure(req.rid, pos + 1):
                victim = self.sched_d.pick_victim(exclude_slot=slot)
                if victim is None:
                    raise RuntimeError(
                        f"decode KV pool too small: request {req.rid} "
                        "needs a page with no preemptible peer left")
                self._preempt_decode(victim)
            want = min(self.decode_horizon, req.remaining)
            lim = 1
            while lim < want and self.alloc_d.ensure(req.rid, pos + lim + 1):
                lim += 1
            limits[slot] = lim
            row = np.asarray(self.alloc_d.block_table_row(
                req.rid, self.pages_per_seq), np.int32)
            if not np.array_equal(row, self._bt[slot]):
                self._bt[slot] = row
                self._dirty = True
        for slot in range(self.num_slots):
            r = self.sched_d.slots[slot]
            if r is None or r.state is not RequestState.ACTIVE:
                limits[slot] = 0
        # the decode worker NEVER runs prefill: its per-step stall is pure
        # control-plane work, independent of any peer prompt length — and
        # its step_prefill_tokens is identically 0 (both test-pinned)
        self.metrics_decode.observe("decode_stall_s",
                                    time.perf_counter() - t_d)
        self.metrics_decode.observe("step_prefill_tokens", 0)

        active = [(s, r) for s, r in self.sched_d.active
                  if r.state is RequestState.ACTIVE]
        if not active:
            # prefill chunks / inflight migrations still progressed
            self._steps += 1
            return True

        if self._dirty:
            self._token_dev = self._up(np.stack([self._z_row, self._token]))
            self._pos_dev = self._up(np.stack([self._z_row, self._pos]))
            self._bt_dev = self._up(np.stack([self._z_bt, self._bt]))
            self._dirty = False
            self.metrics_decode.inc("host_syncs")

        lim2 = np.zeros((2, self.num_slots), np.int32)
        lim2[DECODE_ROLE] = limits
        t_disp = time.perf_counter()
        (toks, self._token_dev, self._pos_dev,
         self.pool_k, self.pool_v) = self._dec_step(
            self.params, self._token_dev, self._pos_dev,
            self.pool_k, self.pool_v, self._bt_dev, jnp.asarray(lim2))
        slab = np.asarray(toks)[DECODE_ROLE]           # [K, B]
        t_done = time.perf_counter()

        self._steps += 1
        self.metrics_decode.inc("dispatches")
        self.metrics_decode.inc("decode_steps", int(limits.max()))
        self.metrics_decode.observe("queue_depth", len(self._handoff))
        self.metrics_decode.observe("active_slots", len(active))

        n_tokens = 0
        emitted_by_slot: dict[int, int] = {}
        for slot, req in active:
            emitted = 0
            for i in range(int(limits[slot])):
                req.generated.append(int(slab[i, slot]))
                emitted += 1
                self.metrics_decode.inc("tokens_generated")
                if req.done:
                    break
            self._token[slot] = slab[emitted - 1, slot]
            self._pos[slot] += emitted
            n_tokens += emitted
            emitted_by_slot[slot] = emitted
            if req.done:
                self._finish_decode(slot)

        dev_dt = t_done - t_disp
        host_dt = (t_disp - t_d) + (time.perf_counter() - t_done)
        self.metrics_decode.observe("step_device_s", dev_dt)
        self.metrics_decode.observe("step_host_s", host_dt)
        per_tok = (dev_dt + host_dt) / max(n_tokens, 1)
        for _ in range(n_tokens):
            self.metrics_decode.observe("tok_latency_s", per_tok)
        for slot, req in active:
            label = class_label(req)
            if label is not None:
                for _ in range(emitted_by_slot.get(slot, 0)):
                    self.metrics_decode.observe_class("itl_s", label, per_tok)
        return True

    def run(self, max_steps: int | None = None,
            arrivals=None, recover=None) -> dict[int, list[int]]:
        """Drive ``step()`` until idle (or ``max_steps``); same contract
        as ``ServingEngine.run`` — returns {rid: tokens} for FINISHED
        requests only (``failed`` exposes the casualties).

        ``recover`` (ISSUE 9): truthy = restore from the journal's last
        checkpoint + suffix replay before stepping. A decode-worker
        restart re-admits every in-flight (including mid-migration)
        request through the rebuilt ledger: the request re-prefills and
        re-migrates deterministically, nothing is failed for having been
        half-migrated at the crash.

        A global progress WATCHDOG (ISSUE 7) backstops the per-request
        ladder: if no externally visible progress marker moves for
        ``_stall_steps`` consecutive non-idle steps — longer than any
        legitimate full-ladder wait — the engine raises
        ``EngineStallError`` with a state dump. Chaos runs assert this
        never fires: every fault path must END somewhere (handoff,
        degradation, or typed failure), not spin."""
        if recover:
            assert self.journal is not None, "recover= needs a journal"
            ck = recover if isinstance(recover, ckpt_mod.Checkpoint) \
                else ckpt_mod.latest(self.journal)
            ckpt_mod.restore(self, ck, self.journal)
        pending = deque(arrivals or [])
        i = 0
        marker, since = self._progress_marker(), 0
        while max_steps is None or i < max_steps:
            while pending and pending[0][0] <= i:
                item = pending.popleft()
                self.submit(item[1], item[2],
                            tenant=item[3] if len(item) > 3 else None,
                            cls=item[4] if len(item) > 4 else None)
            if not self.step() and not pending:
                break
            i += 1
            plan = self._fault_plan if self._fault_plan is not None \
                else faults.active_plan()
            if plan is not None and plan.crash(self._steps,
                                               self._incarnation):
                self.metrics.inc("faults_injected")
                raise faults.InjectedCrash(
                    f"injected crash at step {self._steps} "
                    f"(incarnation {self._incarnation})")
            m = self._progress_marker()
            if m != marker:
                marker, since = m, 0
            else:
                since += 1
                if since >= self._stall_steps and not self.idle:
                    raise EngineStallError(self._stall_report(since)
                                           + self._postmortem())
        return {req.rid: list(req.generated) for req in self._finished}

    def _progress_marker(self) -> tuple:
        """Anything that moves when the engine is making real progress:
        tokens, chunks, migrations, and every rung of the ladder
        (retries/degradations/failures count as progress — they bound a
        wait, they don't extend it)."""
        c, d = self.metrics.counters, self.metrics_decode.counters
        return (c["prefill_chunks"], c["pages_migrated"], c["migrate_chunks"],
                c["restores"], c["expirations"],
                d["tokens_generated"], d["handoffs"], d["retries"],
                d["degradations"], d["failed_requests"], d["preemptions"],
                len(self._finished), len(self._failed),
                self.metrics_decode.hist["degraded_prefill_tokens"].count)

    def _stall_report(self, since: int) -> str:
        rows = []
        for name, sched in (("prefill", self.sched_p),
                            ("decode", self.sched_d)):
            for slot, req in sched.active:
                rows.append(
                    f"{name}[{slot}]: rid={req.rid} {req.state.value} "
                    f"cursor={req.prefill_cursor} retries={req.retries} "
                    f"degradations={req.degradations}")
        return (f"engine made no progress for {since} steps "
                f"(stall deadline {self._stall_steps}, step {self._steps}); "
                f"queues: prefill={self.sched_p.queue_depth} "
                f"handoff={len(self._handoff)} "
                f"local_prefill={sorted(self._local_prefill)} "
                f"recovering={sorted(self._recovery)} "
                f"poisoned={sorted(self._poisoned)}; slots: "
                + ("; ".join(rows) if rows else "<none>"))

    # -- crash consistency (ISSUE 9) --------------------------------------
    def control_digest(self) -> int:
        """FNV-1a digest over BOTH workers' control planes (each role's
        allocator + scheduler) — the per-event stamp journal entries
        carry."""
        return _fnv1a(0x811C9DC5, self.alloc_p.digest(),
                      self.sched_p.digest(), self.alloc_d.digest(),
                      self.sched_d.digest())

    def _jlog(self, kind: str, **payload) -> None:
        if self.journal is None or self._journal_muted:
            return
        self.journal.append(kind, self._steps, self.control_digest(),
                            **payload)

    def _maybe_checkpoint(self) -> None:
        if (self.journal is None or not self.checkpoint_every
                or self._steps == 0
                or self._steps % self.checkpoint_every
                or self._steps == self._last_ckpt_step):
            return
        self.checkpoint()

    def checkpoint(self) -> "ckpt_mod.Checkpoint":
        """Capture a control-plane snapshot of both workers into the
        journal. Host-only — no device work, no KV bytes, no migration
        state beyond the ledger audit artifact."""
        assert self.journal is not None, "checkpoint() needs a journal"
        t0 = time.perf_counter()
        ck = ckpt_mod.capture(self)
        self.journal.record_checkpoint(ck.step, ck.digest, ck.state,
                                       ck.journal_seq)
        self._last_ckpt_step = self._steps
        self.metrics.inc("checkpoints")
        self.metrics.observe("checkpoint_s", time.perf_counter() - t0)
        return ck

    def _capture_state(self) -> dict:
        """JSON-able snapshot of BOTH workers' control planes. Live
        requests are recorded in deterministic order — decode seats by
        admission ticket, the handoff queue, prefill seats by ticket,
        then the prefill queue — and every one of them restores as a
        fresh QUEUED prefill: restart-from-prompt re-earns pages AND
        re-migrates, so no migration state needs to survive."""
        live: list[Request] = []
        seen: set[int] = set()

        def add(r: Request | None) -> None:
            if r is not None and r.rid not in seen:
                seen.add(r.rid)
                live.append(r)

        for _, r in sorted(((r.admitted_seq, r)
                            for _, r in self.sched_d.active),
                           key=lambda t: t[0]):
            add(r)
        for r in self._handoff:
            add(r)
        for _, r in sorted(((r.admitted_seq, r)
                            for _, r in self.sched_p.active),
                           key=lambda t: t[0]):
            add(r)
        for r in self.sched_p.queue:
            add(r)
        return {
            "engine": "disagg",
            "step": self._steps,
            "next_rid": self._next_rid,
            "admit_ticket_p": self.sched_p._admit_ticket,
            "admit_ticket_d": self.sched_d._admit_ticket,
            "pool_p": self.alloc_p.snapshot(),
            "pool_p_digest": self.alloc_p.digest(),
            "pool_d": self.alloc_d.snapshot(),
            "pool_d_digest": self.alloc_d.digest(),
            "prefix_index": (None if self.prefix_cache is None
                             else self.prefix_cache.snapshot()),
            "prefix_digest": (None if self.prefix_cache is None
                              else self.prefix_cache.digest()),
            "live": [ckpt_mod.snapshot_request(r) for r in live],
            "finished": [ckpt_mod.snapshot_finished(r)
                         for r in self._finished],
            "failed": [{"rid": r.rid,
                        "error_type": type(r.failure).__name__,
                        "reason": str(r.failure).splitlines()[0]}
                       for r in self._failed],
            "rejected": [{"rid": r.rid, "kind": "expire"
                          if isinstance(r.failure, TtlExpired) else "reject",
                          "reason": str(r.failure), "tenant": r.tenant,
                          "cls": r.cls} for r in self._rejected],
            "policy": self.sched_p.policy_state(),
            "counters": dict(self.metrics.counters),
            "counters_decode": dict(self.metrics_decode.counters),
        }

    def _restore_state(self, state: dict | None) -> None:
        """Rebuild both workers' host control state (None = from nothing).
        The symmetric device pools are left untouched: every live request
        re-prefills and RE-MIGRATES from scratch, rewriting its pages'
        bytes before any decode read, so stale device KV is unreachable.
        The signal ledger and the channel's attempt/delay state are
        cleared — coverage must be re-earned by fresh signals, never
        trusted across a restart."""
        self.alloc_p = KVPagePool(self.alloc_p.num_pages, self.page_size,
                                  reserved=1)
        self.alloc_d = KVPagePool(self.alloc_d.num_pages, self.page_size,
                                  reserved=1)
        if self.prefix_cache is not None:
            # the cache restarts EMPTY on the fresh ledger: cached KV is
            # device state, and restore's contract is that every page's
            # bytes are re-earned by re-prefill before any read
            self.prefix_cache = PrefixCache(self.alloc_p, self.page_size)
        self.sched_p = ContinuousBatchingScheduler(
            self.sched_p.num_slots, queue_cap=self.sched_p.queue_cap,
            policy=self.sched_p.policy)
        self.sched_d = ContinuousBatchingScheduler(self.num_slots)
        self._handoff.clear()
        self._dslot.clear()
        self._wait_steps.clear()
        self._recovery.clear()
        self._poisoned.clear()
        self._local_prefill.clear()
        self._finished = []
        self._failed = []
        self._rejected = []
        self.channel.ledger = ChunkSignalLedger()
        self.channel._attempt.clear()
        self.channel._delayed.clear()
        for slot in range(self.num_slots):
            self._park(slot)
        self._token_dev = self._up(np.stack([self._z_row, self._token]))
        self._pos_dev = self._up(np.stack([self._z_row, self._pos]))
        self._bt_dev = self._up(np.stack([self._z_bt, self._bt]))
        self._dirty = False
        if state is None:
            return
        ckpt_mod.audit_pool_snapshot(
            state["pool_p"], state["pool_p_digest"],
            self.alloc_p.num_pages, self.page_size, 1)
        ckpt_mod.audit_pool_snapshot(
            state["pool_d"], state["pool_d_digest"],
            self.alloc_d.num_pages, self.page_size, 1)
        if state.get("prefix_index") is not None:
            ckpt_mod.audit_prefix_snapshot(state["prefix_index"],
                                           state["prefix_digest"])
        self._steps = state["step"]
        self._next_rid = state["next_rid"]
        self.sched_p._admit_ticket = state["admit_ticket_p"]
        self.sched_d._admit_ticket = state["admit_ticket_d"]
        for snap in state["live"]:
            req = ckpt_mod.rebuild_request(snap)
            req.submit_time = time.perf_counter()
            ttl = self._ttl_for(req)
            if ttl is not None:
                req.deadline = Deadline(ttl, req.submit_step)
            self.sched_p.submit(req)
        # WFQ/bucket books restore AFTER the requeues: submit()'s idle-
        # class vfloor snap ran against zeroed counters above, and the
        # checkpoint values now overwrite them (order-dependent)
        self.sched_p.restore_policy_state(state.get("policy"))
        for f in state["finished"]:
            self._restore_finished(f["rid"], f["tokens"], meta=f)
        for f in state["failed"]:
            self._restore_terminal(f["rid"], "fail", f["reason"],
                                   f.get("error_type"))
        for f in state["rejected"]:
            self._restore_terminal(f["rid"], f["kind"], f["reason"])

    _ERROR_TYPES = {
        "MigrationSignalTimeout": MigrationSignalTimeout,
        "SignalProtocolError": SignalProtocolError,
        "AdmissionRejected": AdmissionRejected,
        "TtlExpired": TtlExpired,
    }

    def _restore_finished(self, rid: int, tokens: list[int],
                          meta: dict | None = None) -> None:
        req = self._pop_queued(rid)
        if req is None:
            prompt = tuple((meta or {}).get("prompt", (0,)))
            req = Request(rid=rid, prompt=prompt,
                          max_new_tokens=len(tokens), eos_token=self.eos_id)
        req.state = RequestState.FINISHED
        req.generated = list(tokens)
        for k in ("submit_step", "first_token_step", "preemptions"):
            if meta is not None and k in meta:
                setattr(req, k, meta[k])
        self._finished.append(req)

    def _restore_terminal(self, rid: int, kind: str, reason: str,
                          error_type: str | None = None) -> None:
        req = self._pop_queued(rid)
        if req is None:
            req = Request(rid=rid, prompt=(0,), max_new_tokens=1,
                          eos_token=self.eos_id)
        if kind == "fail":
            req.state = RequestState.FAILED
            cls = self._ERROR_TYPES.get(error_type or "", RuntimeError)
            req.failure = cls(reason)
            self._failed.append(req)
        else:
            req.state = RequestState.REJECTED
            req.failure = (TtlExpired(reason) if kind == "expire"
                           else AdmissionRejected(reason))
            self._rejected.append(req)

    def _pop_queued(self, rid: int) -> Request | None:
        for r in self.sched_p.queue:
            if r.rid == rid:
                self.sched_p.queue.remove(r)
                return r
        return None

    def _postmortem(self) -> str:
        counters = {k: v for k, v in self.metrics.counters.items() if v}
        counters_d = {k: v for k, v in self.metrics_decode.counters.items()
                      if v}
        tail = (self.journal.format_tail(8) if self.journal is not None
                else "  <no journal attached>")
        return ("\ncounters: " + json.dumps(counters)
                + "\ncounters_decode: " + json.dumps(counters_d)
                + "\njournal tail:\n" + tail)

    @property
    def failed(self) -> list[Request]:
        """Requests the recovery ladder could not save plus overload
        terminals (REJECTED), in failure order; each carries its typed
        reason in ``req.failure``."""
        return list(self._failed) + list(self._rejected)

    # -- introspection ----------------------------------------------------
    @property
    def compile_stats(self) -> dict:
        """Each role compiles a BOUNDED program set: one chunk program
        (prefill worker, every prompt length), one decode program, one
        migration program (every chunk size ≤ pmax) — no per-prompt-length
        recompiles anywhere (test-pinned)."""
        stats = {
            "prefill_chunk_compiles": programs.compiles(
                self._chunk_step,
                1 if self.metrics.counters["prefill_chunks"] else 0),
            "decode_compiles": programs.compiles(
                self._dec_step, 1 if self._steps else 0),
            "migrate_compiles": programs.compiles(
                self._migrate,
                1 if self.metrics.counters["migrate_chunks"] else 0),
        }
        if self._aot_artifact is not None:
            from triton_dist_tpu.aot.artifact import LoadedProgram
            stats["aot_programs"] = sum(
                isinstance(f, LoadedProgram)
                for f in (self._chunk_step, self._dec_step, self._migrate))
        return stats


__all__ = ["DisaggServingEngine", "PageMigrationChannel",
           "ChunkSignalLedger", "MigrationSignalTimeout",
           "SignalProtocolError", "PREFILL_ROLE", "DECODE_ROLE"]
