"""Disaggregated prefill × sharded decode (ISSUE 12 tentpole, rung 1):
``DisaggServingEngine``'s decode role IS a ``ShardedServingEngine``.

The production topology the ROADMAP names — a prefill fleet feeding a
sharded decode fleet — composes the two serving subsystems that used to
refuse each other:

- the **decode fleet** is an unmodified :class:`ShardedServingEngine` on
  a TP/SP/EP mesh: SP-sharded page pool (``page_pool_pspec``), TP
  projections, EP-MoE FFN through the overlap library, replicated-
  decision digest guard — everything PR 8 pinned.
- the **prefill fleet** runs on the SAME mesh with its OWN pool + ledger
  + scheduler, reusing the decode engine's compiled chunk program (the
  pools are built with identical shapes and the identical committed SP
  sharding, so pjit serves both from ONE executable —
  ``prefill_chunk_compiles == 1`` stays pinned).
- the **handoff** is the disagg signal protocol verbatim
  (``PageMigrationChannel`` + ``ChunkSignalLedger`` + the ISSUE 7
  recovery ladder), over a different transport tier: the one-sided
  Pallas ``migrate_pages`` kernel moves pages between two ranks of ONE
  mesh axis, while here the two pools live on the SAME multi-axis mesh
  as differently-owned arrays — the DCN tier of the reference's
  hierarchy, where a host-driven copy is the idiomatic primitive. ONE
  jitted gather/scatter program (``_xmig``) copies the chunk's pages
  bit-exactly and reports the landed count + echoed attempt tag exactly
  like the kernel's consumer-side report, so the ledger, the signal
  gate, the deadline/retry/degrade ladder and the chaos hooks all run
  UNCHANGED on top of it.

The unified pool contract (kv_pool.py) is what makes the composition
sound: both ledgers carry ``sp_ranks``, so ``check_migratable`` refuses
SP padding ids on either side and ``landed_row`` exposes only real
signal-covered pages — a migration can never land KV in a padding slot
no block table can reach.

Bit-identity chain (tests/test_cluster.py): the sharded engine's tokens
are bitwise mesh-size-independent (PR 8), migration is an exact page
copy, and the first token is argmaxed by the same chunk program — so the
composed engine's per-request traces replay the 1x1x1
``ShardedServingEngine`` golden exactly, at every mesh size, preemptions
and recovery rungs included.

Degradation differs from two-worker disagg in ONE honest way: the
decode fleet natively runs chunked prefill, so a degraded request is
simply requeued (front) into the decode engine's own admission queue —
it keeps its decode-side page reservation and re-prefills through the
decode engine's ordinary chunk path. The decode panel's
``step_prefill_tokens == 0`` isolation invariant therefore holds for
fault-free runs only (same caveat as disagg's degraded rung).
"""

from __future__ import annotations

import json
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from triton_dist_tpu.models.llama import init_page_pool, require_config
from triton_dist_tpu.models.moe import MoEConfig
from triton_dist_tpu.ops.allgather_gemm import GemmConfig
from triton_dist_tpu.serving import checkpoint as ckpt_mod
from triton_dist_tpu.serving import programs
from triton_dist_tpu.serving.deadline import (Backoff, Deadline,
                                              EngineStallError)
from triton_dist_tpu.serving.disagg import (DECODE_ROLE, ChunkSignalLedger,
                                            MigrationSignalTimeout,
                                            PageMigrationChannel,
                                            SignalProtocolError)
from triton_dist_tpu.serving.engine import (class_label, mark_prefill_start,
                                            record_first_token)
from triton_dist_tpu.serving.journal import ControlJournal
from triton_dist_tpu.serving.kv_pool import (KVPagePool, _fnv1a,
                                             shard_pool_arrays)
from triton_dist_tpu.serving.metrics import ServingMetrics
from triton_dist_tpu.serving.prefix_cache import PrefixCache
from triton_dist_tpu.serving.scheduler import (AdmissionRejected,
                                               ContinuousBatchingScheduler,
                                               Request, RequestState,
                                               SLOPolicy, TtlExpired)
from triton_dist_tpu.serving.sharded import ShardedServingEngine
from triton_dist_tpu.shmem import faults
from triton_dist_tpu.shmem.context import ShmemContext


class DisaggShardedEngine:
    """Disaggregated serving with a :class:`ShardedServingEngine` decode
    fleet (module docstring). Constructor knobs are the union of the
    disagg ladder knobs and the sharded mesh knobs; a chunk is both the
    migration unit and the sharded engine's prefill dispatch.

    Request lifecycle mirrors disagg: QUEUED (prefill queue) →
    PREFILLING (prefill fleet seat; decode pages reserved at admission;
    chunks run and migrate) → MIGRATING (seated on the decode fleet,
    signal-gated) → ACTIVE (fully decode-owned — from here the sharded
    engine runs it natively, preemptions and all) → FINISHED, with the
    ladder's degrade rung requeueing into the decode engine's own
    chunked-prefill admission and FAILED only at the bottom.
    """

    def __init__(self, params: dict, cfg: MoEConfig, ctx: ShmemContext,
                 num_slots: int = 4, num_prefill_slots: int = 2,
                 page_size: int = 16, num_pages: int = 64,
                 pages_per_seq: int = 8,
                 metrics: ServingMetrics | None = None,
                 metrics_decode: ServingMetrics | None = None,
                 decode_horizon: int = 1, eos_id: int | None = None,
                 prefill_chunk: int = 16,
                 signal_deadline_steps: int = 8, max_retries: int = 3,
                 allow_degradation: bool = True, max_degradations: int = 1,
                 stall_deadline_steps: int | None = None,
                 wall_deadline_s: float | None = None,
                 wire_dtype: str | None = "auto", tp_impl: str = "xla",
                 tp_cfg: GemmConfig | None = None, moe_block_m: int = 128,
                 digest_every: int = 1,
                 journal: ControlJournal | None = None,
                 checkpoint_every: int | None = None,
                 queue_cap: int | None = None,
                 ttl_steps: int | None = None,
                 fault_plan: "faults.FaultPlan | None" = None,
                 prefix_cache: bool = False,
                 slo: SLOPolicy | None = None,
                 artifact=None, artifact_key: str | None = None):
        require_config(cfg, MoEConfig, type(self).__name__)
        assert signal_deadline_steps >= 1 and max_retries >= 0
        assert checkpoint_every is None or journal is not None, (
            "checkpoint_every needs a journal to record into")
        self.ctx = ctx
        self.params = params
        self.moe_cfg = cfg
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
        ladder = signal_deadline_steps * (2 ** (max_retries + 1) - 1)
        self._stall_steps = (stall_deadline_steps if stall_deadline_steps
                             is not None else max(256, 4 * ladder))
        self.metrics = metrics or ServingMetrics()
        self.metrics_decode = metrics_decode or ServingMetrics()

        # -- the decode fleet: an unmodified sharded engine ---------------
        # journal/TTL/queue-cap stay None — the COMPOSED engine owns the
        # crash-consistency and overload surfaces (one journal, one intake
        # queue); the decode engine's digest guard runs at full cadence.
        # AOT artifact (ISSUE 15): the composition's programs live under
        # ONE key — the inner decode engine seeds chunk/decode from it,
        # and the xmig copy program is seeded below.
        self._aot_artifact = artifact
        self._aot_key = artifact_key or (
            f"disagg_sharded:{ctx.axis_size('tp')}x"
            f"{ctx.axis_size('sp')}x{ctx.axis_size('ep')}")
        self.decode = ShardedServingEngine(
            params, cfg, ctx, num_slots=num_slots, page_size=page_size,
            num_pages=num_pages, pages_per_seq=pages_per_seq,
            metrics=self.metrics_decode, decode_horizon=decode_horizon,
            eos_id=eos_id, prefill_chunk=prefill_chunk,
            wire_dtype=wire_dtype, tp_impl=tp_impl, tp_cfg=tp_cfg,
            moe_block_m=moe_block_m, digest_every=digest_every,
            prefix_cache=prefix_cache,
            artifact=artifact, artifact_key=self._aot_key)
        self.decode._preempt_hook = self._on_decode_preempt
        # ONE committed weight copy: the prefill fleet dispatches the
        # decode engine's chunk executable, so it must present the same
        # (mesh-committed) arrays or pjit would compile a second signature
        self.params = self.decode.params
        self.mesh_desc = self.decode.mesh_desc
        self.wire_dtype = self.decode.wire_dtype
        n_sp = ctx.axis_size("sp")

        # -- the prefill fleet: own pool/ledger/scheduler on the SAME mesh,
        # arrays shaped + sharded IDENTICALLY to the decode pool so the
        # decode engine's compiled chunk program serves both (one pjit
        # executable — compile_stats pins it)
        self.alloc_p = KVPagePool(num_pages + 1, page_size, reserved=1,
                                  sp_ranks=n_sp)
        self.pool_p = shard_pool_arrays(
            init_page_pool(cfg.base, num_pages + 1, page_size), n_sp,
            self.decode._pool_out_sharding)
        # SLO policy (ISSUE 14) on the composed intake only — the decode
        # fleet's scheduler stays policy-free (class-aware victim ordering
        # reads the shed_level stamp each request carries)
        self.slo = slo
        self.sched_p = ContinuousBatchingScheduler(num_prefill_slots,
                                                   queue_cap=queue_cap,
                                                   policy=slo)
        # prefix cache (ISSUE 13), disagg-shaped: one index per fleet.
        # The PREFILL-fleet cache adopts solely-owned pages and skips the
        # chunk compute inside the hit (every page still migrates); the
        # decode fleet's own cache — constructed above — serves the
        # degradation rung's local re-prefills.
        self.prefix_cache = (PrefixCache(self.alloc_p, page_size)
                             if prefix_cache else None)

        # -- the DCN-tier migration program: one jitted gather/scatter
        # copying up to pmax (src → dst) pages between the two pools, with
        # the landed-count + echoed-tag report the channel/ledger protocol
        # expects from the kernel path. Masked lanes gather dst page 0's
        # own bytes and scatter them back — an identity write on the
        # scratch page, never a live one.
        pmax = max(prefill_chunk // page_size + 2, pages_per_seq)

        def xmig(src, dst, n, tag, skp, svp, dkp, dvp):
            m = jnp.arange(pmax, dtype=jnp.int32) < n[0]
            gsrc = jnp.where(m, src, 0)
            gdst = jnp.where(m, dst, 0)
            mk = m[None, :, None, None, None]
            pk = jnp.where(mk, skp[:, gsrc], dkp[:, gdst])
            pv = jnp.where(mk, svp[:, gsrc], dvp[:, gdst])
            dkp = dkp.at[:, gdst].set(pk)
            dvp = dvp.at[:, gdst].set(pv)
            landed_row = jnp.concatenate([n, tag])     # [count, echoed tag]
            landed = jnp.stack([landed_row, landed_row])
            return dkp, dvp, landed

        pshard = self.decode._pool_out_sharding
        kw = {"out_shardings": (pshard, pshard, self.decode._rep_sharding)}
        self._xmig = programs.jit(xmig, (6, 7), **kw)
        if artifact is not None:
            # _launch reads self._xmig at call time, so seeding here is
            # enough — no closure rebind needed
            self._xmig = artifact.program(self._aot_key, "xmig")

        # the decode engine linted its own two programs in its constructor;
        # the composition's third program here
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        kp, vp = (jax.ShapeDtypeStruct(a.shape, a.dtype)
                  for a in (self.pool_p["k"], self.pool_p["v"]))
        programs.lint_if_asked({"xmig_pages": (xmig, (
            i32(pmax), i32(pmax), i32(1), i32(1), kp, vp, kp, vp))}, self)

        def _launch(src, dst, n, tag, kp, vp):
            dk, dv, landed = self._xmig(src, dst, n, tag, kp, vp,
                                        self.decode.pool["k"],
                                        self.decode.pool["v"])
            self.decode.pool = {"k": dk, "v": dv}
            return kp, vp, landed       # prefill pool is a read-only source

        self.channel = PageMigrationChannel(
            _launch, pmax, reserved=1, metrics=self.metrics,
            consumer=DECODE_ROLE, plan=fault_plan,
            clock=lambda: self._steps)

        # -- crash consistency + ladder state (disagg-shaped) -------------
        self.journal = journal
        self.checkpoint_every = checkpoint_every
        self.ttl_steps = ttl_steps
        self._fault_plan = fault_plan
        self._journal_muted = False
        self._replaying = False
        self._incarnation = 0
        self._last_ckpt_step = -1
        self._handoff: deque[Request] = deque()   # MIGRATING, no seat yet
        self._dslot: dict[int, int] = {}          # rid -> MIGRATING seat
        self._wait_steps: dict[int, int] = {}
        self._recovery: dict[int, tuple[Deadline, Backoff]] = {}
        self._poisoned: dict[int, Exception] = {}
        self._degraded: dict[int, Request] = {}   # rid -> req, in decode q
        self._finished: list[Request] = []
        self._failed: list[Request] = []
        self._rejected: list[Request] = []
        self._next_rid = 0
        self._steps = 0

    # the decode fleet's ledger/scheduler under the disagg names — the
    # PROPERTY matters: the decode engine's _restore_state replaces the
    # objects, and the composed engine must always see the live ones
    @property
    def alloc_d(self) -> KVPagePool:
        return self.decode.alloc

    @property
    def sched_d(self) -> ContinuousBatchingScheduler:
        return self.decode.sched

    # -- request intake ----------------------------------------------------
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
        if self.sched_p.at_capacity_for(req.cls) and not self._replaying:
            cap = self.sched_p.queue_cap if self.sched_p.at_capacity else \
                self.sched_p.policy.spec(req.cls).queue_cap
            req.state = RequestState.REJECTED
            req.failure = AdmissionRejected(
                f"admission queue full for class {req.cls!r} (cap {cap}) "
                f"— request {rid} rejected")
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

    # -- prefill fleet -----------------------------------------------------
    def _can_hold(self, req: Request) -> bool:
        """Admission needs BOTH pools (disagg semantics): prefill pages to
        compute into and the decode-side reservation fixed at admit."""
        need = -(-len(req.prompt) // self.page_size)
        need_p = need - len(self.alloc_p.pages_of(req.rid))
        need_d = need - len(self.alloc_d.pages_of(req.rid))
        # refcount-0 cached pages count as reclaimable capacity on BOTH
        # fleets: the prefill fleet evicts through its own index, the
        # decode fleet through the sharded engine's (degradation-rung
        # re-prefills populate it) — otherwise a full cached pool would
        # wedge remote admission forever
        avail_p = self.alloc_p.free_pages + (
            self.prefix_cache.evictable if self.prefix_cache else 0)
        avail_d = self.alloc_d.free_pages + (
            self.decode.prefix_cache.evictable
            if self.decode.prefix_cache else 0)
        return avail_p >= max(need_p, 0) and avail_d >= max(need_d, 0)

    def _cache_adopt(self, req: Request) -> None:
        """Disagg-shaped adoption (sole-ownership rule): adopt the
        longest prefix of the hit whose pages are ALL refcount-0, so the
        acquired pages are solely owned and ``check_migratable`` accepts
        them when their chunks migrate."""
        cache = self.prefix_cache
        if (cache is None or req.prefill_cursor > 0
                or self.alloc_p.holds(req.rid)):
            return
        solo = []
        for p in cache.match(req.prompt):
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
        have_d = len(self.alloc_d.pages_of(req.rid))
        if need > have_d:
            self.decode._reclaim(need - have_d)   # no-op when cache off
            got = self.alloc_d.alloc(req.rid, need - have_d)
            assert got is not None, "admissible() guaranteed the pages"
        self.sched_p.activate(slot, req)
        self._jlog("admit", rid=req.rid, slot=slot)
        req.state = RequestState.PREFILLING
        mark_prefill_start(req, self.metrics, self._steps)
        self.metrics.inc("prefills")

    def _dispatch_prefill_chunk(self) -> int:
        """Advance the oldest PREFILLING prefill seat by one chunk through
        the DECODE engine's compiled chunk program (same executable — the
        pools are twins), then migrate whatever the chunk finalized. The
        final chunk flips the request to MIGRATING with its device-
        argmaxed first token on the host control plane; its prefill-side
        pages are RETAINED as the retry source until coverage confirms."""
        slot, req = None, None
        for i, r in enumerate(self.sched_p.slots):
            if (r is not None and r.state is RequestState.PREFILLING
                    and (req is None or r.admitted_seq < req.admitted_seq)):
                slot, req = i, r
        if slot is None:
            return 0
        C = self.prefill_chunk
        sp = len(req.prompt)
        start = req.prefill_cursor
        part = req.prompt[start:start + C]
        # cache-hit fast path (ISSUE 13, disagg semantics): a chunk fully
        # inside the adopted prefix skips the device compute — the pages
        # already hold that KV — but still migrates; the final chunk
        # always computes (fused first-token argmax)
        skip = start + C <= req.cache_hit_tokens and start + C < sp
        tok0 = None
        if not skip:
            toks = np.zeros(C, np.int32)
            toks[:len(part)] = part
            row = np.asarray(self.alloc_p.block_table_row(
                req.rid, self.pages_per_seq), np.int32)
            t0 = time.perf_counter()
            tok_dev, self.pool_p = self.decode._chunk_step(
                self.params, jnp.asarray(toks),
                jnp.asarray(start, jnp.int32),
                jnp.asarray(sp, jnp.int32), self.pool_p, jnp.asarray(row))
            tok0 = int(tok_dev)
            dt = time.perf_counter() - t0
        cursor_new = min(start + C, sp)
        req.prefill_cursor = cursor_new
        if skip:
            self.metrics.inc("prefix_skipped_chunks")
        else:
            self.metrics.inc("prefill_chunks")
            self.metrics.observe("prefill_stall_s", dt)
        self._jlog("chunk", rid=req.rid, cursor=cursor_new)
        try:
            self._migrate_finalized(req, start, cursor_new)
        except SignalProtocolError as e:
            self._poison(slot, req, e)
        if req.state is RequestState.PREFILLING and cursor_new >= sp:
            if self.prefix_cache is not None:
                self.prefix_cache.insert(
                    req.prompt,
                    self.alloc_p.pages_of(req.rid)[:sp // self.page_size])
                if req.first_token_time is None:
                    self.metrics.observe(
                        "ttft_cached_s" if req.cache_hit_tokens
                        else "ttft_cold_s",
                        time.perf_counter() - req.submit_time)
            req.first_token = tok0
            record_first_token(req, self.metrics, self._steps)
            self.metrics.inc("tokens_generated")
            self.metrics.inc("handoffs")
            self.sched_p.remove(slot)
            req.state = RequestState.MIGRATING
            self._jlog("handoff", rid=req.rid)
            if req.rid not in self._dslot:
                self._handoff.append(req)
        return len(part)

    def _migrate_finalized(self, req: Request, start: int,
                           cursor_new: int) -> None:
        """Send exactly the pages this chunk FINALIZED (disagg's cursor
        arithmetic verbatim) over the host-driven copy program. Both
        ledgers' ``check_migratable`` run first — with the unified pool
        contract that refuses scratch, SP padding AND foreign ids on
        either side of the mesh."""
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
        pk, pv = self.channel.send_chunk(
            req.rid, chunk_idx, src, dst,
            self.pool_p["k"], self.pool_p["v"])
        self.pool_p = {"k": pk, "v": pv}
        self._jlog("migrate", rid=req.rid, chunk=chunk_idx,
                   pages=len(src), attempt=self.channel._attempt.get(
                       (req.rid, chunk_idx), 0))

    # -- decode fleet seating + signal-gated admission ---------------------
    def _seat_decode_slots(self) -> None:
        while self._handoff:
            slot = self.sched_d.free_slot()
            if slot is None:
                return
            req = self._handoff.popleft()
            self.sched_d.place(slot, req)
            self._dslot[req.rid] = slot

    def _check_signal_gate(self, slot: int, covered: set[int]) -> None:
        for p in self.decode._bt[slot]:
            p = int(p)
            if p >= self.alloc_d.reserved and p not in covered:
                raise RuntimeError(
                    f"signal-gate violation: decode block table exposes "
                    f"page {p} before its delivery signal fired")

    def _patch_and_admit(self) -> None:
        """Disagg's block-table patching + signal-gated admission, over
        the DECODE ENGINE's slot mirrors. On the ACTIVE flip the request
        becomes fully decode-owned: mirrors set, ``_dslot`` dropped — the
        sharded engine decodes, preempts and finishes it natively from
        here (its evictions re-prefill bit-identically by determinism)."""
        for slot in range(self.num_slots):
            req = self.sched_d.slots[slot]
            if req is None or req.state is not RequestState.MIGRATING:
                continue
            rid = req.rid
            if rid in self._poisoned:
                self._degrade_or_fail(slot, req, self._poisoned.pop(rid))
                continue
            covered = self.channel.ledger.covered(rid)
            row = np.asarray(self.alloc_d.landed_row(
                rid, covered, self.pages_per_seq), np.int32)
            if not np.array_equal(row, self.decode._bt[slot]):
                self.decode._bt[slot] = row
                self.decode._dirty = True
            self._check_signal_gate(slot, covered)
            sp = len(req.prompt)
            need = set(self.alloc_d.pages_of(rid)[:-(-sp // self.page_size)])
            if req.first_token is not None and need <= covered:
                self.metrics_decode.observe(
                    "migrate_wait_steps", self._wait_steps.pop(rid, 0))
                if req.retries:
                    self.metrics_decode.observe(
                        "recovered_ttft_s",
                        time.perf_counter() - req.submit_time)
                self._recovery.pop(rid, None)
                if self.alloc_p.holds(rid):
                    self.alloc_p.free_seq(rid)
                req.state = RequestState.ACTIVE
                req.generated.append(req.first_token)
                self.metrics_decode.inc("handoffs")
                self.decode._token[slot] = req.first_token
                self.decode._pos[slot] = sp
                self.decode._bt[slot] = np.asarray(
                    self.alloc_d.block_table_row(rid, self.pages_per_seq),
                    np.int32)
                self.decode._dirty = True
                del self._dslot[rid]
                if req.done:
                    self.decode._finish(slot)
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
                f"steps (deadline {self.signal_deadline_steps}, "
                f"{backoff.attempt} retry rung(s) spent) for migration "
                f"signals covering pages {missing}; ledger: "
                f"{self.channel.ledger.describe(rid)}. A signal or page "
                "delivery was lost (or a chunk was never sent)."))

    # -- recovery ladder (disagg's, over the composed transport) -----------
    def _retry_migration(self, req: Request) -> bool:
        rid = req.rid
        if not self.alloc_p.holds(rid):
            return False
        incomplete = self.channel.ledger.incomplete_chunks(rid)
        if not incomplete:
            return False
        src_owned = set(self.alloc_p.pages_of(rid))
        for _, src_ids, _ in incomplete:
            if not src_ids or not set(src_ids) <= src_owned:
                return False
        for ci, src_ids, dst_ids in incomplete:
            pk, pv = self.channel.send_chunk(
                rid, ci, list(src_ids), list(dst_ids),
                self.pool_p["k"], self.pool_p["v"])
            self.pool_p = {"k": pk, "v": pv}
            self._jlog("migrate", rid=rid, chunk=ci, pages=len(src_ids),
                       attempt=self.channel._attempt.get((rid, ci), 0),
                       retry=True)
        req.retries += 1
        self.metrics_decode.inc("retries")
        return True

    def _degrade_or_fail(self, slot: int, req: Request,
                         exc: Exception) -> None:
        if (self.allow_degradation
                and req.degradations < self.max_degradations):
            self._degrade(slot, req)
        else:
            self._fail_decode(slot, req, exc)

    def _degrade(self, slot: int, req: Request) -> None:
        """The composed degrade rung: requeue (front) into the DECODE
        engine's own admission queue. The request keeps its decode-side
        page reservation, so the decode engine's chunked admission
        allocates nothing new and re-prefills the prompt locally through
        its ordinary chunk path — the possibly-lossy migration transport
        is out of the loop, and determinism makes the recomputed tokens
        bit-identical."""
        rid = req.rid
        req.degradations += 1
        self.metrics_decode.inc("degradations")
        self.metrics_decode.observe("degraded_prefill_tokens",
                                    len(req.prompt))
        self.channel.ledger.reset(rid)
        self._recovery.pop(rid, None)
        self._wait_steps.pop(rid, None)
        self._poisoned.pop(rid, None)
        if self.alloc_p.holds(rid):
            self.alloc_p.free_seq(rid)
        self.sched_d.remove(slot)
        self.decode._park(slot)
        req.state = RequestState.QUEUED
        req.prefill_cursor = 0
        req.generated.clear()
        req.first_token = None
        del self._dslot[rid]
        self.sched_d.submit(req, front=True)
        self._degraded[rid] = req

    def _note_degraded_progress(self) -> None:
        """Close the recovery clock of degraded requests the decode
        engine has carried back to life (first locally recomputed token
        seen, or already finished within the same composed step)."""
        done = [rid for rid, r in self._degraded.items()
                if r.generated or r.state in (RequestState.FINISHED,
                                              RequestState.ACTIVE)]
        for rid in done:
            req = self._degraded.pop(rid)
            self.metrics_decode.observe(
                "degraded_ttft_s", time.perf_counter() - req.submit_time)

    def _fail_decode(self, slot: int, req: Request, exc: Exception) -> None:
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
        del self._dslot[rid]
        self.decode._park(slot)
        self._failed.append(req)
        self.metrics_decode.inc("failed_requests")
        self._jlog("fail", rid=rid, error_type=type(exc).__name__,
                   reason=str(exc).splitlines()[0])

    def _poison(self, slot: int, req: Request, exc: Exception) -> None:
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

    def _on_decode_preempt(self, slot: int, req: Request) -> bool:
        """``ServingEngine._preempt`` hook: a MIGRATING seat holds pages
        in the prefill fleet's pool (which the decode engine cannot see)
        and must bounce back to the PREFILL queue — the composed teardown
        below. Post-flip ACTIVE and degraded seats are decode-owned; the
        decode engine's native eviction (local re-prefill, bit-identical)
        handles them, we only void stale migration state first."""
        rid = req.rid
        if rid in self._dslot:
            self._preempt_decode(slot, req)
            return True
        self.channel.ledger.reset(rid)
        if self.alloc_p.holds(rid):
            self.alloc_p.free_seq(rid)
        return False

    def _preempt_decode(self, slot: int, req: Request) -> None:
        rid = req.rid
        self.sched_d.remove(slot)
        req.state = RequestState.QUEUED
        req.preemptions += 1
        req.generated.clear()
        req.prefill_cursor = 0
        req.first_token = None
        req.cache_hit_tokens = 0
        self.alloc_d.free_seq(rid)
        if self.alloc_p.holds(rid):
            self.alloc_p.free_seq(rid)
        self.channel.ledger.reset(rid)
        self._recovery.pop(rid, None)
        self._wait_steps.pop(rid, None)
        self._poisoned.pop(rid, None)
        del self._dslot[rid]
        self.sched_p.submit(req, front=True)
        self.decode._park(slot)
        self.metrics_decode.inc("preemptions")
        self._jlog("preempt", rid=rid, slot=slot, worker="decode")

    def _harvest_decode(self) -> None:
        """Requests the decode engine finished this step move to the
        composed terminal list, with the composed journal's ``finish``
        entry (the decode engine has no journal) and any residual
        migration state torn down."""
        if not self.decode._finished:
            return
        for req in self.decode._finished:
            rid = req.rid
            self.channel.ledger.reset(rid)
            self.channel.forget(rid)
            self._recovery.pop(rid, None)
            self._wait_steps.pop(rid, None)
            self._poisoned.pop(rid, None)
            self._degraded.pop(rid, None)
            self._dslot.pop(rid, None)
            if self.alloc_p.holds(rid):
                self.alloc_p.free_seq(rid)
            req.finish_step = self._steps
            self._finished.append(req)
            self._jlog("finish", rid=rid, tokens=list(req.generated),
                       submit_step=req.submit_step,
                       first_token_step=req.first_token_step,
                       preemptions=req.preemptions)
        self.decode._finished = []

    # -- one driver iteration ---------------------------------------------
    @property
    def idle(self) -> bool:
        return (self.sched_p.idle and not self._handoff
                and self.sched_d.idle)

    def step(self) -> bool:
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
        """One composed step: prefill fleet (admissions + ≤1 chunk +
        migration), delayed-report delivery, decode seating + signal-
        gated admission, then ONE full step of the sharded decode engine
        (its own admissions — the degrade rung — growth/preemption,
        decode dispatch, digest cross-check), then harvest."""
        if self.idle:
            return False
        while True:
            adm = self.sched_p.admissible(self._can_hold)
            if adm is None:
                break
            self._admit_prefill(*adm)
        ptoks = self._dispatch_prefill_chunk()
        self.metrics.observe("step_prefill_tokens", ptoks)

        for rid, exc in self.channel.tick(self._steps):
            self._poisoned.setdefault(rid, exc)
        self._seat_decode_slots()
        self._patch_and_admit()
        self.decode.step()
        self._note_degraded_progress()
        self._harvest_decode()
        self._steps += 1
        return True

    def run(self, max_steps: int | None = None,
            arrivals=None, recover=None) -> dict[int, list[int]]:
        """Drive ``step()`` until idle (or ``max_steps``); same contract
        and recovery/watchdog semantics as the disagg engine's ``run``."""
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
        c, d = self.metrics.counters, self.metrics_decode.counters
        return (c["prefill_chunks"], c["pages_migrated"],
                c["migrate_chunks"], c["restores"], c["expirations"],
                d["tokens_generated"], d["handoffs"], d["retries"],
                d["degradations"], d["failed_requests"], d["preemptions"],
                d["prefill_chunks"], len(self._finished), len(self._failed))

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
                f"(stall deadline {self._stall_steps}, step {self._steps}, "
                f"mesh {self.mesh_desc}); queues: "
                f"prefill={self.sched_p.queue_depth} "
                f"handoff={len(self._handoff)} "
                f"decode={self.sched_d.queue_depth} "
                f"degraded={sorted(self._degraded)} "
                f"recovering={sorted(self._recovery)} "
                f"poisoned={sorted(self._poisoned)}; slots: "
                + ("; ".join(rows) if rows else "<none>"))

    # -- crash consistency (disagg-shaped, over both fleets) ---------------
    def control_digest(self) -> int:
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
        """Disagg-shaped snapshot over both fleets. Live order: decode
        seats by ticket, the decode queue (degraded), the handoff queue,
        prefill seats by ticket, then the prefill queue — every one
        restores as a fresh QUEUED prefill (restart-from-prompt re-earns
        pages AND re-migrates)."""
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
        for r in self.sched_d.queue:
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
            "engine": "disagg_sharded",
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
            "prefix_index_d": (None if self.decode.prefix_cache is None
                               else self.decode.prefix_cache.snapshot()),
            "prefix_digest_d": (None if self.decode.prefix_cache is None
                                else self.decode.prefix_cache.digest()),
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
        """Rebuild both fleets' host control state (None = from nothing).
        The decode engine rebuilds through its own ``_restore_state``
        (mirrors re-uploaded committed, ``sp_ranks`` preserved by the
        unified pool contract); coverage must be re-earned — the ledger
        and the channel's attempt/delay state are cleared."""
        n_sp = self.alloc_p.sp_ranks
        self.alloc_p = KVPagePool(self.alloc_p.num_pages, self.page_size,
                                  reserved=1, sp_ranks=n_sp)
        self.sched_p = ContinuousBatchingScheduler(
            self.sched_p.num_slots, queue_cap=self.sched_p.queue_cap,
            policy=self.sched_p.policy)
        if self.prefix_cache is not None:
            # empty cache on the fresh ledger: cached KV is device state,
            # re-earned by re-prefill (the decode fleet's cache resets
            # inside decode._restore_state the same way)
            self.prefix_cache = PrefixCache(self.alloc_p, self.page_size)
        self.decode._restore_state(None)
        self._handoff.clear()
        self._dslot.clear()
        self._wait_steps.clear()
        self._recovery.clear()
        self._poisoned.clear()
        self._degraded.clear()
        self._finished = []
        self._failed = []
        self._rejected = []
        self.channel.ledger = ChunkSignalLedger()
        self.channel._attempt.clear()
        self.channel._delayed.clear()
        if state is None:
            return
        ckpt_mod.audit_pool_snapshot(
            state["pool_p"], state["pool_p_digest"],
            self.alloc_p.num_pages, self.page_size, 1)
        ckpt_mod.audit_pool_snapshot(
            state["pool_d"], state["pool_d_digest"],
            self.alloc_d.num_pages, self.page_size, 1)
        for ix, dg in (("prefix_index", "prefix_digest"),
                       ("prefix_index_d", "prefix_digest_d")):
            if state.get(ix) is not None:
                ckpt_mod.audit_prefix_snapshot(state[ix], state[dg])
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
        return list(self._failed) + list(self._rejected)

    # -- introspection ----------------------------------------------------
    @property
    def compile_stats(self) -> dict:
        """The composition adds NO programs to the sharded engine's two
        (the prefill fleet reuses its chunk executable — same shapes,
        same committed sharding) beyond the one migration copy program."""
        base = self.decode.compile_stats
        stats = {
            "prefill_chunk_compiles": base["prefill_chunk_compiles"],
            "decode_compiles": base["decode_compiles"],
            "migrate_compiles": programs.compiles(
                self._xmig,
                1 if self.metrics.counters["migrate_chunks"] else 0),
        }
        if self._aot_artifact is not None:
            from triton_dist_tpu.aot.artifact import LoadedProgram
            stats["aot_programs"] = (
                base.get("aot_programs", 0)
                + int(isinstance(self._xmig, LoadedProgram)))
        return stats


__all__ = ["DisaggShardedEngine"]
