"""Cluster serving (ISSUE 12 rungs 2+3): replicas + a deterministic
router.

The reference's L7 seam — "user code" above the overlap library — is
where serving becomes a FLEET problem: N independent engine replicas
behind a router, each replica its own failure domain (ISSUE 7) with its
own crash-consistency journal (ISSUE 9). This module supplies the two
host-side abstractions:

- :class:`EngineReplica` wraps ANY of the serving engines (colocated,
  disagg, sharded, composed, or the host-only :class:`SimEngine`) with a
  PRIVATE, path-namespaced journal (``journal-r{i}.jsonl`` — N replicas
  sharing one ``ControlJournal`` path would interleave their entries and
  cross-replay each other's requests on restore), load/occupancy/queue-
  depth signals read duck-typed off the engine's intake scheduler and
  pool ledger, and a ``kill()``/``restore()`` pair that drives the full
  ISSUE 9 recovery ladder: reload the journal from disk, rebuild a fresh
  engine, restore from the newest checkpoint (or replay the whole
  journal when none was cut), re-attach the append handle.
- :class:`Cluster` routes by **prefix affinity with a least-loaded
  tie-break**, rendezvous style: every alive replica scores
  ``fnv1a(index, prompt[:prefix_tokens])`` and the highest score wins,
  so a shared prompt prefix lands on the same replica (KV/page locality)
  WITHOUT a routing table — and when a replica dies, only its keys move
  (classic highest-random-weight behaviour). Ties break to the least
  loaded then the lowest index; an optional spill threshold diverts from
  a hot affinity target to the least-loaded replica. Everything is a
  pure function of (alive set, prompt, load) — the router adds no
  nondeterminism, which is what lets cluster traces be verified
  bit-identically against single-replica goldens.

:class:`SimEngine` is the scale vehicle: a host-only engine with the
REAL page ledger, the REAL scheduler (admission tickets, strict-FIFO
head-of-line, growth-driven preemption, queue caps, TTLs) and the real
journal/checkpoint surface, but a closed-form token function instead of
device dispatches — ``sim_token(prompt, i)``, a pure function of the
prompt and the token index, exactly the determinism contract the device
engines pin (tokens are a function of (params, prompt) — here params
degenerate to the hash seed). ``expected_tokens`` is therefore the
single-replica golden in closed form, and ``scripts/cluster_sim.py``
checks hundreds of thousands of routed, preempted, killed-and-restored
requests against it bitwise.
"""

from __future__ import annotations

import enum
import os
import time
from collections import deque

import numpy as np

from triton_dist_tpu.serving import checkpoint as ckpt_mod
from triton_dist_tpu.serving.deadline import Deadline
from triton_dist_tpu.serving.engine import (class_label, mark_prefill_start,
                                            record_first_token)
from triton_dist_tpu.serving.journal import ControlJournal
from triton_dist_tpu.serving.kv_pool import KVPagePool, _fnv1a
from triton_dist_tpu.serving.metrics import ServingMetrics
from triton_dist_tpu.serving.prefix_cache import (PrefixCache,
                                                  ReplicaPrefixIndex)
from triton_dist_tpu.serving.scheduler import (AdmissionRejected,
                                               ContinuousBatchingScheduler,
                                               Request, RequestState,
                                               SLOPolicy, TtlExpired)
from triton_dist_tpu.shmem import faults

SIM_VOCAB = 32000


def sim_token(prompt: tuple[int, ...], i: int, vocab: int = SIM_VOCAB
              ) -> int:
    """The SimEngine's "model": token ``i`` of a request is a pure
    function of the prompt (first 8 tokens + length) and the index —
    the same shape of determinism contract the device engines pin."""
    return _fnv1a(0x811C9DC5, *prompt[:8], len(prompt), i) % vocab


def expected_tokens(prompt, max_new_tokens: int, vocab: int = SIM_VOCAB
                    ) -> list[int]:
    """Closed-form single-replica golden for a SimEngine request."""
    prompt = tuple(int(t) for t in prompt)
    return [sim_token(prompt, i, vocab) for i in range(max_new_tokens)]


class SimEngine:
    """Host-only serving engine: real control plane (page ledger,
    scheduler, journal, checkpoints, TTL/queue-cap shedding, growth-
    driven preemption), closed-form tokens (``sim_token``) instead of
    device dispatches. One token per ACTIVE slot per step; "prefill" is
    instantaneous at admission (the first token appears the admitting
    step, exactly like a one-chunk prompt). Exposes the same duck-typed
    surface ``serving/checkpoint.py`` restores through, so an
    :class:`EngineReplica` can kill/restore it like the device engines.

    With ``prefix_cache=True`` (ISSUE 17) the instant prefill becomes the
    device engines' chunked state machine in step space: admission adopts
    the longest cached full-page prefix (real ``PrefixCache`` over the
    real ledger), the PREFILLING slot advances ``prefill_chunk`` tokens
    per step from its cursor, and the first token lands the step the
    cursor reaches the prompt end — so cold, cached and re-warmed TTFTs
    separate DETERMINISTICALLY (``ttft_*_steps`` histograms), which is
    what the cluster lending acceptance asserts on. ``export_prefix`` /
    ``adopt_prefix`` are the lend surface ``serving/lending.py`` drives;
    the Sim pool is a pure ledger, so the "transfer" is bookkeeping only
    (device engines move the actual page bytes — ``ops.lend_pages``).
    """

    def __init__(self, num_slots: int = 4, page_size: int = 16,
                 num_pages: int = 64, pages_per_seq: int = 8,
                 metrics: ServingMetrics | None = None,
                 eos_id: int | None = None, vocab: int = SIM_VOCAB,
                 journal: ControlJournal | None = None,
                 checkpoint_every: int | None = None,
                 queue_cap: int | None = None,
                 ttl_steps: int | None = None,
                 fault_plan: "faults.FaultPlan | None" = None,
                 slo: SLOPolicy | None = None,
                 prefix_cache: bool = False,
                 prefill_chunk: int | None = None):
        assert checkpoint_every is None or journal is not None
        assert prefill_chunk is None or prefill_chunk >= 1
        assert not prefix_cache or prefill_chunk is not None, (
            "prefix_cache needs prefill_chunk set — a cache hit resumes "
            "chunked prefill at its cursor; the instant path has no "
            "cursor to resume at (same contract as ServingEngine)")
        self.page_size = page_size
        self.pages_per_seq = pages_per_seq
        self.num_slots = num_slots
        self.eos_id = eos_id
        self.vocab = vocab
        self.metrics = metrics or ServingMetrics()
        self.alloc = KVPagePool(num_pages + 1, page_size, reserved=1)
        self.prefill_chunk = prefill_chunk
        self.prefix_cache = PrefixCache(self.alloc, page_size) \
            if prefix_cache else None
        # lend bookkeeping (ISSUE 17): pages adopted FROM a peer replica
        # (for the rewarmed-vs-cached TTFT split) and a generation counter
        # for the transient ledger seq-ids adopt_prefix allocates under
        self._lent_pages: set[int] = set()
        self._lend_gen = 0
        self._ttft_kind: dict[int, str] = {}
        self.slo = slo
        self.sched = ContinuousBatchingScheduler(num_slots,
                                                 queue_cap=queue_cap,
                                                 policy=slo)
        self.journal = journal
        self.checkpoint_every = checkpoint_every
        self.ttl_steps = ttl_steps
        self._fault_plan = fault_plan
        self._journal_muted = False
        self._replaying = False
        self._incarnation = 0
        self._last_ckpt_step = -1
        self._finished: list[Request] = []
        self._failed: list[Request] = []
        self._rejected: list[Request] = []
        self._next_rid = 0
        self._steps = 0

    # -- intake (device engines' contract verbatim) ------------------------
    def _ttl_for(self, req: Request) -> int | None:
        """Class TTL override (ISSUE 14) beats the engine-wide knob."""
        spec = self.sched.class_spec(req)
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
        assert need <= self.alloc.num_pages - self.alloc.reserved
        if rid is None:
            rid = self._next_rid
        self._next_rid = max(self._next_rid, rid + 1)
        req = Request(rid=rid, prompt=prompt, max_new_tokens=max_new_tokens,
                      eos_token=self.eos_id, submit_step=self._steps,
                      submit_time=time.perf_counter())
        self.sched.stamp(req, tenant=tenant, cls=cls)
        self.metrics.inc("requests_submitted")
        self.metrics.inc_class("requests_submitted", class_label(req))
        if self.sched.at_capacity_for(req.cls) and not self._replaying:
            cap = self.sched.queue_cap if self.sched.at_capacity else \
                self.sched.policy.spec(req.cls).queue_cap
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
        self.sched.submit(req)
        self._jlog("submit", rid=rid, prompt=list(prompt),
                   max_new_tokens=max_new_tokens,
                   tenant=req.tenant, cls=req.cls)
        return rid

    # -- one step ----------------------------------------------------------
    @property
    def idle(self) -> bool:
        return self.sched.idle

    def step(self) -> bool:
        self.sched.tick(self._steps)
        self._expire_queued()
        progressed = self._step_impl()
        self.metrics.counters["quota_throttled"] = self.sched.quota_throttled
        if progressed:
            self._maybe_checkpoint()
        return progressed

    def _can_hold(self, req: Request) -> bool:
        need = -(-len(req.prompt) // self.page_size)
        need -= len(self.alloc.pages_of(req.rid))
        avail = self.alloc.free_pages
        if self.prefix_cache is not None:
            avail += self.prefix_cache.evictable
        return avail >= max(need, 0)

    def _reclaim(self, n_pages: int) -> None:
        """Evict cached prefixes until ``n_pages`` are allocatable
        (engine.py's ``_reclaim``, verbatim semantics)."""
        short = n_pages - self.alloc.free_pages
        if short > 0 and self.prefix_cache is not None:
            self.metrics.inc("prefix_evictions",
                             self.prefix_cache.evict(short))

    def _cache_adopt(self, req: Request) -> None:
        """Admission-time prefix adoption (engine.py's ``_cache_adopt``
        in step space): acquire the longest cached full-page prefix and
        start the prefill cursor past it. Also classifies the request's
        eventual TTFT — cold (no hit), cached (local hit) or rewarmed
        (hit on pages a peer lent us)."""
        cache = self.prefix_cache
        if cache is None or req.prefill_cursor > 0 \
                or self.alloc.holds(req.rid):
            return      # resumed-after-preempt or replayed: re-prefills
        hit = cache.match(req.prompt)
        if not hit:
            self.metrics.inc("prefix_misses")
            self._ttft_kind[req.rid] = "cold"
            return
        self.alloc.acquire(req.rid, hit)
        req.prefill_cursor = len(hit) * self.page_size
        req.cache_hit_tokens = req.prefill_cursor
        self.metrics.inc("prefix_hits")
        self.metrics.inc("prefix_hit_tokens", req.prefill_cursor)
        # unlike the device engines there is no argmax to recompute, so a
        # whole-prompt hit keeps cursor == len(prompt): the first token
        # emits the admitting step — TTFT identical to a cached hit
        self._ttft_kind[req.rid] = (
            "rewarmed" if any(p in self._lent_pages for p in hit)
            else "cached")

    def _advance_prefill(self, slot: int, req: Request) -> None:
        """One chunk of step-space prefill; on reaching the prompt end,
        emit the first token, index the prompt's full pages, and record
        the cold/cached/rewarmed TTFT split (all deterministic: steps,
        not wall time)."""
        sp = len(req.prompt)
        if req.prefill_cursor < sp:
            chunk = min(self.prefill_chunk, sp - req.prefill_cursor)
            req.prefill_cursor += chunk
            self.metrics.inc("prefill_chunks")
            self._jlog("chunk", rid=req.rid, cursor=req.prefill_cursor)
            if req.prefill_cursor < sp:
                return
        req.state = RequestState.ACTIVE
        req.first_token = sim_token(req.prompt, 0, self.vocab)
        req.generated.append(req.first_token)
        record_first_token(req, self.metrics, self._steps)
        self.metrics.inc("tokens_generated")
        if self.prefix_cache is not None:
            # index full prompt pages BEFORE decode grows the sequence —
            # the partial last page (decode writes there) never enters
            self.prefix_cache.insert(
                req.prompt,
                self.alloc.pages_of(req.rid)[:sp // self.page_size])
        kind = self._ttft_kind.pop(req.rid, "cold")
        self.metrics.observe(f"ttft_{kind}_steps",
                             self._steps - req.submit_step)
        if req.done:
            self._finish(slot)

    def _step_impl(self) -> bool:
        if self.sched.idle:
            return False
        # admissions: instant "prefill" (first token the admitting step)
        # unless prefill_chunk arms the chunked state machine
        while True:
            adm = self.sched.admissible(self._can_hold)
            if adm is None:
                break
            slot, req = adm
            self._cache_adopt(req)
            need = -(-len(req.prompt) // self.page_size)
            have = len(self.alloc.pages_of(req.rid))
            if need > have:
                self._reclaim(need - have)
                got = self.alloc.alloc(req.rid, need - have)
                assert got is not None
            self.sched.activate(slot, req)
            self._jlog("admit", rid=req.rid, slot=slot)
            req.state = RequestState.PREFILLING
            mark_prefill_start(req, self.metrics, self._steps)
            self.metrics.inc("prefills")
            if self.prefill_chunk is None:
                self.metrics.inc("prefill_chunks")
                req.prefill_cursor = len(req.prompt)
                req.state = RequestState.ACTIVE
                req.first_token = sim_token(req.prompt, 0, self.vocab)
                req.generated.append(req.first_token)
                record_first_token(req, self.metrics, self._steps)
                self.metrics.inc("tokens_generated")
                if req.done:
                    self._finish(slot)
        # chunked prefill: every PREFILLING slot (including ones admitted
        # this very step) advances one chunk; a slot whose cursor reaches
        # the prompt end emits its first token and joins decode below —
        # so a whole-prompt cache hit reaches its token the admitting
        # step, exactly like the instant path (TTFT ≈ cached)
        if self.prefill_chunk is not None:
            for slot in range(self.num_slots):
                req = self.sched.slots[slot]
                if req is not None \
                        and req.state is RequestState.PREFILLING:
                    self._advance_prefill(slot, req)
        # growth + decode: one token per ACTIVE slot, paged growth with
        # the real eviction ladder when the pool runs dry. Token i's KV
        # lands at position len(prompt)+i and the LAST token's KV is
        # never written (the request finishes on emission) — so the max
        # footprint is len(prompt)+max_new_tokens-1, the submit() bound.
        for slot in range(self.num_slots):
            req = self.sched.slots[slot]
            if req is None or req.state is not RequestState.ACTIVE:
                continue
            kv_len = len(req.prompt) + len(req.generated)
            ok = self.alloc.ensure(req.rid, kv_len)
            while not ok:
                victim = self.sched.pick_victim(exclude_slot=slot)
                if victim is None:
                    break   # nobody to evict — this slot waits a step
                self._preempt(victim)
                ok = self.alloc.ensure(req.rid, kv_len)
            if not ok:
                continue
            req.generated.append(
                sim_token(req.prompt, len(req.generated), self.vocab))
            self.metrics.inc("tokens_generated")
            self.metrics.inc("decode_steps")
            if req.done:
                self._finish(slot)
        self.metrics.observe("queue_depth", self.sched.queue_depth)
        self._steps += 1
        return True

    def _finish(self, slot: int) -> None:
        req = self.sched.finish(slot)
        self.alloc.free_seq(req.rid)
        req.finish_step = self._steps
        self._finished.append(req)
        self.metrics.inc("requests_finished")
        self.metrics.inc_class("requests_finished", class_label(req))
        self._jlog("finish", rid=req.rid, tokens=list(req.generated),
                   submit_step=req.submit_step,
                   first_token_step=req.first_token_step,
                   preemptions=req.preemptions)

    def _preempt(self, slot: int) -> None:
        req = self.sched.slots[slot]
        self.alloc.free_seq(req.rid)
        req.prefill_cursor = 0
        req.first_token = None
        self._ttft_kind.pop(req.rid, None)   # re-classified on re-admit
        self.sched.evict(slot)
        self.metrics.inc("preemptions")
        self._jlog("preempt", rid=req.rid, slot=slot)

    def _expire_queued(self) -> None:
        for req in self.sched.expire(self._steps):
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

    # -- cluster page lending (ISSUE 17, serving/lending.py drives) --------
    def export_prefix(self, prompt,
                      payload: bool = True) -> tuple[int, list[int], None]:
        """Lender half: the longest locally cached full-page prefix of
        ``prompt`` that is LENDABLE — trimmed to the positional prefix
        ``KVPagePool.check_lendable`` accepts (refcount-0 AND index-
        retained; a page some live sequence still references is never
        shipped, keeping the sole-ownership/COW contract untouched).
        Returns ``(tokens, page_ids, payload)``; the Sim pool is a pure
        ledger so the payload slot is always None (device engines return
        the page bytes here — the host twin of what ``ops.lend_pages``
        moves — and skip the gather when ``payload=False``, the cheap
        depth-only probe rewarm's peer selection uses)."""
        if self.prefix_cache is None:
            return 0, [], None
        prompt = tuple(int(t) for t in prompt)
        hit = self.prefix_cache.match(prompt)
        n = self.alloc.check_lendable(hit)
        return n * self.page_size, hit[:n], None

    def adopt_prefix(self, prompt, n_tokens: int, payload=None) -> int:
        """Borrower half: materialize the first ``n_tokens`` of
        ``prompt`` as locally cached prefix pages. Pages are allocated
        under a transient lend seq-id, indexed, and immediately released
        — ``insert`` marked them cacheable, so the release parks them on
        the cached LRU exactly like a finished prefill's pages. Returns
        pages newly adopted (0 = nothing to do or pool too tight; the
        lending tier degrades to cold prefill, never stalls)."""
        cache = self.prefix_cache
        if cache is None or n_tokens <= 0:
            return 0
        prompt = tuple(int(t) for t in prompt)
        want = min(n_tokens, len(prompt)) // self.page_size
        have = cache.match(prompt)
        if want <= len(have):
            return 0        # local cache already at least as deep
        need = want - len(have)
        sid = ("lend", self._lend_gen)
        self._lend_gen += 1
        if have:
            # pin the local hit under the lend sid BEFORE reclaiming:
            # `have` sits refcount-0 on the cached LRU, so an unpinned
            # reclaim under pool pressure could evict it out from under
            # the insert below (same acquire-first order as _cache_adopt)
            self.alloc.acquire(sid, have)
        self._reclaim(need)
        got = self.alloc.alloc(sid, need)
        if got is None:
            self.alloc.free_seq(sid)    # unpin the hit
            return 0        # pool too tight even after eviction
        # [device engines scatter payload bytes into `got` here]
        # the first len(have) entries ride existing trie edges (insert is
        # first-writer-wins: pages for existing runs are ignored), the
        # fresh pages take the runs beyond the local hit
        cache.insert(prompt[:want * self.page_size], have + got)
        self.alloc.free_seq(sid)    # refcount-0 + cacheable → cached LRU
        self._lent_pages.update(got)
        self._jlog("lend", tokens=want * self.page_size, pages=need)
        return need

    def run(self, max_steps: int | None = None, arrivals=None,
            recover=None) -> dict[int, list[int]]:
        if recover:
            assert self.journal is not None
            ck = recover if isinstance(recover, ckpt_mod.Checkpoint) \
                else ckpt_mod.latest(self.journal)
            ckpt_mod.restore(self, ck, self.journal)
        pending = deque(arrivals or [])
        i = 0
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
        return {req.rid: list(req.generated) for req in self._finished}

    # -- crash consistency (checkpoint.py duck-typed surface) --------------
    def control_digest(self) -> int:
        # cheap by design: folded counters, not the full ledgers — at
        # cluster_sim scale (100k+ requests) an O(pages+queue) digest per
        # journal entry dominates the run. The checkpoint audit still
        # hashes the REAL pool ledger (pool_digest below).
        return _fnv1a(0x811C9DC5, self._steps, self._next_rid,
                      self.alloc.used_pages, self.sched.queue_depth,
                      self.sched._admit_ticket,
                      self.metrics.counters["requests_finished"])

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
        assert self.journal is not None
        ck = ckpt_mod.capture(self)
        self.journal.record_checkpoint(ck.step, ck.digest, ck.state,
                                       ck.journal_seq)
        self._last_ckpt_step = self._steps
        self.metrics.inc("checkpoints")
        return ck

    def _capture_state(self) -> dict:
        live: list[Request] = []
        seen: set[int] = set()
        for _, req in sorted(((r.admitted_seq, r)
                              for _, r in self.sched.active),
                             key=lambda t: t[0]):
            seen.add(req.rid)
            live.append(req)
        for req in self.sched.queue:
            if req.rid not in seen:
                live.append(req)
        return {
            "engine": "sim",
            "step": self._steps,
            "next_rid": self._next_rid,
            "admit_ticket": self.sched._admit_ticket,
            "pool": self.alloc.snapshot(),
            "pool_digest": self.alloc.digest(),
            # prefix index (ISSUE 17): integrity artifact, like the pool
            # snapshot — restore starts with an EMPTY cache (the cluster
            # re-warms it from peers; pre-crash pages are never adopted)
            "prefix_index": None if self.prefix_cache is None
            else self.prefix_cache.snapshot(),
            "prefix_digest": None if self.prefix_cache is None
            else self.prefix_cache.digest(),
            "live": [ckpt_mod.snapshot_request(r) for r in live],
            "finished": [ckpt_mod.snapshot_finished(r)
                         for r in self._finished],
            "rejected": [{"rid": r.rid, "kind": "expire"
                          if isinstance(r.failure, TtlExpired) else "reject",
                          "reason": str(r.failure), "tenant": r.tenant,
                          "cls": r.cls} for r in self._rejected],
            "policy": self.sched.policy_state(),
            "counters": dict(self.metrics.counters),
        }

    def _restore_state(self, state: dict | None) -> None:
        self.alloc = KVPagePool(self.alloc.num_pages, self.page_size,
                                reserved=1)
        self.sched = ContinuousBatchingScheduler(
            self.sched.num_slots, queue_cap=self.sched.queue_cap,
            policy=self.sched.policy)
        if self.prefix_cache is not None:
            # EMPTY cache over the fresh pool: restored requests re-earn
            # KV via re-prefill; the cluster's restore() re-warms shared
            # prefixes from peers through the lending tier
            self.prefix_cache = PrefixCache(self.alloc, self.page_size)
        self._lent_pages = set()
        self._ttft_kind = {}
        self._finished = []
        self._failed = []
        self._rejected = []
        if state is None:
            return
        ckpt_mod.audit_pool_snapshot(state["pool"], state["pool_digest"],
                                     self.alloc.num_pages, self.page_size, 1)
        if state.get("prefix_index") is not None:
            ckpt_mod.audit_prefix_snapshot(state["prefix_index"],
                                           state["prefix_digest"])
        self._steps = state["step"]
        self._next_rid = state["next_rid"]
        self.sched._admit_ticket = state["admit_ticket"]
        for snap in state["live"]:
            req = ckpt_mod.rebuild_request(snap)
            req.submit_time = time.perf_counter()
            ttl = self._ttl_for(req)
            if ttl is not None:
                req.deadline = Deadline(ttl, req.submit_step)
            self.sched.submit(req)
        # WFQ/bucket books restore AFTER the requeues: submit()'s idle-
        # class vfloor snap ran against zeroed counters above, and the
        # checkpoint values now overwrite them (order-dependent)
        self.sched.restore_policy_state(state.get("policy"))
        for f in state["finished"]:
            self._restore_finished(f["rid"], f["tokens"], meta=f)
        for f in state["rejected"]:
            self._restore_terminal(f["rid"], f["kind"], f["reason"])

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
        req.state = RequestState.REJECTED
        req.failure = (TtlExpired(reason) if kind == "expire"
                       else AdmissionRejected(reason))
        self._rejected.append(req)

    def _pop_queued(self, rid: int) -> Request | None:
        for r in self.sched.queue:
            if r.rid == rid:
                self.sched.queue.remove(r)
                return r
        return None

    @property
    def failed(self) -> list[Request]:
        return list(self._failed) + list(self._rejected)


class ReplicaState(enum.Enum):
    """Replica lifecycle (ISSUE 18). The router admits only ACTIVE
    replicas; DRAINING replicas keep stepping (they finish in-flight
    decodes and may still LEND — that is drain-time lend-ahead) but
    receive no new work; WARMING replicas exist (their engine is built,
    the AOT artifact loaded) but neither admit nor step until the
    cluster promotes them; KILLED is the crash state (engine gone,
    journal on disk is the surviving truth — restore() returns the
    replica to whatever it was doing when it died, which is how a crash
    mid-drain resumes the drain rather than resurrecting an admitting
    replica); RETIRED is terminal — a drain completed, the journal
    closed, the engine dropped. Fleet indices are append-only: a retired
    index is never reused, so journal paths and rendezvous scores stay
    stable across any schedule of scale events."""

    WARMING = "warming"
    ACTIVE = "active"
    DRAINING = "draining"
    RETIRED = "retired"
    KILLED = "killed"


class EngineReplica:
    """One engine + one PRIVATE journal + one failure domain.

    ``factory(journal)`` builds the engine; the replica derives its own
    journal path (``journal-r{index}.jsonl`` under ``journal_dir``) so N
    replicas in one directory never interleave entries — the namespacing
    the two-replica restart test pins (no cross-replica replay bleed).
    ``journal_dir=None`` keeps the journal in memory (kill/restore then
    replays the retained object instead of re-reading disk).
    """

    def __init__(self, index: int, factory, journal_dir: str | None = None,
                 artifact=None):
        self.index = index
        self._factory = factory
        self.artifact = artifact
        self.journal_path = (os.path.join(journal_dir,
                                          f"journal-r{index}.jsonl")
                             if journal_dir is not None else None)
        self.journal = ControlJournal(path=self.journal_path)
        self.lifecycle = ReplicaState.WARMING
        t0 = time.perf_counter()
        self.engine = self._build(self.journal)
        # scale-up-to-first-token split: with an AOT artifact threaded
        # the build is dominated by artifact load, not tracing — the
        # number cluster_sim's autoscale panel reports per scale-up
        self.build_s = time.perf_counter() - t0
        self.lifecycle = ReplicaState.ACTIVE
        self.warm_remaining = 0
        self.failovers = 0
        # crash bookkeeping: what the replica was doing when kill() hit
        # (restore() resumes THAT state — a crash mid-drain must come
        # back DRAINING, not admitting) and, for a drain interrupted by
        # a crash, the kill-time tombstones finish_drain still lends
        # ahead (prune already ran at kill time, so the drain-completion
        # prune would otherwise find nothing to hand off)
        self._prekill = ReplicaState.ACTIVE
        self._drain_prefixes: list[tuple[int, ...]] = []

    @property
    def alive(self) -> bool:
        """An engine exists and can step/lend. DRAINING and WARMING
        replicas are alive — only KILLED and RETIRED are not."""
        return self.engine is not None

    @property
    def admitting(self) -> bool:
        """The router's gate: only ACTIVE replicas receive new work."""
        return self.lifecycle is ReplicaState.ACTIVE

    @property
    def draining(self) -> bool:
        return self.lifecycle is ReplicaState.DRAINING

    def _build(self, journal):
        """AOT artifact (ISSUE 15): thread the artifact through BOTH the
        cold build and every restore — a restored replica must reach its
        first token with zero fresh traces, exactly like a cold one."""
        if self.artifact is not None:
            return self._factory(journal, artifact=self.artifact)
        return self._factory(journal)

    # load signals, duck-typed off the engine's intake scheduler and the
    # pool the decode work actually occupies
    @property
    def _sched(self):
        return getattr(self.engine, "sched_p", None) or self.engine.sched

    @property
    def _alloc(self):
        return getattr(self.engine, "alloc_d", None) or self.engine.alloc

    @property
    def queue_depth(self) -> int:
        return self._sched.queue_depth

    @property
    def occupancy(self) -> float:
        return self._alloc.occupancy()

    @property
    def load(self) -> int:
        """Routing load: queued + seated requests on the intake side."""
        s = self._sched
        return s.queue_depth + sum(r is not None for r in s.slots)

    @property
    def idle(self) -> bool:
        e = self.engine
        v = getattr(e, "idle", None)
        return bool(v) if v is not None else e.sched.idle

    def submit(self, prompt, max_new_tokens: int,
               tenant: str | None = None, cls: str | None = None) -> int:
        assert self.alive, f"replica {self.index} is dead"
        return self.engine.submit(prompt, max_new_tokens,
                                  tenant=tenant, cls=cls)

    def step(self) -> bool:
        assert self.alive, f"replica {self.index} is dead"
        return self.engine.step()

    def kill(self) -> None:
        """Fail the replica: close the journal's append handle (the
        on-disk jsonl is the surviving truth) and drop the engine.
        Legal in ANY alive state — killing a DRAINING replica is the
        crash-mid-drain case, and ``_prekill`` remembers the state so
        restore() resumes the drain instead of re-admitting."""
        assert self.alive, f"replica {self.index} is already dead"
        self._prekill = self.lifecycle
        self.journal.close()
        self.engine = None
        self.lifecycle = ReplicaState.KILLED
        self.failovers += 1

    def restore(self) -> dict:
        """The full ISSUE 9 ladder: reload the journal (from disk when
        path-backed), rebuild a fresh engine through the factory, restore
        from the newest checkpoint — or replay the ENTIRE journal when
        none was cut — then re-attach the append handle so post-restore
        events keep journaling to the same file. The replica comes back
        in its pre-kill lifecycle state: a crash mid-drain resumes
        DRAINING (replay requeues its live requests, the cluster's drain
        pass hands them to peers and retires it), never admitting."""
        assert self.lifecycle is ReplicaState.KILLED, \
            f"replica {self.index} is not killed"
        if self.journal_path is not None:
            j = ControlJournal.load(self.journal_path)
            # .load() returns an in-memory journal: re-attach the file so
            # the restored replica keeps appending where it left off
            j.path = self.journal_path
            j._fh = open(self.journal_path, "a", encoding="utf-8")
        else:
            j = self.journal
        self.journal = j
        self.engine = self._build(j)
        stats = ckpt_mod.restore(self.engine, ckpt_mod.latest(j), j)
        self.lifecycle = self._prekill
        return stats

    def retire(self) -> None:
        """Terminal exit of a completed drain: close the journal, drop
        the engine. Unlike kill() there is nothing to restore — every
        request either finished (harvested) or was requeued to a peer."""
        assert self.lifecycle is ReplicaState.DRAINING, \
            f"replica {self.index} is not draining"
        self.journal.close()
        self.engine = None
        self.lifecycle = ReplicaState.RETIRED


class Cluster:
    """Deterministic router over N replicas (module docstring): cache-
    aware radix-hit affinity first (ISSUE 13), rendezvous hashing as the
    fallback, least-loaded tie-break, optional spill threshold,
    kill/restore through each replica's private journal."""

    def __init__(self, factory, replicas: int = 4,
                 journal_dir: str | None = None, prefix_tokens: int = 8,
                 spill_threshold: int | None = None, artifact=None,
                 affinity: bool = True, lend: bool = False,
                 lend_plan: "faults.FaultPlan | None" = None,
                 lend_deadline_steps: int = 4, lend_retries: int = 2):
        assert replicas >= 1
        # kept for elastic scale-up: add_replica() builds late joiners
        # through the same factory/journal_dir/artifact as the seed fleet
        self._factory = factory
        self._journal_dir = journal_dir
        self._artifact = artifact
        self.replicas = [EngineReplica(i, factory, journal_dir,
                                       artifact=artifact)
                         for i in range(replicas)]
        self.prefix_tokens = prefix_tokens
        self.spill_threshold = spill_threshold
        self.affinity = affinity
        # authoritative cluster prefix index (ISSUE 13 → promoted in
        # ISSUE 17): token runs of routed prompts map to the replica that
        # first served them. Two consumers: the router (radix-hit
        # affinity, gated by ``affinity`` so the lending tier can be
        # measured without routing help) and the page-lending tier. A
        # dead replica's entries are PRUNED by kill() — stale entries
        # would route, and worse LEND, against pages that no longer exist
        # — and stashed as tombstones that restore() re-warms from peers
        # and re-registers.
        self.prefix_index = ReplicaPrefixIndex(prefix_tokens)
        self._tombstones: dict[int, list[tuple[int, ...]]] = {}
        self.metrics = ServingMetrics()
        # the lending tier is imported lazily: lending.py is pure host
        # control plane over this module's duck-typed engine surface
        if lend:
            from triton_dist_tpu.serving.lending import PageLendingTier
            self.lending = PageLendingTier(
                self, plan=lend_plan,
                deadline_steps=lend_deadline_steps,
                max_retries=lend_retries)
        else:
            self.lending = None
        self._placement: dict[int, tuple[int, int]] = {}  # gid -> (ri, rid)
        self._rindex: dict[tuple[int, int], int] = {}     # (ri, rid) -> gid
        # gid -> (prompt, max_new_tokens, tenant, cls): enough to re-place
        # the request on a peer when its replica drains (ISSUE 18)
        self._requests: dict[
            int, tuple[tuple[int, ...], int, str | None, str | None]] = {}
        self._results: dict[int, list[int]] = {}
        self._failed: set[int] = set()
        self._next_gid = 0
        # elastic autoscaling (ISSUE 18): every membership event, append-
        # only — (cluster_step, kind, replica index). The Autoscaler
        # journals from this feed (cursor-read, so manual scale events in
        # tests/sims are journaled too); panels read it whole.
        self.scale_history: list[tuple[int, str, int]] = []
        # per-finish (cls, ttft_steps, itl_steps|None) — the autoscaler's
        # attainment sensor drains this; bounded so a run without an
        # autoscaler attached never grows it past the window
        self._latency_feed: deque = deque(maxlen=4096)
        self._cluster_steps = 0

    @property
    def admitting_replicas(self) -> list[EngineReplica]:
        """The router's candidate set: ACTIVE replicas only. Draining,
        warming, killed and retired replicas are all distinguishable
        here — none admit, but DRAINING ones still step and lend."""
        return [r for r in self.replicas if r.admitting]

    def lifecycle_counts(self) -> dict[str, int]:
        """Fleet composition by lifecycle state (panel/debug summary)."""
        out: dict[str, int] = {}
        for r in self.replicas:
            out[r.lifecycle.value] = out.get(r.lifecycle.value, 0) + 1
        return out

    def rendezvous_owner(self, prompt) -> int:
        """Load-free rendezvous winner for ``prompt`` over the current
        admitting set — the pure hash placement, no affinity index, no
        load tie-break. This is the function whose stability under
        membership change the O(1/N) churn tests pin: adding or removing
        one replica moves only the keys the new replica wins (or the
        removed replica owned), ≈ 1/N of a fixed population."""
        prompt = tuple(int(t) for t in prompt)
        key = prompt[:self.prefix_tokens] if self.affinity else prompt
        cands = self.admitting_replicas
        assert cands, "no admitting replicas"
        return max(cands, key=lambda r: (
            _fnv1a(0x811C9DC5, r.index, *key), -r.index)).index

    def route(self, prompt) -> EngineReplica:
        """Longest radix-index hit wins (the deepest run's replica most
        likely holds the prefix KV); rendezvous hashing with least-loaded
        tie-break handles misses and non-admitting affinity targets. Pure
        function of (index state, admitting set, prompt, load) — still
        deterministic through any schedule of scale events."""
        prompt = tuple(int(t) for t in prompt)
        cands = self.admitting_replicas
        assert cands, "no admitting replicas"
        owner = None
        if self.affinity:
            _, owner = self.prefix_index.match(prompt)
        if owner is not None and self.replicas[owner].admitting:
            pick = self.replicas[owner]
            self.metrics.inc("router_radix_hits")
        else:
            # affinity ON keys rendezvous by the shared prefix (a
            # template's requests co-locate even before its first index
            # entry); affinity OFF keys by the FULL prompt — same-prefix
            # requests scatter across the fleet, the adversarial placement
            # the lending tier must absorb (the ISSUE 17 acceptance:
            # cluster hit rate ≈ single-replica hit rate even then)
            key = prompt[:self.prefix_tokens] if self.affinity else prompt
            pick = max(cands, key=lambda r: (
                _fnv1a(0x811C9DC5, r.index, *key),
                -r.load, -r.index))
            self.metrics.inc("router_radix_misses")
        if (self.spill_threshold is not None
                and pick.load > self.spill_threshold):
            pick = min(cands, key=lambda r: (r.load, r.index))
        return pick

    def _place(self, gid: int, prompt, max_new_tokens: int,
               tenant: str | None, cls: str | None) -> EngineReplica:
        """Route + lend + index + submit + book one request under an
        existing gid — the shared tail of submit() and the drain-time
        requeue (which re-places a moved request under its ORIGINAL
        gid, so callers' handles survive the move)."""
        rep = self.route(prompt)
        if self.lending is not None:
            # borrower-side pre-warm (ISSUE 17): if a PEER owns this
            # prompt's deepest indexed prefix and the target replica's
            # cache misses, lend the pages NOW — the request's chunked
            # prefill then resumes past the adopted prefix, so the lend
            # latency overlaps admission instead of serializing with it
            self.lending.lend(rep, prompt)
        # first-writer-wins: runs this prompt ADDS stick to the replica
        # that actually received it, existing runs keep their owner
        self.prefix_index.insert(tuple(int(t) for t in prompt), rep.index)
        rid = rep.submit(prompt, max_new_tokens, tenant=tenant, cls=cls)
        self._placement[gid] = (rep.index, rid)
        self._rindex[(rep.index, rid)] = gid
        self._requests[gid] = (tuple(int(t) for t in prompt),
                               max_new_tokens, tenant, cls)
        return rep

    def submit(self, prompt, max_new_tokens: int,
               tenant: str | None = None, cls: str | None = None) -> int:
        gid = self._next_gid
        self._next_gid += 1
        self._place(gid, prompt, max_new_tokens, tenant, cls)
        self.metrics.inc("requests_submitted")
        return gid

    def step(self) -> bool:
        progressed = False
        # warming → active: promotion is a cluster-step event, so a
        # scale-up becomes routable at a deterministic point in the trace
        # (warm_remaining models the artifact-load window in step space)
        for rep in self.replicas:
            if rep.lifecycle is ReplicaState.WARMING:
                rep.warm_remaining -= 1
                if rep.warm_remaining <= 0:
                    rep.lifecycle = ReplicaState.ACTIVE
                    progressed = True
        stepped = 0
        for rep in self.replicas:
            if rep.alive and rep.lifecycle is not ReplicaState.WARMING:
                progressed |= rep.step()
                stepped += 1
        self.metrics.inc("replica_steps", stepped)
        self._cluster_steps += 1
        self._harvest()
        # drain pass: a DRAINING replica sheds its queue every step (the
        # journal-cursor requeue — normally once at drain_begin, again
        # after a crash-mid-drain restore replays its live requests) and
        # retires the step it reaches quiescence
        for rep in self.replicas:
            if rep.draining and rep.engine is not None:
                progressed |= self._requeue_queued(rep) > 0
                if rep.idle:
                    self._finish_drain(rep)
                    progressed = True
        return progressed

    def _harvest(self) -> None:
        for rep in self.replicas:
            if rep.engine is None:
                continue
            fin = rep.engine._finished
            if fin:
                for req in fin:
                    gid = self._rindex.get((rep.index, req.rid))
                    if gid is None:
                        continue
                    if gid not in self._results:
                        self.metrics.inc("requests_finished")
                        if (req.first_token_time is not None
                                and req.submit_time is not None):
                            self.metrics.observe(
                                "ttft_s",
                                req.first_token_time - req.submit_time)
                        self._observe_latency(req)
                    self._results[gid] = list(req.generated)
                rep.engine._finished = []
            for req in rep.engine.failed:
                gid = self._rindex.get((rep.index, req.rid))
                if gid is not None and gid not in self._failed:
                    self._failed.add(gid)
                    self.metrics.inc("failed_requests")

    def _observe_latency(self, req) -> None:
        """Deterministic step-space TTFT/ITL for one first-time finish —
        the per-class series the autoscaler's attainment windows sample
        (engine-local steps: both stamps come off the same clock, so a
        requeued request measures from its re-placement)."""
        if req.first_token_step is None or req.submit_step is None:
            return
        cls = getattr(req, "cls", None) or "default"
        ttft = req.first_token_step - req.submit_step
        self.metrics.observe("ttft_steps", ttft)
        self.metrics.observe_class("ttft_steps", cls, ttft)
        itl = None
        fin_step = getattr(req, "finish_step", None)
        if fin_step is not None and len(req.generated) > 1:
            itl = ((fin_step - req.first_token_step)
                   / (len(req.generated) - 1))
            self.metrics.observe_class("itl_steps", cls, itl)
        self._latency_feed.append((cls, ttft, itl))

    # -- elastic membership (ISSUE 18) -------------------------------------
    def _scale_event(self, kind: str, index: int) -> None:
        self.scale_history.append((self._cluster_steps, kind, index))

    def add_replica(self, warm_steps: int = 0) -> EngineReplica:
        """Grow the fleet: build a late joiner through the same factory
        (and AOT artifact — it reaches its first token with zero fresh
        traces, which is what makes mid-run scale-up affordable) under
        the next never-used index. The replica joins WARMING and is
        promoted to ACTIVE ``warm_steps`` cluster steps later (0 = the
        next step), so the membership change lands at a deterministic
        point in the trace."""
        assert warm_steps >= 0
        rep = EngineReplica(len(self.replicas), self._factory,
                            self._journal_dir, artifact=self._artifact)
        rep.lifecycle = ReplicaState.WARMING
        rep.warm_remaining = warm_steps
        self.replicas.append(rep)
        self.metrics.inc("scale_ups")
        self._scale_event("scale_up", rep.index)
        return rep

    def begin_drain(self, index: int) -> int:
        """Start a graceful drain: the replica stops admitting NOW and
        its queued (never-admitted) requests move to peers immediately —
        each one re-routed under its original gid, journaled as a
        ``requeue`` on the source engine so a crash after the move never
        re-serves it. In-flight PREFILLING/ACTIVE slots finish where
        they sit (their KV exists only there; determinism means a peer
        would regenerate identical tokens, but letting them run costs no
        correctness and no handoff). step()'s drain pass retires the
        replica at quiescence. Returns the number of requests moved."""
        rep = self.replicas[index]
        assert rep.admitting, (
            f"replica {index} is {rep.lifecycle.value}, not active")
        assert any(r.admitting and r.index != index for r in self.replicas), \
            "cannot drain the last admitting replica"
        rep.lifecycle = ReplicaState.DRAINING
        self._scale_event("drain_begin", index)
        return self._requeue_queued(rep)

    def _requeue_queued(self, rep: EngineReplica) -> int:
        """The journal-cursor requeue: pop every QUEUED request off the
        draining replica's intake (admitted slots stay — they finish
        in place) and re-place it on an admitting peer under the same
        gid. KV is never moved — the peer re-earns it from the prompt
        and the determinism contract regenerates identical tokens, the
        same restart-from-prompt argument restore runs on."""
        sched = rep._sched
        moved = 0
        # snapshot: _place mutates nothing on THIS replica, but pop first
        # so a reroute back here (impossible — it no longer admits) or an
        # assert can't leave the queue half-walked
        queued = list(sched.queue)
        for req in queued:
            gid = self._rindex.pop((rep.index, req.rid), None)
            if gid is None:
                continue    # replay artifact not booked here — drop
            sched.queue.remove(req)
            del self._placement[gid]
            rep.engine._jlog("requeue", rid=req.rid)
            prompt, mnt, tenant, cls = self._requests[gid]
            self._place(gid, prompt, mnt, tenant, cls)
            moved += 1
        if moved:
            self.metrics.inc("requeues", moved)
        return moved

    def _successor_of(self, prefix) -> EngineReplica | None:
        """Rendezvous successor for a drained prefix: the admitting
        replica that wins the SAME key route() would hash once the
        drainee is gone — so lend-ahead lands pages exactly where the
        prefix's future traffic will rendezvous."""
        prefix = tuple(int(t) for t in prefix)
        key = prefix[:self.prefix_tokens] if self.affinity else prefix
        cands = self.admitting_replicas
        if not cands:
            return None
        return max(cands, key=lambda r: (
            _fnv1a(0x811C9DC5, r.index, *key), -r.index))

    def _finish_drain(self, rep: EngineReplica) -> None:
        """Quiescence reached: hand the drainee's hot prefix-index
        entries to their rendezvous successors (drain-time lend-ahead,
        the PR 17 surface pushed instead of pulled), prune what could
        not move, retire."""
        # prune returns the drainee's owned prefixes; a crash-mid-drain
        # already pruned at kill time and stashed them on the replica
        tombs = list(rep._drain_prefixes)
        rep._drain_prefixes = []
        tombs += self.prefix_index.prune(rep.index)
        if self.lending is not None and tombs:
            placed = self.lending.lend_ahead(rep, tombs,
                                             self._successor_of)
            for prefix, succ in placed.items():
                # the successor now holds the pages warm — point the
                # index at it so the very next route() radix-hits there
                self.prefix_index.reassign(prefix, succ)
        rep.retire()
        self.metrics.inc("drains_done")
        self.metrics.inc("retires")
        self._scale_event("drain_done", rep.index)
        self._scale_event("retire", rep.index)

    def kill(self, index: int) -> None:
        self.replicas[index].kill()
        self.metrics.inc("faults_injected")
        self._scale_event("kill", index)
        # ISSUE 17 satellite: a dead replica's pages are gone — prune its
        # index entries so neither the router nor the lending tier targets
        # them, and stash the tombstoned prefixes for restore-time re-warm
        self._tombstones[index] = self.prefix_index.prune(index)

    def restore(self, index: int) -> dict:
        stats = self.replicas[index].restore()
        self.metrics.inc("restores")
        self._scale_event("restore", index)
        tombs = self._tombstones.pop(index, [])
        if self.replicas[index].draining:
            # crash-mid-drain fallback: the replica came back DRAINING —
            # it will never admit again, so re-warming its cache or
            # re-registering its index entries would aim traffic at a
            # retiree. Stash the kill-time tombstones instead: the drain
            # pass requeues the replayed queue to peers and finish_drain
            # lends THESE prefixes ahead to their successors.
            self.replicas[index]._drain_prefixes = tombs
            self._harvest()   # replayed finishes reappear — re-record
            return stats
        if self.lending is not None and tombs:
            # re-warm the restored replica's cache from peers instead of
            # letting every shared prefix re-prefill cold (deepest-first:
            # one lend covers every ancestor tombstone via early-out)
            self.lending.rewarm(self.replicas[index], tombs)
        # re-register only AFTER the restore (and re-warm, when lending)
        # verified: the checkpoint audit ran inside restore(), and the
        # re-warm adopts through the same audited ledger — re-check it
        # before the index points traffic back here. reassign OVERWRITES
        # the current owner, so a tombstone comes back only if the
        # restored cache actually holds it warm (a deep lend covers its
        # ancestor tombstones — the match sees them all) OR nobody else
        # claimed it mid-death (unowned affinity returns even cold: both
        # sides are equally cold, and entries are never dropped). A
        # prefix a peer claimed that the restoree could not re-warm —
        # every claimed prefix when lending is off, the cache being
        # empty by contract — stays with the peer that holds it warm;
        # first-writer-wins re-registers on the next submit routed here.
        eng = self.replicas[index].engine
        if tombs and getattr(eng, "alloc", None) is not None:
            eng.alloc.check()
        cache = getattr(eng, "prefix_cache", None)
        for prefix in tombs:
            warm = cache is not None and cache.match(prefix)
            if warm or self.prefix_index.match(prefix)[1] is None:
                self.prefix_index.reassign(prefix, index)
        self._harvest()   # replayed finishes reappear — re-record them
        return stats

    def drain(self, max_steps: int = 1_000_000) -> dict[int, list[int]]:
        for _ in range(max_steps):
            if not self.step():
                break
        return self.results()

    def results(self) -> dict[int, list[int]]:
        return dict(self._results)

    @property
    def failed_gids(self) -> set[int]:
        return set(self._failed)

    def drain_latency_feed(self) -> list[tuple[str, int, float | None]]:
        """Drain the per-finish (cls, ttft_steps, itl_steps) feed — the
        autoscaler's attainment sensor calls this once per step."""
        out = list(self._latency_feed)
        self._latency_feed.clear()
        return out


__all__ = ["Cluster", "EngineReplica", "ReplicaState", "SimEngine",
           "expected_tokens", "sim_token", "SIM_VOCAB"]
