"""The decode engine: drives ``models.llama.decode_multistep_paged``
under ``jax.jit`` so the hot loop is ONE compiled program per DISPATCH —
and one dispatch advances every slot up to ``decode_horizon`` tokens.

Shape discipline (the TPU contract):

- the batch is ``num_slots`` fixed rows; a request occupies one slot from
  admission to finish. Inactive rows are parked on the reserved scratch
  page (page 0) with pos 0 — their writes land on scratch, their tokens
  are ignored, and the compiled step never sees a shape change.
- the page pool rides the jitted step as a DONATED argument (on backends
  that support donation), so the per-layer scatter of the new (k, v)
  updates pages in place — no pool-sized copy per token.
- admission does no model math: an admitted request takes its prompt's
  pages and a slot in PREFILLING, and each ``step()`` runs AT MOST ONE
  ``prefill_chunk``-token chunk of the oldest such slot
  (``models.llama.prefill_chunk_paged``) beside the batched decode
  dispatch. The chunk writes its KV straight into pages through the block
  table and argmaxes the first token on device. Chunk size is the only
  shape (cursor and prompt length are runtime scalars), so ONE chunk
  program serves every prompt length — see the class docstring.

Device-resident hot loop (the host/device split):

- sampling is fused: the jitted program argmaxes on device and the host
  downloads a ``[horizon, num_slots]`` int32 token slab — never the
  ``[B, vocab]`` logits.
- ``token``/``pos``/``block_table`` live on device between dispatches;
  the host keeps numpy MIRRORS for control decisions (growth, finishes,
  preemption) and re-uploads only after a control-plane change (counted
  as ``host_syncs`` — a quiet dispatch uploads nothing but the per-slot
  ``limit`` word).
- ``decode_horizon=K`` runs K fused steps in one ``lax.scan`` dispatch;
  the per-slot ``limit`` input clamps each row to
  ``min(K, budget, pre-ensured page capacity)`` so no slot can outgrow
  its pages mid-scan, and rows freeze on EOS. The engine reconciles
  scheduler state (finishes, growth, preemption) every K tokens — K=1
  preserves per-token semantics exactly.

Determinism: greedy argmax decode + deterministic allocation and policies
mean a request's tokens are a pure function of (params, prompt) — a
preempted-and-restarted request regenerates exactly the tokens it lost,
and a contended run is bit-identical per request to an uncontended one,
at every horizon (tests/test_serving.py asserts both for K in {1, 4}).
"""

from __future__ import annotations

import json
import time
from collections import deque
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from triton_dist_tpu.ops.flash_decode import chunk_walk_counts
from triton_dist_tpu.serving import checkpoint as ckpt_mod
from triton_dist_tpu.serving import layouts, programs
from triton_dist_tpu.serving.deadline import Deadline, EngineStallError
from triton_dist_tpu.serving.journal import ControlJournal
from triton_dist_tpu.serving.kv_pool import KVPagePool, _fnv1a
from triton_dist_tpu.serving.metrics import Histogram, ServingMetrics
from triton_dist_tpu.serving.prefix_cache import PrefixCache
from triton_dist_tpu.serving.scheduler import (AdmissionRejected,
                                               ContinuousBatchingScheduler,
                                               Request, RequestState,
                                               SLOPolicy, TtlExpired)
from triton_dist_tpu.shmem import faults as faults_mod
from triton_dist_tpu.shmem.faults import InjectedCrash


# -- role-shared bookkeeping helpers ----------------------------------------
# The colocated engine plays BOTH serving roles; the disaggregated engine
# (serving/disagg.py) splits them across workers. These module-level
# helpers are the prefill-role half both share, so TTFT semantics cannot
# drift between the colocated and disaggregated paths.

def class_label(req: Request) -> str | None:
    """Per-class metric label for a request (ISSUE 14): None for the
    unclassed default so an engine without a policy emits exactly the
    pre-ISSUE-14 metric panel — labeled series are pay-for-play."""
    return req.cls if req.cls != "default" else None


def mark_prefill_start(req: Request, metrics: ServingMetrics,
                       step: int) -> None:
    """TTFT-split bookkeeping: queue time ends at FIRST admission
    (re-admissions after preemption keep the original clock)."""
    if req.prefill_start_time is None:
        req.prefill_start_step = step
        req.prefill_start_time = time.perf_counter()
        metrics.observe("ttft_queue_s",
                        req.prefill_start_time - req.submit_time)


def record_first_token(req: Request, metrics: ServingMetrics,
                       step: int) -> None:
    """First-token bookkeeping — TTFT clocks close where the token is
    COMPUTED (the prefill role), never where it is eventually served."""
    if req.first_token_time is None:
        req.first_token_step = step
        req.first_token_time = time.perf_counter()
        metrics.observe("ttft_s", req.first_token_time - req.submit_time)
        metrics.observe("ttft_prefill_s",
                        req.first_token_time - req.prefill_start_time)
        metrics.observe_class("ttft_s", class_label(req),
                              req.first_token_time - req.submit_time)


def check_prefill_chunk(prefill_chunk) -> int:
    """``prefill_chunk`` is a compiled shape, like ``page_size``: the rows
    of the one chunk program every prompt is prefilled through. There is
    no other admission path for ``None`` to select, so anything but a
    positive int is refused here, by name (the sharded engine sizes its
    A2A layers from the value before the base constructor runs, and asks
    first)."""
    if (isinstance(prefill_chunk, bool)
            or not isinstance(prefill_chunk, (int, np.integer))
            or prefill_chunk < 1):
        raise ValueError(
            f"prefill_chunk must be a positive int (the rows of the chunk "
            f"program every prompt is prefilled through); got "
            f"{prefill_chunk!r}")
    return int(prefill_chunk)


class _Ahead(NamedTuple):
    """A chunk launched for the step that follows: whose it is, the cursor
    it started at, and what ``_launch_chunk`` returned."""
    slot: int
    req: Request
    start: int
    chunk: tuple


class ServingEngine:
    """Continuous-batching serving engine over the paged decode step.

    ``num_pages`` counts usable pages; one extra scratch page (id 0) is
    allocated on top for inactive rows. ``pages_per_seq`` bounds one
    sequence's pages (the block table width — a compiled-shape constant).
    ``ffn(h, p) -> [B, D]`` plugs a custom per-layer FFN into the decode
    step (e.g. ``moe_mlp_ep_overlap`` for the EP-MoE serving path, the
    same hook ``decode_step``/``decode_step_sp`` expose).

    ``decode_horizon`` is K, the inner scanned steps per dispatch (see
    module docstring). ``eos_id`` enables early finish: a slot freezes on
    device the step it emits ``eos_id`` and the host finishes the request
    at reconcile.

    ``prefill_chunk`` is a compiled shape like ``page_size``: the rows of
    the ONE chunk program every prompt is prefilled through. An admitted
    slot enters PREFILLING holding its pages and a chunk cursor, and each
    ``step()`` dispatches AT MOST ONE chunk
    (``models.llama.prefill_chunk_paged``) alongside the batched decode
    dispatch — Sarathi-style co-scheduling that bounds the per-step
    decode stall by one chunk instead of a whole prompt. KV goes straight
    into pages through the block table and the first token's argmax is
    fused on device (no host logits download). A mid-prefill preemptee
    keeps its filled pages and resumes at its cursor; a prefix-cache hit
    jumps the cursor the same way.
    """

    def __init__(self, params: dict, cfg, num_slots: int = 4,
                 page_size: int = 16, num_pages: int = 64,
                 pages_per_seq: int = 8, ffn=None,
                 metrics: ServingMetrics | None = None,
                 decode_horizon: int = 1,
                 eos_id: int | None = None,
                 prefill_chunk: int = 16,
                 stall_deadline_steps: int = 256,
                 ffn_chunk=None, attn_io=None, linear=None,
                 journal: ControlJournal | None = None,
                 checkpoint_every: int | None = None,
                 queue_cap: int | None = None,
                 ttl_steps: int | None = None,
                 fault_plan=None,
                 prefix_cache: bool = False,
                 slo: SLOPolicy | None = None,
                 artifact=None, artifact_key: str | None = None,
                 speculate: int | str | None = None,
                 spec_hist: int = 64, spec_bucket: int = 0):
        assert decode_horizon >= 1
        prefill_chunk = check_prefill_chunk(prefill_chunk)
        assert stall_deadline_steps >= 1
        assert checkpoint_every is None or checkpoint_every >= 1
        assert queue_cap is None or queue_cap >= 1
        assert ttl_steps is None or ttl_steps >= 1
        # the model family (models.llama.PagedFamily), picked by the
        # config's type: its kind of page pool and the programs this engine
        # jits. What a family lacks is refused here, by name.
        fam = self._family = cfg.paged
        if fam.bind is not None:
            # a family with a second kind of page (PagedFamily.slot_ring)
            # sizes it for this engine's slots and chunk
            cfg = fam.bind(cfg, num_slots, prefill_chunk)
        asked = {
            "speculate": speculate not in (None, 0, "off"),
            "prefix_cache": bool(prefix_cache),
            "hooks": any(h is not None
                         for h in (ffn, ffn_chunk, attn_io, linear))}
        for option in fam.lacks:
            if asked[option]:
                raise NotImplementedError(
                    f"the {fam.name!r} model family ({type(cfg).__name__}) "
                    f"does not support {option}")
        # the formats the programs take their parameters in, leaf by leaf
        # (None: as they come). Chosen below, once the programs exist;
        # ``params``'s setter commits whatever it is given to them.
        self._formats = None
        self.params = params
        self.cfg = cfg
        self.page_size = page_size
        self.pages_per_seq = pages_per_seq
        self.num_slots = num_slots
        self.metrics = metrics or ServingMetrics()
        for name in fam.counters:
            self.metrics.counters.setdefault(name, 0)
        # the chunk program's K/V walks, a kind of attention layer each
        # (none: a latent pool's chunk, or an ``attn_io`` hook, which takes
        # the chunk as rows of decode): what ``_commit_chunk`` counts a
        # chunk's walked and edge pages from
        self._chunk_walks = tuple(fam.chunk_walks(cfg)) if (
            fam.chunk_walks and attn_io is None) else ()
        if self._chunk_walks:
            self.metrics.counters.setdefault("chunk_walk_pages", 0)
            self.metrics.counters.setdefault("chunk_walk_edge_pages", 0)
        # pages of the ring each slot owns in the family's bounded layers
        # (0: none). The ring is the slot's, not the ledger's: admission
        # needs a slot (which brings its ring) and ledger pages, and the
        # slot's first ring page rides one more column of its table row.
        self._ring = int(fam.slot_ring(cfg, page_size)) if fam.slot_ring \
            else 0
        if self._ring:
            self.metrics.hist.setdefault("kv_pages_full", Histogram())
            self.metrics.hist.setdefault("kv_pages_window", Histogram())
        # bytes of state that is not pages each slot owns (0: none): a
        # recurrent layer's fixed block a sequence (PagedFamily.slot_state).
        # Like a ring it is the slot's: slot s owns row 1 + s of the
        # family's state leaves, which rides the same extra table column.
        self._state_bytes = int(fam.slot_state(cfg)) if fam.slot_state \
            else 0
        assert not (self._ring and self._state_bytes), (
            "one extra table column: a ring or a state, not both")
        if self._state_bytes:
            self.metrics.hist.setdefault("state_bytes", Histogram())
        self._slot_owned = bool(self._ring or self._state_bytes)
        self.decode_horizon = decode_horizon
        self.eos_id = eos_id
        self._stall_steps = stall_deadline_steps
        # speculative decoding (ISSUE 20): speculate = draft length K
        # (int), "auto" (PR 15 registry → default), or None/0/"off".
        # When on, the decode program is decode_speculate_paged — ONE
        # dispatch drafts K-1 tokens, verifies all K positions in one
        # paged-attention pass, and commits the longest draft==argmax
        # prefix; decode_horizon doubles as K so the limits clamp
        # (min(horizon, remaining, page headroom)) bounds the accept
        # burst exactly as it bounds the multistep scan.
        self.spec_k = 0
        self.spec_hist = int(spec_hist)
        if speculate not in (None, 0, "off"):
            assert decode_horizon == 1, (
                "speculate replaces the multistep scan — the verify pass "
                "scores K positions per dispatch, so decode_horizon must "
                "stay 1 when speculation is on")
            assert self.spec_hist >= 8, (
                "spec_hist must be >= 8 — a shorter drafter window cannot "
                "hold a bigram plus its continuation")
            from triton_dist_tpu.serving.speculate import resolve_spec_k
            self.spec_k = resolve_spec_k(
                speculate, getattr(self, "_spec_mesh_shape", ()),
                str(jnp.dtype(cfg.dtype)), spec_bucket)
            self.decode_horizon = self.spec_k

        self.pool = fam.init_pool(cfg, num_pages + 1, page_size)
        if not self._slot_owned:
            # a gauge: bytes a cached token holds over ALL planes, from the
            # pool's leaves [plane, page, ...] (every one the ledger's pages
            # here) and never from the config's layers: a family's planes may
            # outnumber them (``PagedFamily.walks``)
            self.metrics.counters["kv_bytes_per_token"] = sum(
                a.nbytes // (a.shape[1] * page_size)
                for a in jax.tree_util.tree_leaves(self.pool))
        # unified pool contract (ISSUE 12): subclasses that shard the pool
        # arrays over SP set _pool_sp_ranks BEFORE super().__init__ so the
        # ledger knows the padded device page range (padding pages are
        # never handed out and never check_migratable-accepted)
        self.alloc = KVPagePool(num_pages + 1, page_size, reserved=1,
                                sp_ranks=getattr(self, "_pool_sp_ranks", 1),
                                layout=getattr(self, "_pool_layout",
                                               "blocked"))
        # prefix cache (ISSUE 13): a radix index over full-page token
        # runs of this pool's pages. Host-side control plane only — it
        # changes WHICH pages a block table points at, never what the
        # compiled programs look like, so compile counts and the sigcheck
        # lint are identical with it on or off.
        self.prefix_cache = PrefixCache(self.alloc, page_size) \
            if prefix_cache else None
        # cluster page lending (ISSUE 17): pages adopted FROM a peer
        # (splits rewarmed TTFT out of cached), the transient seq-id
        # generation adopt_prefix allocates under, and the rids whose
        # admission hit landed on lent pages
        self._lent_pages: set[int] = set()
        self._lend_gen = 0
        self._rewarmed_rids: set[int] = set()
        # multi-tenant SLO policy (ISSUE 14): entirely control-plane —
        # the policy changes WHICH request a slot admits and how many
        # prompt tokens a step co-schedules, never what the compiled
        # programs look like (zero new programs; compile_stats is flat).
        self.slo = slo
        # the smallest per-step prefill budget any class declares — the
        # deadline-aware chunk floor is pure configuration, precomputed
        self._stall_budgeted = slo is not None and any(
            c.stall_budget is not None for c in slo.classes)
        self.sched = ContinuousBatchingScheduler(num_slots,
                                                 queue_cap=queue_cap,
                                                 policy=slo)
        self._next_rid = 0
        self._steps = 0
        self._finished: list[Request] = []

        # crash consistency (ISSUE 9): the journal is the durable
        # artifact — a fresh engine + journal (which embeds periodic
        # checkpoints) reconstructs bit-identical serving state. See
        # serving/journal.py and serving/checkpoint.py.
        self.journal = journal
        self.checkpoint_every = checkpoint_every
        self.ttl_steps = ttl_steps
        self._fault_plan = fault_plan
        self._journal_muted = False     # True while replaying (restore)
        self._replaying = False         # replayed submits bypass the cap
        self._incarnation = 0           # bumped per restore (crash keying)
        self._preempt_hook = None       # composition override (ISSUE 12)
        self._last_ckpt_step = -1
        self._rejected: list[Request] = []

        # host-side mirrors of the per-slot device state (control plane);
        # the device copies below are authoritative between dispatches
        self._token = np.zeros(num_slots, np.int32)
        self._pos = np.zeros(num_slots, np.int32)
        self._bt = np.zeros((num_slots, pages_per_seq + self._slot_owned),
                            np.int32)
        # drafter history window [B, H] (newest token at column H-1) +
        # valid-suffix lengths. Device-carried between dispatches when
        # speculation is on; the host mirrors the device's roll bitwise
        # so the hot path never re-uploads it (host_syncs stays flat).
        self._hist = np.zeros((num_slots, self.spec_hist), np.int32)
        self._hist_len = np.zeros(num_slots, np.int32)
        self._sync_mirrors()
        self._dirty = False                 # mirrors diverged from device
        # the table mirror ALONE diverged (``_grow`` wrote a row the ledger
        # grew): token and position still equal the device's carry
        self._bt_dirty = False
        # (rid, the ledger's stamp of its pages) as ``_grow`` last mirrored
        # them into the slot's table row, or None where another hand wrote
        # the row since (seating, parking; a composing engine that seats a
        # request itself seats a rid no record names): ``_grow`` looks at a
        # row again only when this no longer names the ledger's state
        self._bt_seen: list[tuple | None] = [None] * num_slots
        # the NEXT step's chunk, launched behind this step's decode dispatch
        # and not committed yet (``_launch_ahead``), or None
        self._ahead: _Ahead | None = None

        # the programs: built once a process for this key, shared by every
        # engine of it (serving/programs.py). The hooks: attn_io/linear are
        # the sharded engine's SP attention and TP projections; ffn_chunk is
        # a chunk-row-count FFN distinct from the decode one, needed when the
        # FFN is shape-specialized like the EP a2a dispatch.
        self.prefill_chunk = prefill_chunk
        ps = getattr(self, "_pool_out_sharding", None)
        # the pool as the programs meet it: the sharded subclass (which set
        # ``_pool_out_sharding`` before this ctor) pads the page dim up to a
        # multiple of |sp| right after it returns (unified pool contract)
        sp = getattr(self, "_pool_sp_ranks", 1)
        pool_met = {
            k: jax.ShapeDtypeStruct(
                v.shape[:1] + (v.shape[1] + (-v.shape[1]) % sp,)
                + v.shape[2:], v.dtype)
            for k, v in self.pool.items()}
        self._step, self._chunk_step, self._formats, lint = \
            programs.engine_programs(
                cfg, self.decode_horizon, eos_id, self.spec_k,
                self.spec_hist, prefill_chunk, num_slots, self._bt.shape[1],
                programs.signature(pool_met),
                programs.signature(self.params),
                (ffn, ffn_chunk, attn_io, linear), ps,
                ps is None and artifact is None)
        held = []
        if self._formats is not None:
            held = layouts.relaid(self.params, self._formats)
            # a format names its device, so these programs' outputs are
            # COMMITTED to it: so is the pool from the start, or the chunk
            # program would be compiled for each of the two
            self.pool = jax.device_put(self.pool, jax.tree_util.tree_leaves(
                self._formats)[0].sharding)
            self.params = params            # committed by the setter
        # what the engine holds in another layout than the device's default
        # (0 bytes: the mechanism did nothing, as on the CPU)
        self._relaid_leaves = [h["leaf"] for h in held]
        self.metrics.counters["params_relaid_bytes"] = sum(
            h["bytes"] for h in held)

        programs.lint_if_asked(lint, self)

        # AOT artifact seeding (ISSUE 15): swap the freshly-built jit
        # objects for the artifact's deserialized programs so a cold start
        # reaches first token with ZERO fresh traces of the model code —
        # compile_stats reports the swap via the ``aot_programs`` key and
        # the replaced programs' trace caches stay at size 0 by
        # construction (LoadedProgram never traces its source).
        self._aot_artifact = artifact
        if artifact is not None:
            self._seed_from_artifact(artifact, artifact_key)

    @property
    def params(self):
        """The weights, as the programs take them. Setting them commits each
        leaf to the format the decode program was compiled for (a copy of a
        leaf that is held in another; the caller's tree is left alone)."""
        return self._params

    @params.setter
    def params(self, tree) -> None:
        self._params = tree if self._formats is None else layouts.commit(
            tree, self._formats)

    # -- AOT artifact (ISSUE 15) ------------------------------------------
    def _default_artifact_key(self) -> str:
        return "colocated"

    def _seed_from_artifact(self, artifact, artifact_key: str | None) -> None:
        key = artifact_key or self._default_artifact_key()
        self._step = artifact.program(key, "decode")
        self._chunk_step = artifact.program(key, "chunk")

    def _upload(self, mirror: np.ndarray):
        """One host mirror as the programs take it. The sharded engine
        COMMITS the upload to the mesh (matching the jit out_shardings pin):
        pjit's executable cache keys on input sharding/committed-ness, so a
        flip between an uncommitted first upload and the committed fed-back
        outputs would cost one spurious recompile per program."""
        return jnp.asarray(mirror)

    def _sync_mirrors(self, table_only: bool = False) -> None:
        """Upload the host slot mirrors to the device copies: all of them,
        or the table alone where nothing but page growth touched a mirror
        since the last upload (token and position then equal the device's
        carry by construction, see ``_reconcile``)."""
        self._bt_dev = self._upload(self._bt)
        if table_only:
            return
        self._token_dev = self._upload(self._token)
        self._pos_dev = self._upload(self._pos)
        if self.spec_k:
            self._hist_dev = self._upload(self._hist)
            self._hlen_dev = self._upload(self._hist_len)

    # -- ledger id → device row (ISSUE 19) --------------------------------
    # The ledger allocates in ID space; the device arrays are indexed in
    # ROW space (``KVPagePool.device_row`` — identity under the default
    # blocked layout, the round-robin bijection under the long-context
    # interleaved layout). EVERY id that crosses the host→device boundary
    # — block-table uploads and host-side pool gathers/scatters — goes
    # through ``alloc.device_rows`` (one array operation) or ``device_row``;
    # journal/digest/snapshot payloads stay in id space, so the
    # control-plane trace is layout-independent.

    def _device_bt_row(self, rid, slot: int) -> np.ndarray:
        """The table row the programs get for ``rid`` in ``slot``: its
        ledger pages, then (a family with per-slot rings) the first page of
        the slot's ring, ``1 + slot * ring`` (page 0 is scratch there too),
        or (a family with per-slot state) the slot's state row, ``1 + slot``."""
        row = self.alloc.device_rows(
            self.alloc.block_table_row(rid, self.pages_per_seq))
        if self._slot_owned:
            row = np.append(row, np.int32(1 + slot * (self._ring or 1)))
        return row

    # -- request intake ---------------------------------------------------
    def _ttl_for(self, req: Request) -> int | None:
        """Effective TTL: the class's override when the policy sets one,
        else the engine-global ``ttl_steps``."""
        spec = self.sched.class_spec(req)
        if spec is not None and spec.ttl_steps is not None:
            return spec.ttl_steps
        return self.ttl_steps

    def submit(self, prompt, max_new_tokens: int, rid: int | None = None,
               tenant: str | None = None, cls: str | None = None) -> int:
        if rid is None:
            rid = self._next_rid
        with self.metrics.phase("submit", rid=rid):
            return self._submit(prompt, max_new_tokens, rid, tenant, cls)

    def _submit(self, prompt, max_new_tokens: int, rid: int,
                tenant: str | None, cls: str | None) -> int:
        prompt = tuple(int(t) for t in np.asarray(prompt).reshape(-1))
        assert prompt and max_new_tokens >= 1
        total = len(prompt) + max_new_tokens - 1   # KV the request may hold
        need = -(-total // self.page_size)
        assert need <= self.pages_per_seq, (
            f"request needs {need} pages > pages_per_seq "
            f"{self.pages_per_seq}")
        assert need <= self.alloc.num_pages - self.alloc.reserved, (
            f"request needs {need} pages > pool size — it could never run "
            "even alone")
        self._next_rid = max(self._next_rid, rid + 1)
        req = Request(rid=rid, prompt=prompt, max_new_tokens=max_new_tokens,
                      eos_token=self.eos_id,
                      submit_step=self._steps,
                      submit_time=time.perf_counter())
        self.sched.stamp(req, tenant, cls)
        self.metrics.inc("requests_submitted")
        self.metrics.inc_class("requests_submitted", class_label(req))
        # bounded admission (ISSUE 9/14): shed fresh arrivals when the
        # queue — global or THIS CLASS's budget — is at capacity. A typed
        # terminal naming the class, never an exception into the
        # submitter. Journal replay bypasses the cap: the journal already
        # holds the authoritative accept/reject decisions.
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

    # -- admission + chunked paged prefill (the PREFILLING state machine) -
    def _mark_prefill_start(self, req: Request) -> None:
        mark_prefill_start(req, self.metrics, self._steps)

    def _cache_adopt(self, req: Request) -> None:
        """Prefix-cache admission half (ISSUE 13): match the prompt
        against the radix index and ADOPT the hit pages — refcounts bump,
        the block table will point at them, and chunked prefill resumes
        at the first miss (the same cursor mechanics a mid-prefill
        preemptee uses). On a whole-prompt hit only the LAST position is
        recomputed (its fused argmax is the first token); that write
        lands in the final adopted page, so the page is COWed first when
        shared — the one organic divergence point on the colocated path.
        Only a FRESH admission matches: a preemptee resuming at its
        cursor already owns its pages."""
        cache = self.prefix_cache
        if cache is None or req.prefill_cursor > 0 \
                or self.alloc.holds(req.rid):
            return
        hit = cache.match(req.prompt)
        sp = len(req.prompt)
        if not hit:
            self.metrics.inc("prefix_misses")
            return
        self.alloc.acquire(req.rid, hit)
        hit_tokens = len(hit) * self.page_size
        if hit_tokens >= sp:
            # whole prompt cached — resume at sp-1, never sp: the final
            # chunk must still run for its on-device first-token argmax
            req.prefill_cursor = sp - 1
            self._cow_writable(req, (sp - 1) // self.page_size)
        else:
            req.prefill_cursor = hit_tokens
        req.cache_hit_tokens = req.prefill_cursor
        self.metrics.inc("prefix_hits")
        self.metrics.inc("prefix_hit_tokens", req.prefill_cursor)
        if any(p in self._lent_pages for p in hit):
            # the hit rode pages a peer lent us — TTFT reports as
            # "rewarmed", the kill/restore acceptance band (ISSUE 17)
            self._rewarmed_rids.add(req.rid)

    def _reclaim(self, n_pages: int) -> None:
        """Refill the free list to ``n_pages`` by LRU-evicting cached
        (refcount-0) pages — the reclaim that composes BEFORE
        youngest-victim preemption. No-op when already covered or the
        cache is off/empty."""
        short = n_pages - self.alloc.free_pages
        if short > 0 and self.prefix_cache is not None:
            self.metrics.inc("prefix_evictions",
                             self.prefix_cache.evict(short))

    def _cow_writable(self, req: Request, page_index: int) -> None:
        """Copy-on-write guard: ``req`` is about to WRITE into its
        ``page_index``-th page. Shared (refcount > 1) pages get a fresh
        page swapped into the ledger and their bytes copied on device —
        eager array ops, NOT a jitted program, so the one-program-per-
        path compile contract is untouched. Sole-owned pages write in
        place (greedy determinism makes the rewrite bit-identical, so
        the index mapping stays valid)."""
        pid = self.alloc.pages_of(req.rid)[page_index]
        if self.alloc.refcount(pid) <= 1:
            return
        self._reclaim(1)
        res = self.alloc.cow_page(req.rid, page_index)
        assert res is not None, "admissible() guaranteed a COW page"
        old, new = res
        # the chunk's attention reads this page's earlier rows through
        # the patched block-table row, so the copy must precede dispatch
        self._copy_page(old, new)
        self.metrics.inc("cow_copies")

    # -- the pool's bytes, whatever its kind ------------------------------
    # The pool is a pytree of arrays [layer, page, ...] (K and V of a GQA
    # family, one latent array of an MLA one): these three map over its
    # leaves. Eager array ops, NOT jitted programs, so the one-program-per-
    # path compile contract is untouched.

    def _pages_alone(self, what: str) -> None:
        """A sequence of a family with per-slot state is its pages AND its
        slot's state: an operation that moves pages alone is refused."""
        if self._state_bytes:
            raise NotImplementedError(
                f"the {self._family.name!r} model family keeps state that "
                f"is not pages: {what} is not supported")

    def _copy_page(self, old: int, new: int) -> None:
        """Copy ledger page ``old`` onto ``new``, every layer."""
        self._pages_alone("page copy")
        o, w = self.alloc.device_row(old), self.alloc.device_row(new)
        self.pool = jax.tree.map(lambda a: a.at[:, w].set(a[:, o]),
                                 self.pool)

    def _export_pages(self, page_ids):
        """The bytes of ``page_ids`` (ledger ids): the pool's pytree with
        the page dim gathered, [layer, len(page_ids), ...] a leaf."""
        self._pages_alone("page export")
        rows = self.alloc.device_rows(page_ids)
        return jax.tree.map(lambda a: a[:, rows], self.pool)

    def _import_pages(self, page_ids, payload) -> None:
        """Land ``payload`` (as ``_export_pages`` gives it) on
        ``page_ids``."""
        self._pages_alone("page import")
        rows = self.alloc.device_rows(page_ids)
        self.pool = jax.tree.map(lambda a, b: a.at[:, rows].set(b),
                                 self.pool, payload)

    # -- cluster page lending (ISSUE 17, serving/lending.py drives) -------
    def export_prefix(self, prompt, payload: bool = True):
        """Lender half: the longest locally cached full-page prefix of
        ``prompt`` that ``KVPagePool.check_lendable`` accepts (refcount-0
        AND index-retained — no live sequence can observe the copy), plus
        the page payload. Returns ``(tokens, page_ids, payload)`` where
        payload is the gathered K/V bytes — the host-mediated twin of the
        per-(layer, page) puts ``ops.lend_pages`` issues on a device
        mesh. Gathers are eager array ops, so the one-program-per-path
        compile contract is untouched (same argument as _cow_writable).
        ``payload=False`` is the cheap depth-only probe (peer selection
        in ``PageLendingTier.rewarm``): no bytes are gathered."""
        if self.prefix_cache is None:
            return 0, [], None
        prompt = tuple(int(t) for t in prompt)
        hit = self.prefix_cache.match(prompt)
        n = self.alloc.check_lendable(hit)
        if n == 0:
            return 0, [], None
        if not payload:
            return n * self.page_size, hit[:n], None
        return n * self.page_size, hit[:n], self._export_pages(hit[:n])

    def adopt_prefix(self, prompt, n_tokens: int, payload=None) -> int:
        """Borrower half: land a peer's prefix pages locally. Fresh pages
        are allocated under a transient lend seq-id, the payload bytes
        scattered in (eager ``.at[].set`` — no new programs), the runs
        indexed, and the pages released to the cached LRU — from here on
        they are ordinary cached pages (admission adopts, COW guards,
        eviction reclaims). Returns pages newly adopted; 0 degrades to
        local prefill on the caller's side, never a stall."""
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
        if payload is not None:
            # the lender exported `want` pages; ours start past the
            # local hit depth
            self._import_pages(got, jax.tree.map(
                lambda b: b[:, len(have):want], payload))
        # first len(have) entries ride existing trie edges (insert is
        # first-writer-wins); the fresh pages take the deeper runs
        cache.insert(prompt[:want * self.page_size], have + got)
        self.alloc.free_seq(sid)    # refcount-0 + cacheable → cached LRU
        self._lent_pages.update(got)
        self._jlog("lend", tokens=want * self.page_size, pages=need)
        return need

    def _admit(self, slot: int, req: Request) -> None:
        """Admission does NO prefill math: adopt any cached
        prefix pages (refcount bump + cursor jump), allocate the prompt's
        remaining pages (only the ones the request does not already own —
        a mid-prefill preemptee kept its filled pages and resumes at its
        cursor) and park the slot in PREFILLING. The chunks themselves
        run one per engine step, co-scheduled with decode."""
        self._cache_adopt(req)
        sp = len(req.prompt)
        n_pages = -(-sp // self.page_size)
        have = len(self.alloc.pages_of(req.rid))
        if n_pages > have:
            self._reclaim(n_pages - have)
            got = self.alloc.alloc(req.rid, n_pages - have)
            assert got is not None, "admissible() guaranteed the pages"
        self.sched.activate(slot, req)
        self._jlog("admit", rid=req.rid, slot=slot)
        req.state = RequestState.PREFILLING
        self._mark_prefill_start(req)
        self.metrics.inc("prefills")
        # slot mirrors stay parked (scratch page) until the LAST chunk
        # lands — the chunk program carries its own block-table argument,
        # so the decode batch never sees a half-prefilled row

    def _step_prefill_budget(self) -> int | None:
        """Deadline-aware chunk sizing (ISSUE 14): the prompt tokens this
        step may co-schedule with decode, i.e. the tightest
        ``stall_budget`` over the classes currently DECODING (their ITL
        is what a long chunk stalls). None = no budget (no policy, no
        budgeted class decoding). A pure function of scheduler state —
        deterministic, digest-covered, crash-replayable."""
        if not self._stall_budgeted:
            return None
        budget = None
        for _, r in self.sched.active:
            if r.state is not RequestState.ACTIVE:
                continue
            spec = self.sched.class_spec(r)
            if spec is not None and spec.stall_budget is not None:
                budget = spec.stall_budget if budget is None \
                    else min(budget, spec.stall_budget)
        return budget

    def _oldest_prefilling(self):
        """The slot whose prompt advances this step: the oldest (lowest
        admission ticket) PREFILLING one. (None, None) = no prefill work."""
        slot, req = None, None
        for i, r in enumerate(self.sched.slots):
            if (r is not None and r.state is RequestState.PREFILLING
                    and (req is None or r.admitted_seq < req.admitted_seq)):
                slot, req = i, r
        return slot, req

    def _launch_chunk(self, slot: int, req: Request):
        """Launch ONE prefill chunk of ``req`` (a step runs at most one):
        ``_commit_chunk`` advances its cursor by one chunk. Returns (the
        token on device, the new cursor, the slot's table row, prompt
        tokens processed). Only a prompt's LAST chunk (new cursor ==
        ``len(req.prompt)``) has a token anybody reads.

        Deadline-aware sizing (ISSUE 14): when a stall-budgeted class is
        decoding, the EFFECTIVE chunk shrinks to its budget — same
        compiled program, fewer real tokens: rows past the reduced
        ``prompt_len`` scalar park on the scratch page exactly like the
        final-chunk padding always has, so KV for the processed prefix
        is bit-identical and ``compile_stats`` stays flat (the scalar is
        a runtime argument, not a shape).
        """
        C = self.prefill_chunk
        budget = self._step_prefill_budget()
        # the prefilling request's OWN class chunk budget (ISSUE 19):
        # a long-context tier drips its 64k prompt through admission at
        # its declared per-step rate even when nothing is decoding
        spec = self.sched.class_spec(req)
        own = spec.chunk_budget if spec is not None else None
        c_eff = C
        for b in (budget, own):
            if b is not None:
                c_eff = min(c_eff, b)
        c_eff = max(1, c_eff)
        if c_eff < C:
            self.metrics.inc("chunk_shrinks")
        sp = len(req.prompt)
        start = req.prefill_cursor
        # the chunk this step actually advances: c_eff real tokens; the
        # compiled program masks rows past n_eff (they write nothing)
        n_eff = min(start + c_eff, sp)
        toks = np.zeros(C, np.int32)
        part = req.prompt[start:n_eff]
        toks[:len(part)] = part
        if self.prefix_cache is not None:
            # COW guard over the chunk's write range: the chunk program
            # never touches a page with refcount > 1 (ISSUE 13). The
            # admission-time guard already covered the whole-prompt-hit
            # rewrite, so these are no-ops unless a new sharing path
            # appears — cheap insurance on the invariant.
            end = n_eff
            for i in range(start // self.page_size,
                           (end - 1) // self.page_size + 1):
                self._cow_writable(req, i)
        row = self._device_bt_row(req.rid, slot)
        tok_dev, self.pool = self._chunk_step(
            self.params, jnp.asarray(toks),
            jnp.asarray(start, jnp.int32), jnp.asarray(n_eff, jnp.int32),
            self.pool, jnp.asarray(row))
        return tok_dev, n_eff, row, len(part)

    def _launch_ahead(self) -> None:
        """Launch the chunk the NEXT step will run, now: behind this step's
        decode dispatch and before the host blocks on its slab, so that the
        device goes from the decode program straight into the chunk and the
        slab's readback, ``reconcile``, ``post``, the caller's loop and the
        next ``admit`` run under it. Only the host's order of launches
        moves: the pool orders the chunk behind the decode program and the
        next decode program behind the chunk, as the launches at the usual
        place did.

        Done only where the next step's choice is fixed already: a request
        is PREFILLING (the coming ``admit`` hands out younger tickets alone,
        so the oldest stays the oldest, and a slot decoding now cannot go
        back to prefilling but through ``_preempt``, which drops the launch)
        and no stall-budgeted class is decoding (that budget is a function
        of who still decodes after the slab: such a chunk is sized and
        launched at the usual place; a class's own ``chunk_budget`` is the
        request's). NOTHING is committed here: cursor, counters, journal and
        digest move in the next step's ``grow``, where they always did, so
        between the two steps the engine is the parent's plus this record.
        ``_preempt`` of its owner and ``_restore_state`` drop it (the chunk
        is redone from the cursor it never moved); ``checkpoint()`` is
        host-only and does not see it; a final chunk's token is read by
        the next step's ``chunk_wait``."""
        slot, req = self._oldest_prefilling()
        if req is None or self._step_prefill_budget() is not None:
            return
        start = req.prefill_cursor
        with self.metrics.phase("chunk_prep", step=self._steps, rid=req.rid,
                                cursor=start):
            self._ahead = _Ahead(slot, req, start,
                                 self._launch_chunk(slot, req))

    def _commit_chunk(self, slot: int, req: Request, tok0: int | None,
                      n_eff: int, row) -> None:
        """Advance the cursor past a launched chunk. A chunk that is not the
        prompt's last has no token (``tok0`` is None) and may still be
        running: the programs that follow queue behind it on the pool it
        writes. A prompt's final chunk fused the first-token argmax on
        device, so ``tok0`` IS the first token and the slot flips to ACTIVE
        (mirrors set, ready for this step's decode dispatch)."""
        sp = len(req.prompt)
        for layers, rows, window in self._chunk_walks:
            # the pages this chunk's row blocks walked in every layer of the
            # kind, and those of them that took the masked update: the plan
            # the kernel walks by, from the cursor and the length alone
            pages, edge = chunk_walk_counts(
                req.prefill_cursor, n_eff - req.prefill_cursor,
                self.prefill_chunk, rows, self.page_size, window,
                self.pages_per_seq)
            self.metrics.inc("chunk_walk_pages", layers * pages)
            self.metrics.inc("chunk_walk_edge_pages", layers * edge)
        req.prefill_cursor = n_eff
        self.metrics.inc("prefill_chunks")
        self._jlog("chunk", rid=req.rid, cursor=req.prefill_cursor)
        if tok0 is None:
            self.metrics.inc("chunks_not_awaited")
            return
        # last chunk → the slot starts decoding this very step
        req.state = RequestState.ACTIVE
        req.generated.append(tok0)
        self.metrics.inc("tokens_generated")
        if self.prefix_cache is not None:
            # index the finished prompt's full pages BEFORE decode grows
            # the sequence — later identical prompts adopt them. The
            # partial last page (still being written by decode) is never
            # indexed; already-indexed runs keep their existing mapping.
            self.prefix_cache.insert(
                req.prompt,
                self.alloc.pages_of(req.rid)[:sp // self.page_size])
            if req.first_token_time is None:
                kind = ("ttft_rewarmed_s"
                        if req.rid in self._rewarmed_rids
                        else "ttft_cached_s" if req.cache_hit_tokens
                        else "ttft_cold_s")
                self._rewarmed_rids.discard(req.rid)
                self.metrics.observe(kind,
                                     time.perf_counter() - req.submit_time)
        record_first_token(req, self.metrics, self._steps)
        self._token[slot] = tok0
        self._pos[slot] = sp
        self._bt[slot] = row
        self._bt_seen[slot] = None
        self._seed_hist(slot, req)
        self._dirty = True
        if req.done:            # max_new_tokens == 1 or tok0 == eos_id
            self._finish(slot)

    # -- slot teardown ----------------------------------------------------
    def _finish(self, slot: int) -> None:
        req = self.sched.finish(slot)
        self.alloc.free_seq(req.rid)
        req.finish_step = self._steps
        self._park(slot)
        self._finished.append(req)
        self.metrics.inc("requests_finished")
        self.metrics.inc_class("requests_finished", class_label(req))
        # the finished tokens ride the journal so a post-checkpoint finish
        # survives a crash without re-running the request; the terminal
        # metadata rides along so the restored record stays faithful
        self._jlog("finish", rid=req.rid, tokens=list(req.generated),
                   submit_step=req.submit_step,
                   first_token_step=req.first_token_step,
                   preemptions=req.preemptions)

    def _preempt(self, slot: int) -> None:
        req = self.sched.slots[slot]
        if self._ahead is not None and self._ahead.req is req:
            # a chunk launched ahead and not committed: the victim's cursor
            # never moved past it, so it is redone after re-admission (what
            # it wrote lies in pages freed here, or in a slot that restarts)
            self._ahead = None
        # composition hook (ISSUE 12): a wrapping engine (compose.py) may
        # own this slot's request — MIGRATING seats hold pages in a pool
        # this engine cannot see — and takes over the eviction when so
        hook = self._preempt_hook
        if hook is not None and hook(slot, req):
            return
        # (a family with per-slot rings or state restarts instead: what
        # those layers computed stays behind in the slot the victim leaves,
        # and a state cannot be rewound to a cursor)
        if (req.state is RequestState.PREFILLING and req.prefill_cursor > 0
                and not self._slot_owned):
            filled = -(-req.prefill_cursor // self.page_size)
            if filled < len(self.alloc.pages_of(req.rid)):
                # mid-prefill victim: keep the pages already holding
                # computed KV, reclaim only the unfilled tail — the
                # request requeues AT ITS CHUNK CURSOR and resumes there
                # on re-admission (not at the prompt start)
                self.alloc.free_tail(req.rid, keep=filled)
            else:
                # every owned page is filled — there is no tail to
                # reclaim, so holding them would free nothing: full
                # restart (frees all pages, guaranteed progress for the
                # grower that triggered the preemption)
                self.alloc.free_seq(req.rid)
                req.prefill_cursor = 0
        else:
            self.alloc.free_seq(req.rid)
            req.prefill_cursor = 0      # a decoding victim re-prefills
        self.sched.evict(slot)
        self._park(slot)
        self.metrics.inc("preemptions")
        self._jlog("preempt", rid=req.rid, slot=slot)

    def _ensure_pages(self, rid, kv_len: int) -> bool:
        """``KVPagePool.ensure`` with cache headroom: LRU-evict cached
        pages before declaring the pool dry, so eviction composes BEFORE
        youngest-victim preemption (a refcount-0 cached page is always a
        cheaper reclaim than restarting a live request)."""
        while not self.alloc.ensure(rid, kv_len):
            if self.prefix_cache is None:
                return False
            freed = self.prefix_cache.evict(1)
            if not freed:
                return False
            self.metrics.inc("prefix_evictions", freed)
        return True

    def _park(self, slot: int) -> None:
        """Point an empty slot at the scratch page: its row writes land on
        page 0 (reserved — never a live sequence's), its reads mask out."""
        self._token[slot] = 0
        self._pos[slot] = 0
        self._bt[slot] = 0
        self._bt_seen[slot] = None
        self._hist[slot] = 0
        self._hist_len[slot] = 0
        self._dirty = True

    def _seed_hist(self, slot: int, req: Request) -> None:
        """Seed the drafter window with the slot's token story (prompt +
        generated suffix, right-aligned, newest last) at admission — the
        only host→history upload; thereafter the device rolls the window
        inside the decode program and the host mirrors the same roll
        bitwise (re-prefill after preemption just re-seeds here)."""
        if not self.spec_k:
            return
        H = self.spec_hist
        seq = (list(req.prompt) + list(req.generated))[-H:]
        row = np.zeros(H, np.int32)
        row[H - len(seq):] = seq
        self._hist[slot] = row
        self._hist_len[slot] = len(seq)

    def _spec_account(self, slot: int, req, lim: int,
                      emitted: int) -> None:
        """Per-slot speculation bookkeeping after a dispatch: roll the
        host history window exactly as the device rolled its carry
        (shift left by ``emitted``, append the committed tokens — bitwise
        the same values, so the mirrors stay equal to the device arrays
        and no re-upload happens), and account draft hit/miss metrics.
        Position 0 of a dispatch is the authentic last token, so only
        the ``lim - 1`` draft positions count as drafted."""
        H = self.spec_hist
        committed = np.asarray(req.generated[-emitted:] if emitted
                               else [], np.int32)
        self._hist[slot] = np.concatenate(
            [self._hist[slot], committed])[-H:]
        self._hist_len[slot] = min(
            int(self._hist_len[slot]) + emitted, H)
        req.spec_drafted += max(0, lim - 1)
        req.spec_accepted += max(0, emitted - 1)
        self.metrics.inc("draft_tokens", max(0, lim - 1))
        self.metrics.inc("draft_accepted", max(0, emitted - 1))
        self.metrics.observe("accepted_per_dispatch", emitted)

    def _spec_rewind(self, slot: int, req) -> None:
        """Unwind a rejected draft suffix's KV. The rejected rows wrote
        positions ``>= pos'`` — dead weight the next dispatch overwrites
        before any read (per-layer writes precede reads and every row's
        ``kv_len`` masks deeper positions), so in-page remainders need no
        scrub; only WHOLE pages past the accepted cursor go back to the
        pool via ``free_tail`` (the mid-prefill preemption mechanics).
        The freed-page journal event is observability-only — replay
        ignores it, keeping crash-recovery sweeps bitwise (ISSUE 9)."""
        keep = int(self._pos[slot]) // self.page_size + 1
        freed = 0
        if len(self.alloc.pages_of(req.rid)) > keep:
            freed = self.alloc.free_tail(req.rid, keep=keep)
        self.metrics.inc("spec_rewinds")
        self._jlog("spec_rewind", rid=req.rid, freed=freed,
                   pos=int(self._pos[slot]))

    # -- one engine iteration ---------------------------------------------
    def step(self) -> bool:
        """Admissions (prefill) + one batched decode dispatch (up to
        ``decode_horizon`` tokens per slot). Returns False when there is
        nothing to do (engine idle).

        The step is the span ``engine.step`` / the histogram ``step_s``,
        and ``_step_impl`` cuts it into the phases of
        ``serving.metrics.PHASES``, each a child span and a histogram of
        its own (``ServingMetrics`` has the table)."""
        with self.metrics.phase("step", step=self._steps) as whole:
            return self._step_impl(whole)

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

    def _post_step(self) -> None:
        """After a productive step (checkpoint cadence here; the sharded
        engine chains its digest cross-check in front)."""
        self._maybe_checkpoint()

    def _step_impl(self, whole) -> bool:
        m, n = self.metrics, self._steps

        def can_hold(req: Request) -> bool:
            # a mid-prefill preemptee kept its filled pages
            need = (-(-len(req.prompt) // self.page_size)
                    - len(self.alloc.pages_of(req.rid)))
            avail = self.alloc.free_pages
            if self.prefix_cache is not None:
                # cached (refcount-0) pages are reclaimable on demand —
                # admission evicts them as needed, and any page the hit
                # ADOPTS instead was counted in ``need`` anyway
                avail += self.prefix_cache.evictable
            return avail >= need

        # the quota buckets refill and the TTL expiry sweep runs before
        # the admissions (an expired request must not be admitted)
        with m.phase("admit", step=n) as admit:
            self.sched.tick(n)
            self._expire_queued()
            if self.sched.idle:
                # a poll between arrivals observes nothing: its microseconds
                # must not dilute the mean of the steps that did work
                whole.drop()
                admit.drop()
                return False
            while (adm := self.sched.admissible(can_hold)) is not None:
                self._admit(*adm)
            m.counters["quota_throttled"] = self.sched.quota_throttled
            pslot, preq = self._oldest_prefilling()

        # ≤1 prefill chunk co-scheduled with the decode dispatch
        # (Sarathi-style): the decode stall this step is bounded by
        # prefill_chunk tokens, not a whole prompt. The previous step may
        # have launched it already, behind its decode dispatch
        ahead, self._ahead = self._ahead, None
        if ahead is not None:
            pslot, preq, start, chunk = ahead
            assert self.sched.slots[pslot] is preq \
                and preq.state is RequestState.PREFILLING \
                and preq.prefill_cursor == start, \
                "a chunk launched ahead outlived its request's seat"
        prefilled_tokens, stalled, tok0 = 0, admit.t1, None
        if preq is not None:
            ids = {"step": n, "rid": preq.rid, "cursor": preq.prefill_cursor}
            began = stalled
            if ahead is None:
                with m.phase("chunk_prep", **ids) as prep:
                    chunk = self._launch_chunk(pslot, preq)
                began, stalled = prep.t0, prep.t1
            tok_dev, cursor, prow, prefilled_tokens = chunk
            if cursor >= len(preq.prompt):
                with m.phase("chunk_wait", **ids) as chunk_wait:
                    # one int32 scalar download: the prompt's first token
                    # (the argmax ran on device; the host never sees logits)
                    tok0 = int(tok_dev)
                stalled = chunk_wait.t1
            # (any other chunk's token is nobody's and is never read: the
            # host goes on while the chunk runs, and what this step or the
            # next launches queues behind it on the pool it writes)
            m.observe("prefill_stall_s", stalled - began)
        m.observe("decode_stall_s", stalled - whole.t0)
        m.observe("step_prefill_tokens", prefilled_tokens)

        with m.phase("grow", step=n):
            if preq is not None:
                self._commit_chunk(pslot, preq, tok0, cursor, prow)
                if ahead is not None:
                    m.inc("chunks_prelaunched")
            limits, active = self._grow()

        if not active:
            if prefilled_tokens or not self.sched.idle:
                # the step did real work (a prefill chunk) even with no
                # decodable row — count it and keep the loop hot. Or nothing
                # was dispatched but work is still queued (quota-throttled
                # or capacity-blocked): the logical clock MUST advance
                # anyway — sched.tick(self._steps) refills the token buckets
                # off it, so a frozen clock would turn a bounded deficit
                # wait into permanent starvation (and a spurious
                # stall-watchdog trip)
                self._steps += 1
                with m.phase("post", step=n):
                    self._post_step()
                return True
            return False

        if self._dirty or self._bt_dirty:
            with m.phase("sync", step=n):
                self._sync_mirrors(table_only=not self._dirty)
            self._dirty = self._bt_dirty = False
            m.inc("host_syncs")

        with m.phase("dispatch", step=n) as dispatch:
            if self.spec_k:
                (toks, acc, self._token_dev, self._pos_dev, self._hist_dev,
                 self._hlen_dev, self.pool) = self._step(
                    self.params, self._token_dev, self._pos_dev, self.pool,
                    self._bt_dev, jnp.asarray(limits), self._hist_dev,
                    self._hlen_dev)
            else:
                toks, self._token_dev, self._pos_dev, self.pool = self._step(
                    self.params, self._token_dev, self._pos_dev, self.pool,
                    self._bt_dev, jnp.asarray(limits))
        self._launch_ahead()
        with m.phase("decode_wait", step=n) as decode_wait:
            # [B] committed-count vector under speculation
            accepted = np.asarray(acc) if self.spec_k else None
            slab = np.asarray(toks)        # [horizon, B] — blocks on device
        with m.phase("reconcile", step=n) as reconcile:
            n_tokens, emitted_by_slot = self._reconcile(
                limits, active, slab, accepted)

        dev_dt = decode_wait.t1 - dispatch.t0
        host_dt = (reconcile.t1 - whole.t0) - dev_dt
        with m.phase("post", step=n):
            m.observe("step_device_s", dev_dt)
            m.observe("step_host_s", host_dt)
            per_tok = (dev_dt + host_dt) / max(n_tokens, 1)
            m.observe("tok_latency_s", per_tok, n_tokens)
            # per-class ITL (ISSUE 14): the same per-token estimate, labeled
            # by the emitting request's class — the isolation panel's number
            for slot, req in active:
                m.observe_class("itl_s", class_label(req), per_tok,
                                emitted_by_slot.get(slot, 0))
            self._post_step()
        return True

    def _grow(self):
        """Allocate-on-decode growth, preempting (youngest first) when dry.
        Returns (the per-slot ``limits``, the decoding (slot, request)s).

        Slot order is index order — deterministic. The FIRST step is
        guaranteed (preempt until a page frees); the rest of the horizon
        is opportunistic: extend capacity page by page WITHOUT
        preempting, and clamp the slot's limit where growth stops — the
        auto-clamp that keeps a slot inside its pre-ensured pages
        mid-scan."""
        limits = np.zeros(self.num_slots, np.int32)
        alloc, slots, page = self.alloc, self.sched.slots, self.page_size
        pos_of = self._pos.tolist()
        active, rebuilt, preempted = [], 0, False
        for slot in range(self.num_slots):
            req = slots[slot]
            if req is None or req.state is not RequestState.ACTIVE:
                continue            # mid-prefill slots do not decode
            rid, pos = req.rid, pos_of[slot]
            # tokens the pages it owns still have room for: a page is taken
            # (and the pool asked) only where the dispatch outgrows them
            room = alloc.n_pages_of(rid) * page - pos
            if room < 1:
                while not self._ensure_pages(rid, pos + 1):
                    victim = self.sched.pick_victim(exclude_slot=slot)
                    if victim is None:
                        raise RuntimeError(
                            f"KV pool too small: request {rid} needs a page "
                            "with no preemptible peer left")
                    self._preempt(victim)
                    preempted = True
                room = alloc.n_pages_of(rid) * page - pos
            want = min(self.decode_horizon, req.remaining)
            lim = max(1, min(want, room))
            while lim < want and self._ensure_pages(rid, pos + lim + 1):
                lim += 1
            limits[slot] = lim
            active.append((slot, req))
            # the row AFTER growth — the kernel writes this scan's (k, v)
            # into pages ensure() may just have allocated — and only where
            # the ledger says the sequence's pages moved since the row was
            # mirrored (the ring / state column is the slot's and stands)
            seen = (rid, alloc.stamp(rid))
            if self._bt_seen[slot] != seen:
                self._bt_seen[slot] = seen
                rebuilt += 1
                row = self._device_bt_row(rid, slot)
                if not np.array_equal(row, self._bt[slot]):
                    self._bt[slot] = row
                    self._bt_dirty = True
                    self._jlog("grow", rid=rid, pages=alloc.n_pages_of(rid))
        self.metrics.inc("table_rows_checked", len(active))
        self.metrics.inc("table_rows_rebuilt", rebuilt)
        if preempted:
            # a slot preempted while a LATER slot grew already has its limit
            # computed — zero it (its mirrors are parked; writes go to
            # scratch)
            for slot in range(self.num_slots):
                r = slots[slot]
                if r is None or r.state is not RequestState.ACTIVE:
                    limits[slot] = 0
            active = [(s, r) for s, r in self.sched.active
                      if r.state is RequestState.ACTIVE]
        return limits, active

    def _reconcile(self, limits, active, slab, accepted):
        """Commit a dispatch's token slab to the scheduler's state. Returns
        (tokens emitted, {slot: tokens emitted})."""
        # a family's own counters ride the same slab, one row each after
        # the token rows (decode_multistep_paged): no further download
        for j, name in enumerate(self._family.counters):
            self.metrics.inc(name, int(slab[self.decode_horizon + j, 0]))

        self._steps += 1
        self.metrics.inc("dispatches")
        if self.spec_k:
            # the verify pass IS one device step — the whole point is
            # that decode_steps stops tracking tokens
            self.metrics.inc("decode_steps")
            self.metrics.inc("spec_dispatches")
        else:
            self.metrics.inc("decode_steps", int(limits.max()))
        self.metrics.observe("queue_depth", self.sched.queue_depth)
        self.metrics.observe("active_slots", len(active))
        if self._ring:
            # pages held by kind: a seated sequence's ledger pages (a full
            # layer's), and what a ring layer holds of them at most
            held = [self.alloc.n_pages_of(r.rid)
                    for r in self.sched.slots if r is not None]
            self.metrics.observe("kv_pages_full", sum(held))
            self.metrics.observe("kv_pages_window",
                                 sum(min(n, self._ring) for n in held))
        if self._state_bytes:
            # state held beside the pages: a seated sequence's, whatever
            # its context
            self.metrics.observe("state_bytes", self._state_bytes * sum(
                r is not None for r in self.sched.slots))

        n_tokens = 0
        emitted_by_slot = {}
        # the slab and the counts as Python ints once a dispatch: a slot's
        # column each
        commits = (limits if accepted is None else accepted).tolist()
        columns = slab[:self.decode_horizon].T.tolist()
        for slot, req in active:
            # up to the first token that ends the request (``Request.done``:
            # budget exhausted, then EOS)
            col = columns[slot][:commits[slot]]
            del col[max(1, req.max_new_tokens - len(req.generated)):]
            if req.eos_token in col:
                del col[col.index(req.eos_token) + 1:]
            req.generated.extend(col)
            emitted = len(col)
            self.metrics.inc("tokens_generated", emitted)
            # the device froze this row after the same ``emitted`` steps
            # (limit clamp / EOS mask / accept prefix), so the mirrors
            # stay equal to the device carry — a continuing slot costs no
            # re-upload
            self._token[slot] = slab[emitted - 1, slot]
            self._pos[slot] += emitted
            if self.spec_k:
                self._spec_account(slot, req, int(limits[slot]), emitted)
            n_tokens += emitted
            emitted_by_slot[slot] = emitted
            if req.done:
                self._finish(slot)
            elif self.spec_k and emitted < int(limits[slot]):
                self._spec_rewind(slot, req)
        return n_tokens, emitted_by_slot

    def run(self, max_steps: int | None = None,
            arrivals=None, recover=None) -> dict[int, list[int]]:
        """Drive ``step()`` until idle (or ``max_steps``). ``arrivals`` is
        an optional iterable of (step_index, prompt, max_new_tokens)
        3-tuples — or 5-tuples with (…, tenant, cls) appended (ISSUE 14,
        the bursty multi-tenant workloads) — sorted by step: the
        synthetic-trace replay hook serve_sim uses.
        Returns {rid: generated tokens} for FINISHED requests only — a
        truncated run (``max_steps`` hit) simply omits the unfinished.

        ``recover`` (ISSUE 9): truthy = restore from the journal's last
        checkpoint + suffix replay before stepping (a ``Checkpoint``
        object restores from that specific snapshot). Requires a journal.
        The caller feeds only not-yet-journaled arrivals — journaled
        submissions are replayed from the WAL. Restored FINISHED requests
        are included in the returned dict, so a recovered run returns the
        complete trace.

        A progress watchdog (ISSUE 7, shared with the disagg engine)
        deadlines the whole drive loop: ``stall_deadline_steps``
        consecutive non-idle steps with no counter movement raise
        ``EngineStallError`` instead of spinning forever — the colocated
        engine has no migration ladder, so ANY stall here is a bug."""
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
                else faults_mod.active_plan()
            if plan is not None and plan.crash(self._steps,
                                               self._incarnation):
                self.metrics.inc("faults_injected")
                raise InjectedCrash(
                    f"injected crash at step {self._steps} "
                    f"(incarnation {self._incarnation})")
            m = self._progress_marker()
            if m != marker:
                marker, since = m, 0
            else:
                since += 1
                if since >= self._stall_steps and not self.sched.idle:
                    active = "; ".join(
                        f"[{s}] rid={r.rid} {r.state.value} "
                        f"cursor={r.prefill_cursor}"
                        for s, r in self.sched.active)
                    raise EngineStallError(
                        f"engine made no progress for {since} steps "
                        f"(stall deadline {self._stall_steps}); queue="
                        f"{self.sched.queue_depth}, slots: "
                        f"{active or '<none>'}" + self._postmortem())
        return {req.rid: list(req.generated) for req in self._finished}

    def _progress_marker(self) -> tuple:
        c = self.metrics.counters
        return (c["tokens_generated"], c["prefills"], c["prefill_chunks"],
                c["preemptions"], c["requests_finished"],
                c["restores"], len(self._finished))

    # -- crash consistency (ISSUE 9) --------------------------------------
    def control_digest(self) -> int:
        """FNV-1a digest of the full host control plane (allocator +
        scheduler) — the per-event stamp journal entries carry, and the
        replicated-decision word the sharded engine cross-checks."""
        return _fnv1a(0x811C9DC5, self.alloc.digest(), self.sched.digest())

    def _jlog(self, kind: str, **payload) -> None:
        """Append one control-plane event to the journal (no-op without
        one; muted while a restore replays the journal into this engine —
        replay must not re-journal its own effects)."""
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
        """Capture a control-plane snapshot into the journal. Host-only
        (no device work, no KV bytes); restore pairs it with the journal
        suffix appended after it."""
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
        """JSON-able control-plane snapshot. Live requests are recorded in
        deterministic order (seated slots by admission ticket, then the
        queue); the page-ledger snapshot is an integrity audit artifact —
        restore re-earns pages via re-prefill, it never trusts old
        ownership."""
        live = [r for _, r in sorted(
            ((r.admitted_seq, r) for _, r in self.sched.active),
            key=lambda t: t[0])]
        live += list(self.sched.queue)
        return {
            "engine": "colocated",
            "step": self._steps,
            "next_rid": self._next_rid,
            "admit_ticket": self.sched._admit_ticket,
            "pool": self.alloc.snapshot(),
            "pool_digest": self.alloc.digest(),
            # prefix index (ISSUE 13): integrity artifact, like the pool
            # snapshot — restore starts with an EMPTY cache (re-prefill
            # re-earns KV; pre-crash device bytes are never adopted)
            "prefix_index": None if self.prefix_cache is None
            else self.prefix_cache.snapshot(),
            "prefix_digest": None if self.prefix_cache is None
            else self.prefix_cache.digest(),
            "live": [ckpt_mod.snapshot_request(r) for r in live],
            "finished": [ckpt_mod.snapshot_finished(r)
                         for r in self._finished],
            "rejected": [{"rid": r.rid, "kind": "expire"
                          if isinstance(r.failure, TtlExpired) else "reject",
                          "reason": str(r.failure),
                          "tenant": r.tenant, "cls": r.cls}
                         for r in self._rejected],
            # multi-tenant policy books (ISSUE 14): WFQ service counters,
            # virtual-time floor, token-bucket levels — restored AFTER
            # the live requests requeue so the exact cross-class order
            # resumes (None without a policy)
            "policy": self.sched.policy_state(),
            "counters": dict(self.metrics.counters),
        }

    def _restore_state(self, state: dict | None) -> None:
        """Rebuild host control state from a snapshot (None = from
        nothing — the whole journal is then the replay suffix). Device
        pool arrays are left untouched: every live request restarts from
        its prompt, and re-prefill rewrites a page's KV before any decode
        read of it, so stale device bytes are unreachable."""
        self.alloc = KVPagePool(self.alloc.num_pages, self.page_size,
                                reserved=self.alloc.reserved,
                                sp_ranks=self.alloc.sp_ranks,
                                layout=self.alloc.layout)
        if self.prefix_cache is not None:
            # fresh pool → fresh (empty) index: every cached mapping
            # pointed at KV the restored process never computed
            self.prefix_cache = PrefixCache(self.alloc, self.page_size)
        self._lent_pages = set()
        self._rewarmed_rids = set()
        self.sched = ContinuousBatchingScheduler(
            self.num_slots, queue_cap=self.sched.queue_cap,
            policy=self.sched.policy)
        self._finished = []
        self._rejected = []
        for slot in range(self.num_slots):
            self._park(slot)
        self._sync_mirrors()
        self._dirty = self._bt_dirty = False
        self._ahead = None          # every live request restarts at cursor 0
        if state is None:
            return
        # integrity audit: the snapshot's ledger must digest to the value
        # recorded at capture time (a torn snapshot fails loudly here)
        ckpt_mod.audit_pool_snapshot(
            state["pool"], state["pool_digest"], self.alloc.num_pages,
            self.page_size, self.alloc.reserved)
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
        # policy books AFTER the requeues: submit()'s idle-class snap ran
        # against zeroed counters; the checkpoint values overwrite them
        # so the restored WFQ order is exactly the captured one
        self.sched.restore_policy_state(state.get("policy"))
        for f in state["finished"]:
            self._restore_finished(f["rid"], f["tokens"], meta=f)
        for f in state["rejected"]:
            self._restore_terminal(f["rid"], f["kind"], f["reason"])

    def _restore_finished(self, rid: int, tokens: list[int],
                          meta: dict | None = None) -> None:
        """Settle ``rid`` as FINISHED with ``tokens`` (from a snapshot or
        a journal ``finish`` entry), removing it from the restored queue
        if it was live at the checkpoint. ``meta`` carries the terminal
        record's prompt/steps so the restored entry reports the same
        ttft/preemption numbers the original process measured."""
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

    def _postmortem(self) -> str:
        """Counters + journal tail appended to engine-level error reports
        so a post-mortem never needs a live process."""
        counters = {k: v for k, v in self.metrics.counters.items() if v}
        tail = (self.journal.format_tail(8) if self.journal is not None
                else "  <no journal attached>")
        return ("\ncounters: " + json.dumps(counters)
                + "\njournal tail:\n" + tail)

    @property
    def failed(self) -> list[Request]:
        """Typed terminals that will never finish (REJECTED overload
        terminals — the colocated engine has no other failure domain)."""
        return list(self._rejected)

    # -- introspection ----------------------------------------------------
    @property
    def compile_stats(self) -> dict:
        """Compile counts for the hot loop: the decode program and the
        chunk program, each exactly 1 however mixed the traffic. Uses the
        jit-internal cache size when available, falling back to whether
        the program has run."""
        stats = {
            "decode_compiles": programs.compiles(
                self._step, 1 if self._steps else 0),
            # exactly one program for ALL prompt lengths
            "prefill_chunk_compiles": programs.compiles(
                self._chunk_step,
                1 if self.metrics.counters["prefill_chunks"] else 0),
            # bytes and paths of the parameter leaves held in another layout
            # than the device's default (serving/layouts.py; 0: none)
            "params_relaid_bytes": self.metrics.counters[
                "params_relaid_bytes"],
            "params_relaid_leaves": list(self._relaid_leaves),
        }
        if self._aot_artifact is not None:
            from triton_dist_tpu.aot.artifact import LoadedProgram
            stats["aot_programs"] = sum(
                isinstance(f, LoadedProgram)
                for f in (self._step, self._chunk_step))
        return stats


__all__ = ["ServingEngine", "mark_prefill_start", "record_first_token"]
