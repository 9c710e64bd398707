"""Serving metrics: counters + histograms, emitted as JSON lines.

One ``ServingMetrics`` per engine. Everything is host-side and O(1) per
event; histograms keep (count, sum, min, max) plus a bounded reservoir so
percentiles stay cheap and memory stays flat over million-request runs.
``json_line()`` is the wire format — one self-contained JSON object per
call, the shape ``scripts/serve_sim.py`` prints and ``bench.py`` folds
into its extras.
"""

from __future__ import annotations

import json
import time
from collections import deque

from jax.profiler import TraceAnnotation

# The phases of one ``ServingEngine.step()``, in the order a step runs them
# (``chunk_prep`` a second time between ``dispatch`` and ``decode_wait``, for
# the NEXT step's chunk where it is launched ahead: that step then opens none).
# Each is a span ``engine.<name>`` in the profiler's trace and an exact
# histogram ``phase_<name>_s``, children of ``engine.step`` / ``step_s``.
# The two ``*_wait`` block on a device program; the rest is the host's own work.
PHASES = ("admit", "chunk_prep", "chunk_wait", "grow", "sync", "dispatch",
          "decode_wait", "reconcile", "post")
_PHASE_HIST = {"step": "step_s", **{p: f"phase_{p}_s" for p in PHASES}}


class Histogram:
    """Streaming histogram: exact count/sum/min/max + a bounded sample
    reservoir (deterministic stride thinning, no RNG — replays emit
    identical metrics) for approximate percentiles."""

    def __init__(self, max_samples: int = 512):
        self.count = 0
        self.total = 0.0
        self.min = None
        self.max = None
        self._samples: list[float] = []
        self._max_samples = max_samples
        self._stride = 1

    def observe(self, value: float) -> None:
        value = float(value)
        self.count += 1
        self.total += value
        self.min = value if self.min is None else min(self.min, value)
        self.max = value if self.max is None else max(self.max, value)
        if (self.count - 1) % self._stride == 0:
            self._samples.append(value)
            if len(self._samples) >= self._max_samples:
                # thin deterministically: keep every other sample, double
                # the stride — the reservoir stays size-bounded and replay-
                # stable (random eviction would jitter the percentiles)
                self._samples = self._samples[::2]
                self._stride *= 2

    def observe_n(self, value: float, n: int) -> None:
        """``n`` observations of one value: what ``n`` calls of ``observe``
        leave behind (count, total, min, max, the retained samples and the
        stride), visiting the retained observations alone."""
        if n <= 0:
            return
        value = float(value)
        total = self.total
        for _ in range(n):              # the calls' own rounding, add by add
            total += value
        self.total = total
        self.min = value if self.min is None else min(self.min, value)
        self.max = value if self.max is None else max(self.max, value)
        at, end = self.count, self.count + n
        while True:
            at = -(-at // self._stride) * self._stride  # next one retained
            if at >= end:
                break
            self._samples.append(value)
            if len(self._samples) >= self._max_samples:
                self._samples = self._samples[::2]
                self._stride *= 2
            at += 1
        self.count = end

    def percentile(self, q: float) -> float | None:
        if not self._samples:
            return None
        s = sorted(self._samples)
        idx = min(int(q / 100.0 * len(s)), len(s) - 1)
        return s[idx]

    @property
    def mean(self) -> float | None:
        return self.total / self.count if self.count else None

    def summary(self) -> dict:
        return {
            "count": self.count,
            "mean": self.mean,
            "min": self.min,
            "max": self.max,
            "p50": self.percentile(50),
            "p90": self.percentile(90),
            "p99": self.percentile(99),
        }


class Phase:
    """One timed span: a profiler annotation around a ``perf_counter()``
    pair. ``t0`` / ``t1`` are the stamps the engine derives its older
    timers from; ``drop()`` keeps the span and forgets the observation (a
    step that found nothing to do must not dilute a mean)."""

    __slots__ = ("_span", "_hist", "t0", "t1")

    def __init__(self, span, hist):
        self._span = span
        self._hist = hist

    def __enter__(self):
        self._span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        self._span.__exit__(*exc)
        if self._hist is not None:
            self._hist.observe(self.t1 - self.t0)
        return False

    def drop(self) -> None:
        self._hist = None


class AttainmentWindow:
    """Windowed SLO attainment over keyed latency series (ISSUE 18).

    The autoscaler's sensor: per key (e.g. ``("ttft", "chat")``) a
    bounded FIFO window of the most recent observations; ``attainment``
    is the fraction of the window at or under a budget. Deterministic by
    construction — observations arrive in engine-step order and the
    window is a plain deque, so the same trace always yields the same
    scale decisions (no wall clock, no decay constants to drift)."""

    def __init__(self, window: int = 128):
        assert window >= 1
        self.window = window
        self._series: dict = {}

    def observe(self, key, value: float) -> None:
        s = self._series.get(key)
        if s is None:
            s = self._series[key] = deque(maxlen=self.window)
        s.append(float(value))

    def count(self, key) -> int:
        s = self._series.get(key)
        return len(s) if s is not None else 0

    def attainment(self, key, budget: float) -> float | None:
        """Fraction of the window ≤ ``budget``; None while empty."""
        s = self._series.get(key)
        if not s:
            return None
        return sum(1 for v in s if v <= budget) / len(s)

    def snapshot(self) -> dict:
        return {str(k): {"count": len(s),
                         "newest": s[-1] if s else None}
                for k, s in sorted(self._series.items(), key=lambda t:
                                   str(t[0]))}


class ServingMetrics:
    """The engine's instrument panel (ISSUE 2 tentpole part 4):

    counters — tokens generated, requests submitted/finished, prefills,
    preemptions, decode steps (inner device steps: += horizon per
    dispatch), dispatches (host→device decode launches — at horizon K one
    dispatch covers up to K steps, so dispatches ≲ decode_steps / K),
    host_syncs (dispatches that had to re-upload host slot state after a
    control-plane change — admission, finish, preemption: every mirror;
    growth: the table alone; a quiet dispatch reuses the device-resident
    carry and uploads nothing);
    histograms — TTFT (s), per-token latency (s), queue depth (sampled
    per step), batch occupancy (active slots per step), per-dispatch
    device time and host overhead (s) — the device/host split bench.py
    reports.

    The phases of a step (``phase(name, **ids)``; always on). Each is a
    span ``engine.<name>`` on the ``/host:CPU`` plane of the profiler's
    trace (the clock the device's ``XLA Ops`` line uses; it carries the
    step number, and ``rid`` / ``cursor`` where it works for one request)
    and an exact histogram; all are children of ``engine.step`` /
    ``step_s``, and they tile it: the host-work and wait totals add up to
    ``step_s.total``. ``snapshot()`` prints each one's mean, p50, p99 and
    max: a stalled step shows as ``phase_decode_wait_s.max``.

    ====================  ===========================================  =====
    histogram             what the step does there                     kind
    ====================  ===========================================  =====
    phase_admit_s         quota refill, TTL expiry, admissions         host
                          (prefix adoption inside), choice of the
                          slot to prefill
    phase_chunk_prep_s    a chunk's budget, token buffer, COW guard,   host
                          table row, uploads and launch (rid, cursor):
                          after admit for the step's own chunk, or
                          between dispatch and decode_wait for the
                          NEXT step's, launched ahead (one a chunk)
    phase_chunk_wait_s    a prompt's LAST chunk alone: blocked on the  wait
                          chunk program for the first token (rid,
                          cursor); no other chunk is waited for
    phase_grow_s          the chunk's commit, page growth and          host
                          preemption, limits, the table row of a slot
                          whose pages the ledger stamped anew
    phase_sync_s          slot mirrors uploaded after a control-plane  host
                          change (the table alone after growth)
    phase_dispatch_s      the limits' upload and the decode launch     host
    phase_decode_wait_s   blocked on the decode program's token slab   wait
    phase_reconcile_s     family counters, gauges, the commit loop,    host
                          finishes, speculation rewinds
    phase_post_s          the closing per-token observations, then     host
                          checkpoint cadence (sharded: digest check)
    ====================  ===========================================  =====

    ``engine.submit`` (rid) is a span alone. A ``step()`` that finds the
    engine idle observes nothing. A chunk that is not its prompt's last has
    no token anybody reads (counter ``chunks_not_awaited``): the step goes
    on while it runs, the decode program queues behind it, and a step that
    is such a chunk alone returns with the chunk in flight. Where a request
    is PREFILLING at dispatch time and no stall-budgeted class decodes, the
    chunk the NEXT step would launch is launched right behind the decode
    program, before the host blocks on the slab (counter
    ``chunks_prelaunched``, counted at the chunk's commit): nothing of it
    is committed until that step's ``grow``, a step returns with it in
    flight, and its token, where it is the prompt's last, is read by that
    step's ``chunk_wait`` (``docs/serving.md`` has who drops it).
    ``decode_stall_s`` (step start to the chunk's launch, or to its token
    where it is the last; to admission's end where a non-final chunk was
    launched ahead), ``prefill_stall_s`` (one a chunk: its prep where the
    step launched it itself, + wait where there is one), ``step_device_s``
    (dispatch's start to decode wait's end: it holds the tail of a chunk
    that was not waited for and the prep of the chunk launched ahead, so
    every prep lies in exactly one of it and ``prefill_stall_s``) and
    ``step_host_s`` (step start to reconcile's end, less
    ``step_device_s``: it counts a LAST chunk's blocking call as host time)
    are differences of the same stamps.
    Where the chunk program walks K/V pages (``PagedFamily.chunk_walks``) the
    engine counts, at every chunk's commit and on the host, from the cursor
    and the chunk's length alone (``ops.flash_decode.chunk_walk_counts``: the
    plan the kernel walks by): ``chunk_walk_pages`` (pages a row block of
    ``gqa_prefill_paged`` walked, summed over row blocks, attention layers
    and chunks) and ``chunk_walk_edge_pages`` (those of them that took the
    MASKED online-softmax update: the pages where some live row of the block
    does not see every key; the others run without the mask).
    A model family's own counters (``PagedFamily.counters``: for a share of
    an expert-parallel layer, ``moe_local_rows`` = routed assignments that
    landed on the experts held here and ``moe_experts_touched`` = held
    experts with at least one row, both summed over the layers and inner
    steps of every decode dispatch, live rows only) are added by the engine
    that serves the family; they come off the token slab, not a transfer
    of their own. A family with window and full attention layers adds
    ``attn_window_keys`` (min(context, window) summed over live rows, inner
    steps and window layers: the keys its windowed walks had to attend) and
    ``attn_full_keys`` (the context, summed the same way over full layers).
    A looped family (``PagedFamily.walks``) adds ``loop_plane_keys`` (keys
    walked, summed over live rows, the walks x layers planes and inner
    steps), ``loop_row_calls`` (live rows summed the same way) and
    ``loop_early_exit_rows`` (live rows whose picked walk was not the last).
    ``kv_bytes_per_token`` is a gauge set at build where every pool leaf is
    the ledger's pages: bytes a cached token holds over all planes.
    For a family whose slots own rings of pages (``PagedFamily.slot_ring``)
    the engine samples two gauges every decode dispatch, pages held by kind:
    ``kv_pages_full`` (the seated sequences' ledger pages: what a full layer
    holds) and ``kv_pages_window`` (min(those, ring) a sequence: what a
    window layer holds of them).
    """

    def __init__(self):
        self.counters = {
            "requests_submitted": 0,
            "requests_finished": 0,
            "prefills": 0,
            "preemptions": 0,
            "decode_steps": 0,
            "dispatches": 0,
            "host_syncs": 0,
            "tokens_generated": 0,
            # chunked-prefill dispatches (ISSUE 5): with chunking on,
            # EVERY prompt token enters pages through a chunk program —
            # the contiguous-cache converters and the host argmax never
            # run (tests assert this via prefill_chunks > 0)
            "prefill_chunks": 0,
            # of those, the chunks whose token the step never read (every
            # chunk but a prompt's last): the host went on while they ran,
            # so prefill_chunks - chunks_not_awaited ==
            # phase_chunk_wait_s.count
            "chunks_not_awaited": 0,
            # and the chunks launched AHEAD: behind the previous step's
            # decode dispatch, before the host blocked on its slab, so the
            # step that ran them had no chunk_prep of its own (counted at
            # the chunk's commit: chunks_prelaunched <= prefill_chunks)
            "chunks_prelaunched": 0,
            # sharded serving (ISSUE 8): replicated-decision digest
            # cross-checks run (each one all-gathered the control-plane
            # digest over the mesh and compared every rank to rank 0)
            "digest_checks": 0,
            # disaggregated serving (ISSUE 6): pages pushed over the
            # one-sided shmem layer, migration kernel launches (one per
            # finished chunk with at least one finalized page), and
            # completed prefill→decode handoffs
            "pages_migrated": 0,
            "migrate_chunks": 0,
            "handoffs": 0,
            # robustness ladder (ISSUE 7): signal-deadline expiries that
            # re-issued a chunk's migrate send, requests rescued by
            # decode-local re-prefill after retries ran out, requests
            # that exhausted the whole ladder and were failed (typed,
            # per-request — the engine keeps running), landed reports
            # discarded because their generation tag was stale (they
            # arrived after a retry re-armed the chunk), and host-tier
            # fault-plan injections actually applied to this engine
            "retries": 0,
            "degradations": 0,
            "failed_requests": 0,
            "stale_signals": 0,
            "faults_injected": 0,
            # crash consistency (ISSUE 9): control-plane checkpoints
            # captured into the journal, restores completed (from a
            # checkpoint + journal-suffix replay), digest divergences
            # absorbed by the sharded restore rung instead of raised,
            # and the overload terminals — submits rejected at a full
            # bounded queue, queued requests expired past their TTL
            "checkpoints": 0,
            "restores": 0,
            "digest_recoveries": 0,
            "rejections": 0,
            "expirations": 0,
            # prefix caching (ISSUE 13): admissions that adopted at least
            # one cached page vs admissions that matched nothing, total
            # prompt tokens served straight from adopted pages (never
            # recomputed), copy-on-write page copies (a writer diverged
            # from a shared page), and cached pages reclaimed by LRU
            # eviction to refill the free list
            "prefix_hits": 0,
            "prefix_misses": 0,
            "prefix_hit_tokens": 0,
            "cow_copies": 0,
            "prefix_evictions": 0,
            "prefix_skipped_chunks": 0,
            "router_radix_hits": 0,
            "router_radix_misses": 0,
            # multi-tenant SLO policy (ISSUE 14): admission-scan skips of
            # a class head whose tenant token bucket was dry (mirrored
            # from the scheduler's cumulative count each step), and
            # prefill chunks shrunk below prefill_chunk because a
            # stall-budgeted class was decoding (deadline-aware sizing)
            "quota_throttled": 0,
            "chunk_shrinks": 0,
            # cluster prefix lending (ISSUE 17): completed lends (one per
            # borrowed prefix), pages and prompt tokens delivered through
            # them, lend attempts that degraded to local re-prefill (dead
            # or slow lender — the request proceeds cold, never stalls),
            # and prefixes a restored replica re-warmed from peers
            # instead of cold re-prefilling
            "lends": 0,
            "lent_pages": 0,
            "lend_tokens": 0,
            "lend_degradations": 0,
            "rewarmed_prefixes": 0,
            # elastic autoscaling (ISSUE 18): fleet membership changes
            # (replicas added / drains reaching quiescence / replicas
            # retired), queued requests a draining replica
            # handed back through its journal cursor for re-placement on
            # a peer, total replica-steps actually run (the counterfactual
            # bench row divides this by static-peak provisioning), drain-
            # time lend-ahead pushes (one per prefix landed on its
            # rendezvous successor, plus the pages they carried), and
            # lend-ahead attempts that degraded to a typed no-op because
            # an engine lacked the lend surface (mixed fleets)
            "scale_ups": 0,
            "drains_done": 0,
            "retires": 0,
            "requeues": 0,
            "replica_steps": 0,
            "lend_aheads": 0,
            "lend_ahead_pages": 0,
            "lend_ahead_noops": 0,
            # speculative decoding (ISSUE 20): verify dispatches run with
            # speculation on, draft positions those dispatches scored
            # (position 0 consumes the authentic last token, so a
            # K-horizon dispatch drafts K-1), drafts that committed
            # (draft == verified argmax — ``draft_hit_rate`` in
            # ``snapshot()`` is accepted/drafted), and dispatches that
            # rejected a suffix and rewound its KV past the accepted
            # cursor
            "spec_dispatches": 0,
            "draft_tokens": 0,
            "draft_accepted": 0,
            "spec_rewinds": 0,
            # the table mirror (``ServingEngine._grow``): decoding slots a
            # dispatch looked at, and those of them whose table row was
            # built again from the ledger because the stamp of the
            # sequence's pages had moved since the row was mirrored (a page
            # taken, a rewind, a fresh seat); the others cost one compare
            "table_rows_checked": 0,
            "table_rows_rebuilt": 0,
        }
        self.hist = {
            "ttft_s": Histogram(),
            # TTFT split: queue wait (submit → first admission) vs
            # prefill latency (first admission → first token) — the two
            # levers chunked prefill trades between
            "ttft_queue_s": Histogram(),
            "ttft_prefill_s": Histogram(),
            "tok_latency_s": Histogram(),
            "queue_depth": Histogram(),
            "active_slots": Histogram(),
            "step_device_s": Histogram(),
            "step_host_s": Histogram(),
            # the whole of a step that found work, and its phases (PHASES)
            **{name: Histogram() for name in _PHASE_HIST.values()},
            # a chunk's prep + wait (one prefill chunk per step max)
            "prefill_stall_s": Histogram(),
            # per-step decode stall: time the step spent on admission +
            # prefill work before the decode dispatch could launch —
            # bounded by one chunk when chunking is on, by the whole
            # prompt (inline prefill) when it is off
            "decode_stall_s": Histogram(),
            # prompt tokens prefilled in the step (the token-space stall
            # bound the simulator regression test asserts: max ≤ chunk)
            "step_prefill_tokens": Histogram(),
            # disaggregated serving (ISSUE 6): per-chunk migration launch
            # latency (s), and how many decode-worker steps a completed
            # prefill waited for its covering signals (0 = admitted the
            # very step the last chunk landed)
            "migrate_s": Histogram(),
            "migrate_wait_steps": Histogram(),
            # robustness ladder (ISSUE 7): TTFT of requests that needed
            # at least one retry but still handed off (recovered), TTFT
            # of requests rescued by decode-local re-prefill (degraded;
            # measured at local prefill completion), and prompt tokens
            # re-prefilled locally per degraded chunk — kept OUT of
            # step_prefill_tokens so the decode-cadence isolation
            # invariant (max == 0 on the decode panel in fault-free
            # runs) stays pinned
            "recovered_ttft_s": Histogram(),
            "degraded_ttft_s": Histogram(),
            "degraded_prefill_tokens": Histogram(),
            # crash consistency (ISSUE 9): wall time per checkpoint
            # capture, per restore (snapshot rebuild + journal-suffix
            # replay — host-only, zero dispatches), and per absorbed
            # digest divergence (the sharded restore rung end-to-end)
            "checkpoint_s": Histogram(),
            "restore_s": Histogram(),
            "digest_recovery_s": Histogram(),
            # prefix caching (ISSUE 13): the TTFT split the cache exists
            # to move — first-token latency of admissions that adopted
            # cached pages vs ones that prefilled from scratch
            "ttft_cached_s": Histogram(),
            "ttft_cold_s": Histogram(),
            # overlapped serving (ISSUE 16): per-decode-step EP wire time
            # split by the wire-fit model — comm the schedule still
            # exposes on the critical path vs comm hidden behind expert
            # FFN compute by the microbatch pipeline. MODELED (t = t0 +
            # bytes/BW per a2a round), not wall clock: CPU test runs
            # serialize ranks and can never exhibit real overlap, so the
            # honest number is the model, labeled as such (docs/
            # serving.md). overlap=off exposes everything; n_ep=1 has no
            # wire and observes zeros.
            "exposed_comm_us": Histogram(),
            "overlapped_comm_us": Histogram(),
            # long-context serving (ISSUE 19): per-decode-step attention
            # split under ``flash_decode_dist`` — the local per-page
            # partial walk (∝ this rank's OWN slice of the block-table
            # pages: the half that shrinks as the SP mesh grows) vs the
            # fixed-order fold's wait on the remote partial slabs.
            # MODELED on the same wire fit as exposed/overlapped_comm_us
            # (CPU runs serialize ranks and cannot exhibit the real
            # overlap), labeled as such in docs/serving.md; zeros outside
            # long_context mode.
            "attn_local_us": Histogram(),
            "attn_fold_wait_us": Histogram(),
            # cluster prefix lending (ISSUE 17): the kill/restore TTFT
            # split — cold (no cached pages), cached (locally cached
            # pages adopted), re-warmed (adopted pages arrived via the
            # lending tier: a peer's lend or a post-restore re-warm).
            # The ``_steps`` trio is the deterministic engine-step-space
            # twin the SimEngine/cluster_sim panels report (wall TTFT is
            # meaningless for a host-only engine); ``ttft_rewarmed_s``
            # extends the ISSUE 13 wall-clock pair for device engines.
            "ttft_rewarmed_s": Histogram(),
            "ttft_cold_steps": Histogram(),
            "ttft_cached_steps": Histogram(),
            "ttft_rewarmed_steps": Histogram(),
            # lend wall time per page (µs) — the bench row
            "lend_us_per_page": Histogram(),
            # elastic autoscaling (ISSUE 18): deterministic step-space
            # TTFT (wall clock would make scale decisions replay-unstable;
            # the per-class ``ttft_steps`` / ``itl_steps`` series the SLO
            # attainment windows sample are labeled twins, made on first
            # touch)
            "ttft_steps": Histogram(),
            # speculative decoding (ISSUE 20): tokens COMMITTED per slot
            # per verify dispatch (1 = speculation earned nothing over
            # greedy that dispatch; mean > 1 is the whole win — the bench
            # gate asserts it on the repetitive workload)
            "accepted_per_dispatch": Histogram(),
        }
        self._t0 = time.perf_counter()

    # what opens a span: the profiler's annotation (a flag test while no
    # profiler session runs). A test replaces it with a recorder.
    span = TraceAnnotation

    def phase(self, name: str, **ids) -> Phase:
        """``with metrics.phase("grow", step=n):`` is the span
        ``engine.grow`` carrying ``ids`` in the profiler's trace, and its
        seconds observed by ``phase_grow_s`` (``step`` by ``step_s``; a
        name with no histogram is a span alone)."""
        return Phase(self.span("engine." + name, **ids),
                     self.hist.get(_PHASE_HIST.get(name)))

    def inc(self, name: str, by: int = 1) -> None:
        self.counters[name] += by

    def observe(self, name: str, value: float, n: int = 1) -> None:
        """``n`` observations of ``value`` (one, unless said)."""
        self.hist[name].observe_n(value, n)

    # -- per-class labels (ISSUE 14) --------------------------------------
    # Labeled series live in the SAME flat dicts under Prometheus-style
    # keys (``ttft_s{class=chat}``), created lazily on first touch so an
    # unpoliced engine emits exactly the pre-ISSUE-14 panel. ``itl_s`` is
    # the per-class twin of ``tok_latency_s`` (inter-token latency).
    @staticmethod
    def class_key(name: str, cls: str) -> str:
        return f"{name}{{class={cls}}}"

    def inc_class(self, name: str, cls: str | None, by: int = 1) -> None:
        if cls is None:
            return
        key = self.class_key(name, cls)
        self.counters[key] = self.counters.get(key, 0) + by

    def observe_class(self, name: str, cls: str | None,
                      value: float, n: int = 1) -> None:
        if cls is None or n <= 0:
            return
        key = self.class_key(name, cls)
        if key not in self.hist:
            self.hist[key] = Histogram()
        self.hist[key].observe_n(value, n)

    def classes(self) -> list[str]:
        """Class labels seen so far (sorted — deterministic panels)."""
        out = set()
        for d in (self.counters, self.hist):
            for k in d:
                if "{class=" in k:
                    out.add(k.split("{class=", 1)[1].rstrip("}"))
        return sorted(out)

    def per_class(self) -> dict:
        """The two-panel serve_sim summary's per-class block: TTFT/ITL
        p50/p99 plus the shed/throttle counts, one entry per class."""
        out = {}
        for cls in self.classes():
            ttft = self.hist.get(self.class_key("ttft_s", cls))
            itl = self.hist.get(self.class_key("itl_s", cls))
            out[cls] = {
                "ttft_p50_s": ttft.percentile(50) if ttft else None,
                "ttft_p99_s": ttft.percentile(99) if ttft else None,
                "itl_p50_s": itl.percentile(50) if itl else None,
                "itl_p99_s": itl.percentile(99) if itl else None,
                "finished": self.counters.get(
                    self.class_key("requests_finished", cls), 0),
                "rejections": self.counters.get(
                    self.class_key("rejections", cls), 0),
                "expirations": self.counters.get(
                    self.class_key("expirations", cls), 0),
            }
        return out

    def snapshot(self) -> dict:
        wall = time.perf_counter() - self._t0
        toks = self.counters["tokens_generated"]
        drafted = self.counters["draft_tokens"]
        return {
            "wall_s": round(wall, 4),
            "tok_per_s": round(toks / wall, 2) if wall > 0 else None,
            # derived: fraction of draft positions whose token committed
            # (ISSUE 20); None when speculation never drafted
            "draft_hit_rate": round(
                self.counters["draft_accepted"] / drafted, 4)
                if drafted else None,
            **self.counters,
            **{k: v.summary() for k, v in self.hist.items()},
        }

    def json_line(self) -> str:
        return json.dumps(self.snapshot())

    def emit(self, file=None) -> None:
        """Print one JSON line (the serve_sim / log-scraper format)."""
        print(self.json_line(), file=file)


__all__ = ["AttainmentWindow", "Histogram", "PHASES", "Phase",
           "ServingMetrics"]
