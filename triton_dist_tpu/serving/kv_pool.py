"""Paged KV block allocator over the stacked ``[L, P, Hkv, page_size, D]``
page pool that ``ops.flash_decode.gqa_decode_paged`` consumes.

Two cleanly separated halves:

- **device memory**: ``models.llama.init_page_pool`` arrays — plain jax
  arrays the engine threads through its jitted step, donated. The pool has
  one layout (row-major) and one home: the programs carry it whole through
  their layer loop, ``paged_kv_write(layer=)`` writes the new rows into it
  in place (decode rows, each of its own sequence, as a scatter of rows; a
  prefill chunk's run of ONE sequence page by page, whole pages that the
  request owns alone: the engine's copy-on-write guard covers every page
  of a chunk before it is launched) and ``gqa_decode_paged(layer=)``
  streams pages out of it in place, so a step moves the rows or pages it
  writes and the pages it reads and no other byte of the pool
  (``tests/test_aot_topology.py`` holds the compiled programs to that).
  Page 0 is scratch: parked decode rows land there, a chunk's masked rows
  land nowhere, and nothing live ever reads it. Nothing here ever looks at
  the arrays' values.
- **host accounting** (this module): ``KVPagePool`` — a free-list over
  page ids with per-sequence ownership, allocate-on-decode growth and
  free-on-finish. Pure Python, deterministic (LIFO free list), microsecond
  scale next to a decode step.

Sharding: the pool shards exactly like the SP cache — the page-major pool
array is the paged twin of the ``[L, B, Hkv, S, D]`` cache whose S dim is
``P(..., axis, ...)``-sharded. ``page_pool_pspec(axis)`` shards the page
dim: each SP rank owns the pages of its sequence shard and runs an
identical (replicated-decision) allocator instance, so block tables stay
host-replicated control plane — same split as ``decode_step_sp``'s cache.

ONE pool contract (ISSUE 12): a single ``KVPagePool`` is simultaneously

- **shard_map-visible**: construct with ``sp_ranks=n`` and place the
  device arrays with ``shard_pool_arrays`` — the page dim is padded up to
  a multiple of ``n`` so ``page_pool_pspec`` splits it evenly. The
  allocator never hands out a padding id (``device_pages`` > ids ≥
  ``num_pages`` exist only on device), so allocation/preemption schedules
  are identical at every mesh size; and
- **a valid ``migrate_pages`` target**: ``check_migratable`` refuses
  scratch AND padding ids, and ``landed_row`` exposes only the signal-
  covered prefix of real owned pages — both independent of ``sp_ranks``.

``digest()`` deliberately EXCLUDES ``sp_ranks``/``device_pages``: the
ledger digest describes allocation DECISIONS, which the device layout
must never influence — pools driving meshes of different SP widths over
the same trace digest identically (test-pinned at n ∈ {1, 2, 4}).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def _fnv1a(h: int, *words: int) -> int:
    """Fold ints into a 32-bit FNV-1a state (4 bytes each, two's
    complement for the odd negative sentinel). Shared by the pool and
    scheduler digests so the two ledgers hash identically across ranks."""
    for w in words:
        w &= 0xFFFFFFFF
        for shift in (0, 8, 16, 24):
            h ^= (w >> shift) & 0xFF
            h = (h * 0x01000193) & 0xFFFFFFFF
    return h


class PageLedgerError(AssertionError):
    """Page-accounting corruption: double free, freeing a foreign page, or
    migrating a reserved/scratch page. Raised EXPLICITLY (not via bare
    ``assert``) so detection survives ``python -O`` — silent ledger
    corruption would let two sequences share a page and scribble over each
    other's KV. Subclasses ``AssertionError`` because the ledger checks
    started life as asserts and callers/tests catch them as such."""


def page_pool_pspec(axis: str | None) -> P:
    """PartitionSpec for the [L, P, Hkv, page_size, D] pool arrays: pages
    sharded over ``axis`` (the SP-cache analog — its S dim becomes the
    page dim here); everything else replicated."""
    return P(None, axis, None, None, None)


class KVPagePool:
    """Host-side free-list allocator over ``num_pages`` page ids.

    Invariants (asserted here, exercised in tests/test_serving.py):
    - a page id is owned by at most one sequence at a time;
    - ``reserved`` low ids are never handed out (the engine parks
      inactive batch slots on page 0 — its writes must never land on a
      live sequence's page);
    - alloc is all-or-nothing: a request for ``n`` pages either returns
      ``n`` ids or ``None`` and changes nothing (no partial grabs to
      unwind on preemption).
    The free list is LIFO so allocation order is deterministic — replay
    of the same trace allocates the same pages.

    ``sp_ranks`` (ISSUE 12, the unified pool contract) declares the SP
    width of the DEVICE arrays this ledger fronts: the device page dim is
    padded up to ``device_pages`` (a multiple of ``sp_ranks`` so
    ``page_pool_pspec`` splits evenly), but the allocator's id space stays
    ``[reserved, num_pages)`` — padding ids exist only on device, are
    never handed out, and are refused by ``check_migratable``. Every
    allocation DECISION (and hence ``digest()``) is independent of
    ``sp_ranks``; only ``page_shard`` / ``device_pages`` see the layout.

    ``layout`` (ISSUE 19) picks the ledger-id → device-row placement:

    - ``"blocked"`` (default): device row == page id — consecutive ids
      land on the same SP shard, the across-REQUESTS balance the pool-
      allgather attention path wants.
    - ``"interleaved"``: row ``(id % sp_ranks) * (device_pages /
      sp_ranks) + id // sp_ranks`` — consecutive ids round-robin across
      SP shards, so ONE long sequence's pages spread evenly over the
      mesh (the ``flash_decode_dist`` long-context mode, where per-rank
      attention compute is ∝ the LOCAL page count).

    Either way the map is a bijection over ``[0, device_pages)`` with
    row 0 fixed (the scratch page parks in shard 0's slice under both),
    and it is pure DEVICE layout: allocator ids, snapshots, and
    ``digest()`` never see it — the fixed-order page fold makes the
    attention result placement-invariant, so layout is a balance knob,
    never a decision input.
    """

    def __init__(self, num_pages: int, page_size: int, reserved: int = 0,
                 sp_ranks: int = 1, layout: str = "blocked"):
        assert num_pages > reserved >= 0
        assert sp_ranks >= 1
        assert layout in ("blocked", "interleaved"), (
            f"layout must be 'blocked' or 'interleaved', got {layout!r}")
        self.num_pages = num_pages
        self.page_size = page_size
        self.reserved = reserved
        self.sp_ranks = sp_ranks
        self.layout = layout
        # device page count: padded up so the page dim splits evenly over
        # the SP axis (the padding pages are invisible to the allocator)
        self.device_pages = num_pages + (-num_pages) % sp_ranks
        # LIFO: lowest ids on top, so fresh pools allocate reserved, 1, 2…
        self._free = list(range(num_pages - 1, reserved - 1, -1))
        self._owned: dict[object, list[int]] = {}
        # prefix caching (ISSUE 13): every referenced page carries a
        # refcount (1 for a plain allocation; >1 when the prefix cache
        # shares it across sequences). ``_cacheable`` marks pages the
        # prefix index holds; a cacheable page whose last reference drops
        # is RETAINED on the ``_cached`` LRU list (oldest first) instead
        # of returning to the free list — reclaimable, never a leak.
        self._refs: dict[int, int] = {}
        self._cached: list[int] = []
        self._cacheable: set[int] = set()
        # per-sequence mutation stamps: ``_stamps[seq]`` is a fresh value of
        # one pool-wide clock after every change to ``_owned[seq]``, so a
        # stamp never names two page lists, not even across a sequence's
        # free and re-allocation. What mirrors a sequence's pages (the
        # engine's table rows) keeps the stamp it mirrored and looks again
        # only when it moved. No allocation decision: ``digest()`` and
        # ``snapshot()`` leave it out.
        self._stamps: dict[object, int] = {}
        self._clock = 0

    def _touch(self, seq_id) -> None:
        """``_owned[seq_id]`` changed: every method that changes it ends
        here."""
        self._clock += 1
        if seq_id in self._owned:
            self._stamps[seq_id] = self._clock
        else:
            self._stamps.pop(seq_id, None)

    # -- introspection ----------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.num_pages - self.reserved) - len(self._free)

    def occupancy(self) -> float:
        cap = self.num_pages - self.reserved
        return self.used_pages / cap if cap else 0.0

    def pages_of(self, seq_id) -> list[int]:
        return list(self._owned.get(seq_id, ()))

    def holds(self, seq_id) -> bool:
        return seq_id in self._owned

    def n_pages_of(self, seq_id) -> int:
        return len(self._owned.get(seq_id, ()))

    def stamp(self, seq_id) -> int:
        """The mutation stamp of ``seq_id``'s page list: it differs from
        every stamp this pool gave before whenever the list may differ (0:
        the sequence holds nothing)."""
        return self._stamps.get(seq_id, 0)

    def refcount(self, page_id: int) -> int:
        """How many sequences hold ``page_id`` right now (0 = free or
        cached). The COW guard: a writer must never touch a page whose
        refcount exceeds 1."""
        return self._refs.get(page_id, 0)

    @property
    def cached_pages(self) -> int:
        """Refcount-0 pages retained for the prefix index — reclaimable
        on demand (LRU), counted as used by ``occupancy`` because they
        hold live KV bytes."""
        return len(self._cached)

    def lru_cached(self) -> list[int]:
        """Cached (refcount-0, index-retained) pages, oldest first — the
        eviction scan order. Copy; mutations go through ``uncache``."""
        return list(self._cached)

    def device_row(self, page_id: int) -> int:
        """Device-array row (page-dim index) holding ledger page
        ``page_id`` — identity under ``"blocked"``, the round-robin
        bijection under ``"interleaved"``. Every id that crosses to the
        device (block-table entries, host-side pool gathers/scatters)
        goes through here; everything that stays in the ledger (digest,
        snapshot, journal payloads) never does."""
        if not 0 <= page_id < self.device_pages:
            raise PageLedgerError(
                f"page {page_id} outside the device range "
                f"[0, {self.device_pages})")
        if self.layout == "blocked":
            return page_id
        return ((page_id % self.sp_ranks)
                * (self.device_pages // self.sp_ranks)
                + page_id // self.sp_ranks)

    def device_rows(self, page_ids):
        """``device_row`` of every id of ``page_ids`` as ONE int32 array
        operation (a table row, a gather's or a scatter's index): the same
        map, the same refusal of an id outside the device range."""
        ids = np.asarray(page_ids, np.int64)
        bad = (ids < 0) | (ids >= self.device_pages)
        if bad.any():
            raise PageLedgerError(
                f"page {int(ids[bad][0])} outside the device range "
                f"[0, {self.device_pages})")
        if self.layout == "blocked":
            return ids.astype(np.int32)
        return ((ids % self.sp_ranks) * (self.device_pages // self.sp_ranks)
                + ids // self.sp_ranks).astype(np.int32)

    def page_shard(self, page_id: int) -> int:
        """Which SP rank's device shard holds ``page_id`` under the
        ``page_pool_pspec`` even split of the padded page dim. Pure layout
        introspection — no allocation decision may depend on it (that
        would fork the replicated control plane across mesh sizes)."""
        return self.device_row(page_id) \
            // (self.device_pages // self.sp_ranks)

    def digest(self) -> int:
        """Cheap order-sensitive ledger digest (32-bit FNV-1a) over the
        ENTIRE allocator state: free-list order, ownership map in insertion
        order, and the static geometry. Two pools that ever made a
        different allocation decision — even ones that converged back to
        the same free-page COUNT — digest differently, because the LIFO
        free-list ORDER encodes the whole decision history. This is the
        replicated-decision guard the sharded serving engine cross-checks
        every step: every rank runs an identical allocator on identical
        inputs, so any digest divergence means a rank's control plane
        forked (and its block tables are about to scribble on the wrong
        pages). Pure Python ints, microseconds at serving pool sizes."""
        h = _fnv1a(0x811C9DC5, self.num_pages, self.page_size, self.reserved)
        h = _fnv1a(h, len(self._free), *self._free)
        for sid, pages in self._owned.items():
            h = _fnv1a(h, hash(sid) & 0xFFFFFFFF, len(pages), *pages)
        # prefix-cache state (ISSUE 13): refcounts by page id, the cached
        # LRU order, and the index-retention marks — all allocation
        # DECISIONS, all still independent of ``sp_ranks``
        for p in sorted(self._refs):
            h = _fnv1a(h, p, self._refs[p])
        h = _fnv1a(h, len(self._cached), *self._cached)
        h = _fnv1a(h, len(self._cacheable), *sorted(self._cacheable))
        return h

    # -- checkpointing (ISSUE 9) ------------------------------------------
    def snapshot(self) -> dict:
        """JSON-able snapshot of the ledger: free-list order and ownership
        map in insertion order (both order-sensitive — they round-trip the
        digest exactly). Used by serving/checkpoint.py, which rebuilds a
        pool from the snapshot and audits ``digest()`` against the value
        recorded at capture time (a torn snapshot fails loudly instead of
        silently double-owning pages after a restore)."""
        return {"free": list(self._free),
                "owned": [[sid, list(pages)]
                          for sid, pages in self._owned.items()],
                "refs": [[p, self._refs[p]] for p in sorted(self._refs)],
                "cached": list(self._cached),
                "cacheable": sorted(self._cacheable)}

    @classmethod
    def from_snapshot(cls, snap: dict, num_pages: int, page_size: int,
                      reserved: int = 0, sp_ranks: int = 1,
                      layout: str = "blocked") -> "KVPagePool":
        """Rebuild a ledger from ``snapshot()`` output (geometry is not in
        the snapshot — it comes from the engine's own configuration, which
        a restore never changes; ``sp_ranks``/``layout`` are device layout
        only and do not affect the rebuilt digest)."""
        pool = cls(num_pages, page_size, reserved, sp_ranks=sp_ranks,
                   layout=layout)
        pool._free = [int(p) for p in snap["free"]]
        pool._owned = {sid: [int(p) for p in pages]
                       for sid, pages in snap["owned"]}
        for sid in pool._owned:
            pool._touch(sid)
        # restored VERBATIM (not re-derived from ownership multiplicity):
        # the checkpoint integrity audit digests the rebuilt pool against
        # the capture-time value, so a tampered refcount/cache field must
        # surface as a digest mismatch, not be silently repaired
        if "refs" in snap:
            pool._refs = {int(p): int(c) for p, c in snap["refs"]}
        else:           # pre-cache snapshot: refcounts are the ownership
            for pages in pool._owned.values():
                for p in pages:
                    pool._refs[p] = pool._refs.get(p, 0) + 1
        pool._cached = [int(p) for p in snap.get("cached", ())]
        pool._cacheable = {int(p) for p in snap.get("cacheable", ())}
        return pool

    # -- allocation -------------------------------------------------------
    def alloc(self, seq_id, n_pages: int) -> list[int] | None:
        """Grow ``seq_id`` by ``n_pages``; all-or-nothing. Returns the new
        page ids or ``None`` when the pool is dry."""
        if n_pages > len(self._free):
            return None
        got = [self._free.pop() for _ in range(n_pages)]
        for p in got:
            self._refs[p] = 1
        self._owned.setdefault(seq_id, []).extend(got)
        self._touch(seq_id)
        return got

    def acquire(self, seq_id, page_ids) -> None:
        """Adopt ``page_ids`` for ``seq_id`` — the prefix-cache hit path
        (ISSUE 13). Each page must already hold live KV: either cached
        (refcount 0, retained for the index — it leaves the LRU list) or
        referenced by other sequences (its refcount climbs). Appended to
        the sequence's page list IN ORDER (pages are positional). All
        checks run before any mutation, so a refused acquire changes
        nothing."""
        own = set(self._owned.get(seq_id, ()))
        seen: set[int] = set()
        for p in page_ids:
            if not (self.reserved <= p < self.num_pages):
                raise PageLedgerError(
                    f"cannot adopt out-of-range page {p} (seq {seq_id!r})")
            if p in own or p in seen:
                raise PageLedgerError(
                    f"seq {seq_id!r} already holds page {p}")
            seen.add(p)
            if self._refs.get(p, 0) == 0 and p not in self._cached:
                raise PageLedgerError(
                    f"page {p} holds no live KV (free?) — refusing to "
                    f"adopt it for seq {seq_id!r}")
        for p in page_ids:
            if self._refs.get(p, 0) == 0:
                self._cached.remove(p)
            self._refs[p] = self._refs.get(p, 0) + 1
            self._owned.setdefault(seq_id, []).append(p)
        self._touch(seq_id)

    def _release_page(self, seq_id, p: int) -> bool:
        """Drop one reference to ``p``. On the LAST reference the page
        returns to the free list — unless the prefix index retains it
        (``_cacheable``), in which case it parks on the cached LRU list.
        True iff the page actually left the referenced set."""
        r = self._refs.get(p, 0)
        if r <= 0:
            raise PageLedgerError(
                f"refcount underflow on page {p} (seq {seq_id!r})")
        if r > 1:
            self._refs[p] = r - 1
            return False
        del self._refs[p]
        if p in self._free:
            raise PageLedgerError(
                f"double free of page {p} (seq {seq_id!r})")
        if p in self._cacheable:
            self._cached.append(p)      # MRU position
        else:
            self._free.append(p)
        return True

    def ensure(self, seq_id, kv_len: int) -> bool:
        """Allocate-on-decode growth: make ``seq_id`` own enough pages to
        hold ``kv_len`` tokens. True on success (including no-op), False
        when the pool is dry (caller preempts and retries)."""
        have = len(self._owned.get(seq_id, ()))
        need = -(-kv_len // self.page_size) - have
        if need <= 0:
            return True
        return self.alloc(seq_id, need) is not None

    def free_tail(self, seq_id, keep: int) -> int:
        """Free every page of ``seq_id`` past the first ``keep`` — the
        mid-prefill preemption primitive: the pages already holding
        computed KV (up to the chunk cursor) stay owned across the
        eviction, only the unfilled tail returns to the pool. Freed in
        allocation order (same convention as ``free_seq``) so replay
        stays deterministic. Returns how many were freed."""
        pages = self._owned.get(seq_id, [])
        if not 0 <= keep <= len(pages):
            raise PageLedgerError(
                f"free_tail(keep={keep}) out of range for seq {seq_id!r} "
                f"owning {len(pages)} pages")
        tail = pages[keep:]
        for p in tail:
            self._release_page(seq_id, p)
        if keep:
            self._owned[seq_id] = pages[:keep]
        else:
            self._owned.pop(seq_id, None)
        self._touch(seq_id)
        return len(tail)

    def free_seq(self, seq_id) -> int:
        """Free-on-finish (and on preemption): return every page of
        ``seq_id`` to the free list. Returns how many were freed."""
        pages = self._owned.pop(seq_id, [])
        for p in pages:
            self._release_page(seq_id, p)
        self._touch(seq_id)
        return len(pages)

    # -- prefix-cache retention + copy-on-write (ISSUE 13) ----------------
    def mark_cacheable(self, page_id: int) -> None:
        """Flag ``page_id`` as held by the prefix index: when its last
        reference drops it parks on the cached LRU list instead of the
        free list. Only live pages can be marked — a free page holds no
        KV worth retaining."""
        if not (self.reserved <= page_id < self.num_pages):
            raise PageLedgerError(
                f"cannot index out-of-range page {page_id}")
        if page_id in self._free:
            raise PageLedgerError(
                f"cannot index free page {page_id} — it holds no KV")
        self._cacheable.add(page_id)

    def uncache(self, page_id: int) -> bool:
        """Drop the index retention mark (eviction / index invalidation).
        If the page is sitting on the cached LRU list it returns to the
        free list NOW; if it is still referenced it simply frees normally
        on its last release. True iff a cached page was reclaimed."""
        self._cacheable.discard(page_id)
        if page_id in self._cached:
            self._cached.remove(page_id)
            self._free.append(page_id)
            return True
        return False

    def cow_page(self, seq_id, index: int) -> tuple[int, int] | None:
        """Copy-on-write ledger half: ``seq_id`` is about to WRITE its
        ``index``-th page but shares it (refcount > 1), so swap a fresh
        page into its page list and drop one reference on the shared one
        (which stays alive for its other holders / the index). Returns
        ``(old_id, new_id)`` — the caller must copy the device page bytes
        old → new before any read — or ``None`` when the pool is dry
        (caller evicts or preempts, then retries). Refuses a COW of a
        sole-owned page: writing in place is correct there, and a silent
        pointless copy would hide an engine-side guard bug."""
        pages = self._owned.get(seq_id, [])
        if not 0 <= index < len(pages):
            raise PageLedgerError(
                f"cow_page(index={index}) out of range for seq "
                f"{seq_id!r} owning {len(pages)} pages")
        old = pages[index]
        if self._refs.get(old, 0) <= 1:
            raise PageLedgerError(
                f"COW of page {old} with refcount "
                f"{self._refs.get(old, 0)} — copy-on-write is only for "
                f"shared pages (seq {seq_id!r})")
        if not self._free:
            return None
        new = self._free.pop()
        self._refs[new] = 1
        pages[index] = new
        self._refs[old] -= 1
        self._touch(seq_id)
        return old, new

    # -- migration support (disaggregated serving, ISSUE 6) ---------------
    def check_migratable(self, seq_id, page_ids) -> None:
        """Migration precondition: every id in ``page_ids`` must be owned
        by ``seq_id``, non-reserved, and a REAL page (< ``num_pages``).
        The scratch page(s) are engine-local parking — inactive rows WRITE
        to them every dispatch, so shipping one to a peer pool would plant
        live-mutating garbage there. SP padding ids (``num_pages`` ≤ id <
        ``device_pages``) exist only to even the device shard split —
        migrating one would write KV into a slot no block table can ever
        expose (a silent data loss). Raises ``PageLedgerError`` (loud,
        not silent corruption)."""
        owned = set(self._owned.get(seq_id, ()))
        for p in page_ids:
            if p < self.reserved:
                raise PageLedgerError(
                    f"page {p} is a reserved scratch page — scratch pages "
                    f"are never migrated (seq {seq_id!r})")
            if p >= self.num_pages:
                raise PageLedgerError(
                    f"page {p} is an SP padding/out-of-range id (real "
                    f"pages end at {self.num_pages}, device shard pads to "
                    f"{self.device_pages}) — padding pages are never "
                    f"migrated (seq {seq_id!r})")
            if p not in owned:
                raise PageLedgerError(
                    f"page {p} is not owned by seq {seq_id!r} — refusing "
                    "to migrate a foreign page")
            if self._refs.get(p, 0) > 1:
                raise PageLedgerError(
                    f"page {p} is shared (refcount {self._refs[p]}) — "
                    f"migration requires sole ownership; a migrated page "
                    f"is rewritten at the destination while other "
                    f"sequences still read it here (seq {seq_id!r})")

    def check_lendable(self, page_ids) -> int:
        """Lending precondition (ISSUE 17): how many pages of the
        POSITIONAL PREFIX of ``page_ids`` may be lent to a peer replica.
        A page is lendable iff it is refcount-0 AND retained on the
        cached LRU list — nobody here reads or writes it, the prefix
        index alone keeps it alive, so copying its bytes out races with
        nothing and the COW contract is untouched (the sole-ownership
        twin of ``check_migratable``, one rung stricter: migration wants
        exactly one owner, lending wants zero). Pages are positional
        (page i holds tokens ``[i*page_size, (i+1)*page_size)``), so the
        lendable run stops at the FIRST non-lendable page — a borrower
        resumes chunked prefill right there. Out-of-range/reserved ids
        are loud errors, not a short count: the caller handed us ids
        straight from its prefix index, so a bad id is ledger
        corruption."""
        cached = set(self._cached)
        n = 0
        for p in page_ids:
            if not (self.reserved <= p < self.num_pages):
                raise PageLedgerError(
                    f"check_lendable: page {p} outside the real-page "
                    f"range [{self.reserved}, {self.num_pages})")
            if self._refs.get(p, 0) != 0 or p not in cached:
                break
            n += 1
        return n

    def landed_row(self, seq_id, covered, pages_per_seq: int,
                   fill: int = 0) -> list[int]:
        """Block-table row exposing only the LANDED PREFIX of ``seq_id``'s
        pages. Pages are positional (page i holds tokens
        ``[i*page_size, (i+1)*page_size)``), so a page is usable only when
        it AND every page before it are in ``covered`` — the set of ids
        whose delivery signals have fired (``ChunkSignalLedger.covered``).
        Entries past the prefix are ``fill`` (the scratch page): the
        decode worker can never dereference a page whose signal has not
        fired. This is the block-table-patching half of signal-gated
        admission (serving/disagg.py)."""
        row: list[int] = []
        for p in self._owned.get(seq_id, []):
            if p not in covered:
                break
            row.append(p)
        if len(row) > pages_per_seq:
            raise PageLedgerError(
                f"seq {seq_id!r} landed {len(row)} pages > pages_per_seq "
                f"{pages_per_seq}")
        return row + [fill] * (pages_per_seq - len(row))

    def check(self, ledger=None) -> None:
        """Full-invariant audit (ISSUE 7): verify the free-list and
        ownership map are mutually consistent, and — given the migration
        ``ChunkSignalLedger`` — that signal accounting agrees with page
        ownership. Cheap enough to run after every chaos schedule; raises
        ``PageLedgerError`` with the first violation found.

        Invariants:
        - every free id is in range ``[reserved, num_pages)`` and listed
          exactly once;
        - every owned id is in range, not simultaneously free, and held
          by exactly ``refcount`` sequences (a page in two sequences'
          lists without a matching refcount is corruption, with one it
          is prefix sharing);
        - every refcount is positive and matches the ownership
          multiplicity; every cached page has refcount 0, carries the
          index-retention mark, and is neither free nor owned;
        - free + referenced + cached together account for every
          non-reserved page (count conservation — cached pages are
          reclaimable, never audited as leaks);
        - (with ``ledger``) every page a chunk expects to land for a
          sequence is owned by that sequence here, landed never exceeds
          expected per chunk, and the covered set never exceeds the
          sequence's allocation (landed prefix <= allocated).
        """
        owner: dict[int, object] = {}
        mult: dict[int, int] = {}
        for sid, pages in self._owned.items():
            seen: set[int] = set()
            for p in pages:
                if not (self.reserved <= p < self.num_pages):
                    raise PageLedgerError(
                        f"seq {sid!r} owns out-of-range page {p}")
                if p in seen:
                    raise PageLedgerError(
                        f"seq {sid!r} lists page {p} twice")
                seen.add(p)
                mult[p] = mult.get(p, 0) + 1
                owner.setdefault(p, sid)
        for p, n in mult.items():
            if self._refs.get(p, 0) != n:
                raise PageLedgerError(
                    f"page {p} held by {n} sequence(s) but refcount is "
                    f"{self._refs.get(p, 0)}")
        for p, r in self._refs.items():
            if r <= 0:
                raise PageLedgerError(
                    f"page {p} carries non-positive refcount {r}")
            if p not in mult:
                raise PageLedgerError(
                    f"page {p} has refcount {r} but no owning sequence")
        free = set(self._free)
        if len(free) != len(self._free):
            raise PageLedgerError("duplicate ids on the free list")
        for p in free:
            if not (self.reserved <= p < self.num_pages):
                raise PageLedgerError(f"out-of-range page {p} on free list")
            if p in owner:
                raise PageLedgerError(
                    f"page {p} is both free and owned by seq {owner[p]!r}")
            if p in self._cacheable:
                raise PageLedgerError(
                    f"page {p} is free yet still index-retained")
        cached = set(self._cached)
        if len(cached) != len(self._cached):
            raise PageLedgerError("duplicate ids on the cached LRU list")
        for p in cached:
            if not (self.reserved <= p < self.num_pages):
                raise PageLedgerError(
                    f"out-of-range page {p} on the cached list")
            if p in owner:
                raise PageLedgerError(
                    f"page {p} is cached (refcount 0) yet owned by seq "
                    f"{owner[p]!r}")
            if p in free:
                raise PageLedgerError(f"page {p} is both cached and free")
            if p not in self._cacheable:
                raise PageLedgerError(
                    f"page {p} is cached without an index-retention mark")
        total = len(free) + len(owner) + len(cached)
        if total != self.num_pages - self.reserved:
            raise PageLedgerError(
                f"page conservation violated: {len(free)} free + "
                f"{len(owner)} referenced + {len(cached)} cached != "
                f"{self.num_pages - self.reserved} non-reserved pages")
        if ledger is None:
            return
        for sid in ledger.rids():
            owned = set(self._owned.get(sid, ()))
            covered = ledger.covered(sid)
            if not covered <= owned:
                raise PageLedgerError(
                    f"seq {sid!r}: ledger covers pages "
                    f"{sorted(covered - owned)} this pool never allocated "
                    "to it (landed prefix exceeds allocation)")
            for chunk_idx, dst_ids, landed in ledger.chunk_items(sid):
                if landed > len(dst_ids):
                    raise PageLedgerError(
                        f"seq {sid!r} chunk {chunk_idx}: landed {landed} > "
                        f"expected {len(dst_ids)}")
                if not set(dst_ids) <= owned:
                    raise PageLedgerError(
                        f"seq {sid!r} chunk {chunk_idx}: expects pages "
                        f"{sorted(set(dst_ids) - owned)} not owned here")

    def block_table_row(self, seq_id, pages_per_seq: int,
                        fill: int = 0) -> list[int]:
        """Fixed-width block-table row for the kernel: owned pages then
        ``fill`` (the engine's scratch page — entries past the valid count
        are never dereferenced by ``gqa_decode_paged``, but a valid id
        keeps the row honest)."""
        pages = self._owned.get(seq_id, [])
        assert len(pages) <= pages_per_seq, (
            f"seq {seq_id!r} owns {len(pages)} pages > pages_per_seq "
            f"{pages_per_seq}")
        return pages + [fill] * (pages_per_seq - len(pages))


# ---------------------------------------------------------------------------
# device-side pool layout (the shard_map half of the one pool contract)
# ---------------------------------------------------------------------------

def shard_pool_arrays(pool: dict, sp_ranks: int, sharding=None) -> dict:
    """Pad the ``{"k", "v"}`` pool arrays' page dim (axis 1) up to a
    multiple of ``sp_ranks`` and (optionally) commit them to ``sharding``
    — the one place the SP device layout is materialized, shared by the
    sharded engine and the composed disagg-on-mesh prefill fleet so both
    sides of a cross-mesh migration carry the SAME array shapes and
    placement (one pjit executable serves both pools).

    Zero-init padding matches the live pages' init; the allocator never
    hands a padding id out (``KVPagePool(sp_ranks=...)``), so every
    block-table fill entry stays the scratch page and the padding is
    unreachable from any compiled program's reads."""
    pad = (-pool["k"].shape[1]) % sp_ranks
    if pad:
        pool = {
            k: jnp.concatenate(
                [v, jnp.zeros(v.shape[:1] + (pad,) + v.shape[2:],
                              v.dtype)], axis=1)
            for k, v in pool.items()}
    if sharding is not None:
        pool = {k: jax.device_put(v, sharding) for k, v in pool.items()}
    return pool


__all__ = ["KVPagePool", "PageLedgerError", "page_pool_pspec",
           "shard_pool_arrays", "_fnv1a"]
