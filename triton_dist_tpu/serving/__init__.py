"""Continuous-batching serving runtime over the paged decode +
EP/SP overlap ops (see docs/serving.md).

- kv_pool    — paged KV page allocator + cache<->pages converters
- scheduler  — FIFO admission / preemption policy over fixed batch slots
- engine     — the jitted one-step-per-token decode engine
- layouts    — the layouts the engine holds its weights in (ISSUE 38): the
               decode program compiled with each parameter leaf's layout
               left to the compiler, the weights committed once to what it
               chose, the chunk program compiled against that
- sharded    — the engine on a TP/SP/EP mesh (SP-sharded page pool, TP
               projections, EP MoE FFN through the overlap kernels, with
               the replicated-decision digest guard)
- disagg     — disaggregated prefill/decode over the shmem page-migration
               kernel (signal-gated admission + the ISSUE-7 recovery
               ladder: deadline → retry/backoff → local re-prefill →
               typed per-request failure)
- compose    — disagg × sharded (ISSUE 12): a disaggregated prefill fleet
               feeding a ShardedServingEngine decode fleet on ONE
               TP/SP/EP mesh, over the unified pool contract
- cluster    — N engine replicas behind a deterministic prefix-affinity
               router, each with a private path-namespaced journal and
               kill/restore through the ISSUE-9 ladder; SimEngine is the
               host-only scale vehicle (scripts/cluster_sim.py)
- prefix_cache — token-keyed radix index over KVPagePool pages (ISSUE
               13): refcounted adoption of cached prefixes, copy-on-
               write on divergence, LRU eviction of refcount-0 pages,
               and the cluster-authoritative ReplicaPrefixIndex twin
- lending    — cluster-wide prefix sharing (ISSUE 17): on a borrower-
               side cache miss with a remote index hit the owner LENDS
               its refcount-0 cached pages (ops.lend_pages on device
               meshes, export/adopt_prefix on host engines), wrapped in
               the Deadline/Backoff/degrade ladder; a restored replica
               re-warms its empty cache from peers the same way
- deadline   — Deadline/Backoff helpers + EngineStallError (the global
               progress watchdog both engines share)
- journal    — append-only WAL of control-plane events (ISSUE 9)
- checkpoint — periodic control-plane snapshot + journal-suffix replay
               restore (crash recovery with zero new compiles)
- metrics    — counters + histograms, JSON-lines wire format
- scheduler (ISSUE 14) — also the multi-tenant SLO policy surface:
               ClassSpec/SLOPolicy (priority classes, WFQ weights,
               per-tenant token-bucket quotas, per-class caps/TTLs)
- workload   — bursty two-class trace generation (ISSUE 14): Zipf prompt
               sharing × chat-vs-batch × diurnal bursts, plus the
               --workload / --slo CLI spec parsers
- autoscaler — elastic fleet control (ISSUE 18): a deterministic policy
               loop over windowed per-class TTFT/ITL SLO attainment that
               scales replicas up from the AOT artifact and down through
               the graceful drain ladder (requeue, lend-ahead, retire),
               journaling every decision so a controller restart resumes
               the fleet from the journal
- speculate  — model-free speculative decoding primitives (ISSUE 20):
               the bigram prompt-lookup drafter, the exact-match-greedy
               accept rule (EOS/limit composed), and the draft-length
               resolution ladder (explicit → tuned registry → default)
"""

from triton_dist_tpu.serving.autoscaler import Autoscaler, parse_budgets
from triton_dist_tpu.serving.checkpoint import (Checkpoint,
                                                CheckpointIntegrityError,
                                                capture, latest, restore)
from triton_dist_tpu.serving.cluster import (Cluster, EngineReplica,
                                             ReplicaState, SimEngine,
                                             expected_tokens, sim_token)
from triton_dist_tpu.serving.compose import DisaggShardedEngine
from triton_dist_tpu.serving.deadline import (Backoff, Deadline,
                                              EngineStallError)
from triton_dist_tpu.serving.disagg import (ChunkSignalLedger,
                                            DisaggServingEngine,
                                            MigrationSignalTimeout,
                                            PageMigrationChannel,
                                            SignalProtocolError)
from triton_dist_tpu.serving.engine import ServingEngine
from triton_dist_tpu.serving.journal import (EVENT_KINDS, SCHEMA_VERSION,
                                             ControlJournal)
from triton_dist_tpu.serving.kv_pool import (KVPagePool, PageLedgerError,
                                             page_pool_pspec,
                                             shard_pool_arrays)
from triton_dist_tpu.serving.lending import PageLendingTier
from triton_dist_tpu.serving.metrics import (AttainmentWindow, Histogram,
                                             ServingMetrics)
from triton_dist_tpu.serving.prefix_cache import (PrefixCache,
                                                  ReplicaPrefixIndex)
from triton_dist_tpu.serving.scheduler import (AdmissionRejected, ClassSpec,
                                               ContinuousBatchingScheduler,
                                               Request, RequestState,
                                               SLOPolicy, TtlExpired)
from triton_dist_tpu.serving.sharded import (MESH_AXES,
                                             ReplicatedDecisionError,
                                             ShardedServingEngine,
                                             serving_mesh,
                                             serving_param_shardings)
from triton_dist_tpu.serving.speculate import (ngram_draft, resolve_spec_k,
                                               spec_accept)
from triton_dist_tpu.serving.workload import (WorkloadSpec,
                                              generate_arrivals, parse_slo,
                                              parse_workload, rate_at,
                                              spec_bucket_of)

__all__ = [
    "ServingEngine",
    "ShardedServingEngine",
    "ReplicatedDecisionError",
    "serving_mesh",
    "serving_param_shardings",
    "MESH_AXES",
    "DisaggServingEngine",
    "DisaggShardedEngine",
    "Cluster",
    "EngineReplica",
    "ReplicaState",
    "SimEngine",
    "Autoscaler",
    "parse_budgets",
    "PageLendingTier",
    "expected_tokens",
    "sim_token",
    "shard_pool_arrays",
    "PageMigrationChannel",
    "ChunkSignalLedger",
    "MigrationSignalTimeout",
    "SignalProtocolError",
    "Deadline",
    "Backoff",
    "EngineStallError",
    "ControlJournal",
    "EVENT_KINDS",
    "SCHEMA_VERSION",
    "Checkpoint",
    "CheckpointIntegrityError",
    "capture",
    "restore",
    "latest",
    "AdmissionRejected",
    "TtlExpired",
    "ClassSpec",
    "SLOPolicy",
    "WorkloadSpec",
    "parse_workload",
    "generate_arrivals",
    "parse_slo",
    "rate_at",
    "KVPagePool",
    "PageLedgerError",
    "PrefixCache",
    "ReplicaPrefixIndex",
    "page_pool_pspec",
    "ContinuousBatchingScheduler",
    "Request",
    "RequestState",
    "ServingMetrics",
    "Histogram",
    "AttainmentWindow",
    "ngram_draft",
    "spec_accept",
    "resolve_spec_k",
    "spec_bucket_of",
]
