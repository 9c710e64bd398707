"""Cluster-scale serving simulation (ISSUE 12 rung 3): a deterministic
prefix-affinity router over N engine replicas, driven by a Zipf workload
of hundreds of thousands of requests, with a mid-run replica kill +
restore through the crash-consistency ladder — and EVERY surviving
request's trace verified bit-identical to its single-replica golden.

    python scripts/cluster_sim.py                          # 100k over 4
    python scripts/cluster_sim.py --requests 250000 --replicas 8
    python scripts/cluster_sim.py --requests 200 --engine colocated
    python scripts/cluster_sim.py --no-kill                # fault-free
    python scripts/cluster_sim.py --autoscale --prefix-cache --lend \
        --pages 129 --min-replicas 1 --max-replicas 4 \
        --workload 'n=1500,rate=0.25,burst_every=300,burst_len=60,\
burst_x=10,seed=7'                                         # ISSUE 18

The default engine is ``SimEngine`` (serving/cluster.py): the REAL page
ledger / scheduler / journal / checkpoint control plane with a closed-
form token function, so the workload exercises admission, growth-driven
preemption, routing, journaling and kill/restore at a scale the device
engines cannot reach on CPU — and ``expected_tokens`` IS the golden, no
second run needed. ``--engine colocated`` swaps in the real jitted
``ServingEngine`` (tiny Llama) for a small-scale cross-check that the
replica/router layer is engine-agnostic; goldens then come from a
single-replica reference run of the same engine configuration.

Workload: ``--templates`` distinct prompt prefixes, Zipf-ranked
(``--zipf``), each request = template prefix + a unique tail. The router
hashes the first 8 tokens, so one template's requests land on one
replica (KV locality) until it dies — rendezvous hashing then moves only
its keys. Prints one JSON summary line: aggregate tok/s, TTFT p50/p99,
per-replica placement, failover timing, verification counts.
"""
import argparse
import json
import sys
import tempfile
import time

REPO = __file__.rsplit("/", 2)[0]
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
p.add_argument("--requests", type=int, default=100_000,
               help="total requests to route through the cluster")
p.add_argument("--replicas", type=int, default=4)
p.add_argument("--engine", choices=("sim", "colocated"), default="sim",
               help="'sim' = host-only SimEngine (scale); 'colocated' = "
                    "the real jitted ServingEngine (small cross-check)")
p.add_argument("--slots", type=int, default=8, help="slots per replica")
p.add_argument("--page-size", type=int, default=8)
p.add_argument("--pages", type=int, default=48,
               help="usable KV pool pages per replica")
p.add_argument("--pages-per-seq", type=int, default=8)
p.add_argument("--templates", type=int, default=64,
               help="distinct Zipf-ranked prompt prefixes")
p.add_argument("--zipf", type=float, default=1.1,
               help="Zipf exponent over the templates")
p.add_argument("--max-new", type=int, default=8,
               help="decode budget per request (uniform 2..max-new)")
p.add_argument("--arrive-per-step", type=int, default=None,
               help="requests submitted per cluster step (default: "
                    "2 per replica)")
p.add_argument("--seed", type=int, default=0)
p.add_argument("--journal-dir", default=None,
               help="directory for the per-replica journal-r{i}.jsonl "
                    "files (default: a fresh temp dir)")
p.add_argument("--checkpoint-every", type=int, default=0, metavar="N",
               help="checkpoint cadence in engine steps; 0 (default) "
                    "cuts NO checkpoints — the restore then replays the "
                    "ENTIRE journal (the slowest, most honest rung)")
p.add_argument("--kill-at", type=int, default=None, metavar="REQ",
               help="kill a replica after this many submissions "
                    "(default: requests // 2); --no-kill disables")
p.add_argument("--restore-after", type=int, default=None, metavar="REQ",
               help="restore it after this many further submissions "
                    "(default: requests // 10)")
p.add_argument("--kill-replica", type=int, default=1, metavar="I")
p.add_argument("--no-kill", action="store_true",
               help="fault-free run (no kill/restore cycle)")
p.add_argument("--prefix-cache", action="store_true",
               help="ref-counted prefix caching inside each replica "
                    "(ISSUE 13; SimEngine runs the same ledger/cache "
                    "control plane with chunked prefill since ISSUE 17). "
                    "The router's radix index already sends shared-"
                    "template prompts to one replica, so its cache sees "
                    "them all; prints an aggregate hit-rate + cold/"
                    "cached/rewarmed TTFT line to stderr")
p.add_argument("--lend", action="store_true",
               help="cluster-wide prefix sharing (ISSUE 17): on a local "
                    "cache miss with a remote radix-index hit, the owner "
                    "replica LENDS its refcount-0 cached pages to the "
                    "routed replica, and a restored replica re-warms its "
                    "cache from peers instead of cold re-prefilling. "
                    "Needs --prefix-cache; prints a lend-rate panel to "
                    "stderr")
p.add_argument("--no-affinity", action="store_true",
               help="disable the router's radix/prefix affinity: "
                    "rendezvous hashes the FULL prompt, so same-template "
                    "requests scatter across the fleet — the adversarial "
                    "placement the lending tier must absorb (the ISSUE "
                    "17 acceptance compares this + --lend against the "
                    "single-replica hit rate)")
p.add_argument("--lend-deadline", type=int, default=4, metavar="STEPS",
               help="first Backoff rung of the lend ladder, in engine "
                    "steps (a dead/slow lender burns rungs, exhaustion "
                    "degrades to local re-prefill)")
p.add_argument("--lend-retries", type=int, default=2, metavar="N",
               help="rung count of the lend ladder")
p.add_argument("--workload", default=None, metavar="SPEC",
               help="bursty two-class trace (ISSUE 14) replacing the "
                    "template workload: key=value pairs (see serve_sim "
                    "--workload) — every request stamped (tenant, class); "
                    "overrides --requests/--templates/--zipf/--max-new. "
                    "Bad fields fail loudly BY NAME")
p.add_argument("--slo", default=None, metavar="SPEC",
               help="per-replica multi-tenant SLO policy (ISSUE 14): "
                    "chat/batch WFQ weights + per-class overrides + "
                    "token-bucket quotas (see serve_sim --slo)")
p.add_argument("--autoscale", action="store_true",
               help="elastic fleet (ISSUE 18): start at --min-replicas "
                    "and let the Autoscaler grow/shrink on windowed "
                    "per-class SLO attainment, draining gracefully "
                    "(journal-cursor requeue + lend-ahead) on the way "
                    "down. Needs --workload (the attainment sensor is "
                    "per-class); overrides --replicas, disables the "
                    "default kill/restore schedule (inject crashes with "
                    "--crash-mid-drain), and defaults --slo to the "
                    "chat-priority WFQ policy so batch — not the "
                    "latency-lagged chat signal — is the binding class. "
                    "Prints an autoscale panel to stderr")
p.add_argument("--min-replicas", type=int, default=1, metavar="N",
               help="autoscale floor AND the starting fleet size")
p.add_argument("--max-replicas", type=int, default=4, metavar="N",
               help="autoscale ceiling; also the static-peak "
                    "counterfactual the panel's replica-steps-saved "
                    "row is measured against")
p.add_argument("--slo-budget", default="chat:12,batch:20", metavar="SPEC",
               help="per-class step-space budgets 'cls:ttft[/itl],...' "
                    "the attainment windows police (parse_budgets)")
p.add_argument("--slo-window", type=int, default=32, metavar="N",
               help="attainment window: finished-request samples kept "
                    "per (kind, class) series")
p.add_argument("--slo-min-samples", type=int, default=6, metavar="N",
               help="samples a series needs before it can drive scaling")
p.add_argument("--cooldown", type=int, default=20, metavar="STEPS",
               help="controller steps between membership changes "
                    "(thrash control, with the up/down hysteresis band)")
p.add_argument("--warm-steps", type=int, default=1, metavar="STEPS",
               help="cluster steps a scale-up spends WARMING before it "
                    "admits (models the artifact-load window)")
p.add_argument("--spill-threshold", type=int, default=None, metavar="N",
               help="router load spill threshold (default: 10 under "
                    "--autoscale — affinity must not pin a template to "
                    "an overloaded replica while peers sit idle — "
                    "otherwise off)")
p.add_argument("--crash-mid-drain", action="store_true",
               help="kill the first replica observed DRAINING (once): "
                    "the controller auto-restores it, journal replay "
                    "requeues its live requests, the drain resumes and "
                    "retires — and every trace must STILL verify "
                    "bitwise (--autoscale only)")
p.add_argument("--mesh", default=None, metavar="TPxSPxEP",
               help="run each colocated replica as a ShardedServingEngine "
                    "on this TP/SP/EP mesh serving the tiny MoE model "
                    "(--engine colocated only; implied 1x1x1 by "
                    "--overlap). Needs tp*sp*ep devices: real ones, or "
                    "--sim")
p.add_argument("--sim", action="store_true",
               help="provision the virtual CPU mesh --mesh needs (the "
                    "simulator is only ever used when asked for; too few "
                    "live devices is otherwise an error naming the count)")
p.add_argument("--overlap", choices=("off", "ep", "ep+sp"), default="off",
               help="fine-grained compute/comm overlap inside each "
                    "sharded replica (ISSUE 16; --engine colocated only). "
                    "The single-replica golden reference always runs "
                    "overlap=off, so the per-request trace verification "
                    "IS the overlap bit-identity check at cluster scale")
p.add_argument("--speculate", default=None, metavar="K",
               help="model-free speculative decoding inside each replica "
                    "(ISSUE 20; --engine colocated only — SimEngine has "
                    "no decode dispatch to draft through): an integer K "
                    "or 'auto'. The single-replica golden reference "
                    "always runs speculate=off, so the per-request trace "
                    "verification IS the spec bit-identity check at "
                    "cluster scale. Prints a fleet spec panel to stderr")
p.add_argument("--artifact", default=None, metavar="DIR",
               help="persisted AOT artifact (ISSUE 15; --engine colocated "
                    "only — SimEngine has nothing to compile). EVERY "
                    "replica — cold-built AND kill/restored — seeds its "
                    "jit caches from the artifact's programs instead of "
                    "tracing; a stale artifact is a loud typed error. "
                    "Prints a cold_start summary line to stderr")
args = p.parse_args()
if args.lend and not args.prefix_cache:
    p.error("--lend needs --prefix-cache (lending moves CACHED prefix "
            "pages; without a cache there is nothing to lend or adopt)")
if args.artifact is not None and args.engine != "colocated":
    p.error("--artifact needs --engine colocated")
if args.speculate is not None:
    if args.speculate != "auto":
        try:
            args.speculate = int(args.speculate)
        except ValueError:
            p.error("--speculate wants an integer K or 'auto'")
    if args.engine != "colocated":
        p.error("--speculate needs --engine colocated (SimEngine's token "
                "function is closed-form — there is no decode dispatch "
                "to draft through)")
if ((args.overlap != "off" or args.mesh is not None)
        and args.engine != "colocated"):
    p.error("--overlap/--mesh need --engine colocated (SimEngine has no "
            "device programs to overlap)")
if args.overlap != "off" and args.mesh is None:
    args.mesh = "1x1x1"
if args.crash_mid_drain and not args.autoscale:
    p.error("--crash-mid-drain needs --autoscale (only elastic drains "
            "can crash mid-drain)")
if args.autoscale:
    if args.workload is None:
        p.error("--autoscale needs --workload (the attainment sensor is "
                "per-class; the template workload has no classes)")
    if not 1 <= args.min_replicas <= args.max_replicas:
        p.error("--autoscale needs 1 <= --min-replicas <= --max-replicas")
    # chat-priority WFQ keeps chat TTFT flat through burst fronts, which
    # makes BATCH the binding scaling class — reactive TTFT sensing lags
    # by the TTFT itself, so the class that can wait must carry the lag
    if args.slo is None:
        args.slo = "chat_weight=4,batch_weight=1"
    if args.spill_threshold is None:
        args.spill_threshold = 10
    args.replicas = args.min_replicas
    args.no_kill = True     # fault injection is --crash-mid-drain here

# multi-tenant SLO scheduling (ISSUE 14): both specs fail loudly NAMING
# the bad field instead of silently replaying a default-shaped trace
slo_policy = None
workload_spec = None
if args.slo is not None:
    from triton_dist_tpu.serving.workload import parse_slo  # noqa: E402
    try:
        slo_policy = parse_slo(args.slo)
    except ValueError as e:
        p.error(str(e))
if args.workload is not None:
    from triton_dist_tpu.serving.workload import parse_workload  # noqa: E402
    try:
        workload_spec = parse_workload(args.workload)
    except ValueError as e:
        p.error(str(e))
    args.requests = workload_spec.n
budgets = None
if args.autoscale:
    from triton_dist_tpu.serving.autoscaler import parse_budgets  # noqa: E402
    try:
        budgets = parse_budgets(args.slo_budget)
    except (AssertionError, ValueError) as e:
        p.error(f"--slo-budget: {e}")

kill_at = args.kill_at if args.kill_at is not None else args.requests // 2
restore_after = (args.restore_after if args.restore_after is not None
                 else max(args.requests // 10, 1))
arrive = args.arrive_per_step or 2 * args.replicas
ckpt_every = args.checkpoint_every or None

from triton_dist_tpu.serving.cluster import (Cluster, SimEngine,  # noqa: E402
                                             expected_tokens)

# AOT artifact (ISSUE 15): loaded ONCE before any replica exists; the
# wall clock for cold-start-to-first-token starts here so the load (or
# the fleet-wide fresh traces it replaces) is inside the measurement
_t_cold0 = time.perf_counter()
artifact = None
if args.artifact is not None:
    from triton_dist_tpu.aot import load_artifact  # noqa: E402
    artifact = load_artifact(args.artifact)

if args.engine == "sim":
    VOCAB = 32000

    def factory(journal):
        # prefix caching needs chunked prefill (a cache hit resumes the
        # chunk cursor past the adopted pages — ISSUE 17); one page per
        # chunk mirrors the colocated engines below
        return SimEngine(num_slots=args.slots, page_size=args.page_size,
                         num_pages=args.pages,
                         pages_per_seq=args.pages_per_seq,
                         journal=journal, checkpoint_every=ckpt_every,
                         slo=slo_policy, prefix_cache=args.prefix_cache,
                         prefill_chunk=(args.page_size
                                        if args.prefix_cache else None))

    def golden(prompt, mnt):
        return expected_tokens(prompt, mnt)
else:
    # the real jitted engine, replica/router layer unchanged. Goldens
    # come from one single-replica reference engine fed every request —
    # the engine's own determinism contract (tokens are a pure function
    # of (params, prompt)) makes per-request traces placement-invariant.
    import jax  # noqa: E402

    from triton_dist_tpu.utils.env import (configure_compile_cache,  # noqa: E402
                                           force_virtual_cpu_devices,
                                           require_devices)
    configure_compile_cache()

    if args.mesh is not None:
        # sharded replicas (ISSUE 16): each replica is the MoE
        # ShardedServingEngine on its own TP/SP/EP mesh, overlap as
        # requested — while the golden reference below is the SAME
        # engine pinned to overlap=off, so every verified trace is an
        # overlap-on-vs-off bit-identity witness
        tp, sp, ep = (int(d) for d in args.mesh.lower().split("x"))
        if args.sim:
            force_virtual_cpu_devices(tp * sp * ep)
        else:
            require_devices(tp * sp * ep, f"--mesh {args.mesh}")
        from triton_dist_tpu.models.moe import (MoEConfig,  # noqa: E402
                                                init_moe_params)
        from triton_dist_tpu.serving import (ShardedServingEngine,  # noqa: E402
                                             serving_mesh)

        cfg = MoEConfig.tiny(n_layers=2)
        params = init_moe_params(jax.random.PRNGKey(args.seed), cfg)
        VOCAB = cfg.base.vocab_size

        def factory(journal, artifact=None):
            return ShardedServingEngine(
                params, cfg, serving_mesh(tp, sp, ep),
                num_slots=args.slots, page_size=args.page_size,
                num_pages=args.pages, pages_per_seq=args.pages_per_seq,
                prefill_chunk=args.page_size, overlap=args.overlap,
                journal=journal, checkpoint_every=ckpt_every,
                prefix_cache=args.prefix_cache, slo=slo_policy,
                speculate=args.speculate, artifact=artifact)

        _ref = ShardedServingEngine(
            params, cfg, serving_mesh(tp, sp, ep), num_slots=args.slots,
            page_size=args.page_size, num_pages=args.pages,
            pages_per_seq=args.pages_per_seq,
            prefill_chunk=args.page_size, overlap="off")
    else:
        from triton_dist_tpu.models.llama import (LlamaConfig,  # noqa: E402
                                                  init_params)
        from triton_dist_tpu.serving import ServingEngine  # noqa: E402

        cfg = LlamaConfig.tiny(n_layers=2)
        params = init_params(jax.random.PRNGKey(args.seed), cfg)
        VOCAB = cfg.vocab_size

        def factory(journal, artifact=None):
            # EngineReplica passes artifact= on the cold build AND on
            # every restore, so a failed-over replica reaches its first
            # replayed token with zero fresh traces too
            return ServingEngine(params, cfg, num_slots=args.slots,
                                 page_size=args.page_size,
                                 num_pages=args.pages,
                                 pages_per_seq=args.pages_per_seq,
                                 prefill_chunk=args.page_size,
                                 journal=journal,
                                 checkpoint_every=ckpt_every,
                                 prefix_cache=args.prefix_cache,
                                 slo=slo_policy,
                                 speculate=args.speculate,
                                 artifact=artifact)

        _ref = ServingEngine(params, cfg, num_slots=args.slots,
                             page_size=args.page_size, num_pages=args.pages,
                             pages_per_seq=args.pages_per_seq,
                             prefill_chunk=args.page_size)
    _ref_cache: dict = {}

    def golden(prompt, mnt):
        key = (tuple(prompt), mnt)
        if key not in _ref_cache:
            rid = _ref.submit(prompt, mnt)
            out = _ref.run(max_steps=200_000)
            _ref_cache[key] = out[rid]
        return _ref_cache[key]

rng = np.random.RandomState(args.seed)
max_plen = args.pages_per_seq * args.page_size - args.max_new
tpl_lens = rng.randint(3, max(4, min(max_plen - 4, 17)),
                       size=args.templates)
templates = [rng.randint(1, VOCAB, size=int(n)).tolist()
             for n in tpl_lens]
ranks = np.arange(1, args.templates + 1, dtype=np.float64)
zipf_p = ranks ** -args.zipf
zipf_p /= zipf_p.sum()

journal_dir = args.journal_dir or tempfile.mkdtemp(prefix="cluster-sim-")
# the golden reference engine (_ref) deliberately stays artifact-OFF:
# bit-identity of every verified trace vs its fresh-traced golden IS the
# artifact-transparency check at cluster scale
cluster = Cluster(factory, replicas=args.replicas, journal_dir=journal_dir,
                  artifact=artifact, affinity=not args.no_affinity,
                  spill_threshold=args.spill_threshold,
                  lend=args.lend, lend_deadline_steps=args.lend_deadline,
                  lend_retries=args.lend_retries)
asc = None
if args.autoscale:
    from triton_dist_tpu.serving.autoscaler import Autoscaler  # noqa: E402
    asc = Autoscaler(cluster, budgets, window=args.slo_window,
                     min_samples=args.slo_min_samples,
                     min_replicas=args.min_replicas,
                     max_replicas=args.max_replicas,
                     cooldown=args.cooldown, warm_steps=args.warm_steps,
                     journal=Autoscaler.journal_path_for(journal_dir))

reqs: dict[int, tuple[list[int], int]] = {}
killed_step = restored_step = None
failover_s = None
tk = None
t0 = time.perf_counter()
submitted = 0
_t_first = None  # wall clock when the cluster's first token surfaced


_crash_fired_at = None


def _maybe_crash_mid_drain() -> None:
    """Forced crash-mid-drain (once): kill the first DRAINING replica we
    see; the controller's next tick restores it, journal replay requeues
    its live requests, and the drain resumes."""
    global _crash_fired_at
    if not args.crash_mid_drain or _crash_fired_at is not None:
        return
    for rep in cluster.replicas:
        if rep.draining and rep.engine is not None:
            cluster.kill(rep.index)
            _crash_fired_at = (rep.index, cluster._cluster_steps)
            print(json.dumps({"crash_mid_drain": {
                "replica": rep.index,
                "at_step": cluster._cluster_steps}}), file=sys.stderr)
            break


def _step() -> None:
    """cluster.step() + the controller tick + first-token clock
    (engine._finished is harvested and cleared inside step, so the
    summary can't read it post-drain)."""
    global _t_first
    cluster.step()
    if asc is not None:
        asc.step()
        _maybe_crash_mid_drain()
    if _t_first is None and cluster._results:
        _t_first = time.perf_counter()


def _maybe_kill_restore() -> None:
    """The mid-run kill/restore cycle, keyed on the submission count —
    shared by the template loop and the --workload loop."""
    global killed_step, restored_step, failover_s, tk
    if not args.no_kill and submitted == kill_at:
        cluster.kill(args.kill_replica)
        killed_step = submitted
        tk = time.perf_counter()
    if (not args.no_kill and killed_step is not None
            and restored_step is None
            and submitted == kill_at + restore_after):
        stats = cluster.restore(args.kill_replica)
        restored_step = submitted
        failover_s = time.perf_counter() - tk
        print(json.dumps({"restore": stats,
                          "failover_us": round(failover_s * 1e6, 1)}),
              file=sys.stderr)


if workload_spec is not None:
    # bursty two-class arrivals (ISSUE 14): the generator's step stamps
    # drive submission cadence; every request lands routed AND stamped
    from collections import deque  # noqa: E402

    from triton_dist_tpu.serving.workload import generate_arrivals  # noqa: E402
    cap = args.pages_per_seq * args.page_size
    if workload_spec.plen[1] + workload_spec.mnt[1] - 1 > cap:
        p.error(f"workload spec field 'plen': plen+mnt-1 = "
                f"{workload_spec.plen[1] + workload_spec.mnt[1] - 1} "
                f"exceeds pages_per_seq*page_size = {cap}")
    if (workload_spec.long > 0
            and workload_spec.lplen[1] + workload_spec.mnt[1] - 1 > cap):
        p.error(f"workload spec field 'lplen': lplen+mnt-1 = "
                f"{workload_spec.lplen[1] + workload_spec.mnt[1] - 1} "
                f"exceeds pages_per_seq*page_size = {cap} — raise "
                f"--pages-per-seq (long-context prompts span many pages)")
    pending = deque(generate_arrivals(workload_spec, vocab=VOCAB,
                                      page_size=args.page_size))
    i = 0
    while pending:
        while pending and pending[0][0] <= i:
            _, prompt, mnt, tenant, cls = pending.popleft()
            gid = cluster.submit(prompt, mnt, tenant=tenant, cls=cls)
            reqs[gid] = (prompt, mnt)
            submitted += 1
            _maybe_kill_restore()
        _step()
        i += 1
else:
    while submitted < args.requests:
        burst = min(arrive, args.requests - submitted)
        for _ in range(burst):
            t = int(rng.choice(args.templates, p=zipf_p))
            tail = rng.randint(1, VOCAB,
                               size=int(rng.randint(1, 5))).tolist()
            prompt = (templates[t] + tail)[:max_plen]
            mnt = int(rng.randint(2, args.max_new + 1))
            gid = cluster.submit(prompt, mnt)
            reqs[gid] = (prompt, mnt)
            submitted += 1
            _maybe_kill_restore()
        _step()
if asc is not None:
    # drain the tail with the controller still ticking: a crash-mid-drain
    # landing near the end needs its auto-restore, and a quiet cluster
    # step right after one is NOT quiescence — hence the idle debounce
    idle = 0
    while idle < 3:
        idle = 0 if cluster.step() else idle + 1
        asc.step()
        _maybe_crash_mid_drain()
    results = cluster.results()
else:
    results = cluster.drain()
if _t_first is None and cluster._results:
    _t_first = time.perf_counter()
wall = time.perf_counter() - t0

# -- verification: every surviving trace vs its single-replica golden ----
missing = sorted(set(reqs) - set(results) - cluster.failed_gids)
mismatched = [g for g, toks in results.items()
              if toks != golden(*reqs[g])]
ok = not missing and not mismatched

per_replica = [0] * len(cluster.replicas)   # elastic: may exceed seed N
for gid, (ri, _) in cluster._placement.items():
    per_replica[ri] += 1
if args.prefix_cache:
    # aggregate the per-replica engine caches; the reference engine is
    # cache-off on purpose — bit-identity of verified traces IS the
    # cache-transparency check at cluster scale
    agg: dict[str, int] = {}
    from triton_dist_tpu.serving.metrics import Histogram  # noqa: E402
    # wall-clock split (device engines) AND step-space split (SimEngine)
    # — cold vs cached vs REWARMED (pages adopted from a peer, ISSUE 17);
    # the kill/restore acceptance is rewarmed ≈ cached, NOT cold
    wall_h = {k: Histogram() for k in ("cold", "cached", "rewarmed")}
    step_h = {k: Histogram() for k in ("cold", "cached", "rewarmed")}
    for rep in cluster.replicas:
        if rep.engine is None:
            continue
        c = rep.engine.metrics.counters
        for k in ("prefix_hits", "prefix_misses", "prefix_hit_tokens",
                  "cow_copies", "prefix_evictions"):
            agg[k] = agg.get(k, 0) + c[k]
        for kind in ("cold", "cached", "rewarmed"):
            for src, dst in ((f"ttft_{kind}_s", wall_h[kind]),
                             (f"ttft_{kind}_steps", step_h[kind])):
                for v in rep.engine.metrics.hist[src]._samples:
                    dst.observe(v)
    hm = lambda h: (None if h.mean is None  # noqa: E731
                    else round(h.mean * 1e6, 1))
    split = {f"ttft_{k}_us_mean": hm(wall_h[k])
             for k in ("cold", "cached", "rewarmed")}
    if any(h.count for h in step_h.values()):   # SimEngine's step space
        split.update({f"ttft_{k}_steps_mean":
                      None if step_h[k].mean is None
                      else round(step_h[k].mean, 2)
                      for k in ("cold", "cached", "rewarmed")})
    print(json.dumps({
        "prefix_cache": True,
        **agg,
        "hit_rate": round(agg["prefix_hits"]
                          / max(agg["prefix_hits"]
                                + agg["prefix_misses"], 1), 3),
        "router_radix_hits": cluster.metrics.counters["router_radix_hits"],
        "router_radix_misses":
            cluster.metrics.counters["router_radix_misses"],
        **split,
    }), file=sys.stderr)
if args.lend:
    # lend-rate panel (ISSUE 17): how much of the fleet's hit rate the
    # lending tier bought, and what each lent page cost
    cm = cluster.metrics
    lp = cm.hist["lend_us_per_page"]
    print(json.dumps({
        "lend": True,
        "affinity": not args.no_affinity,
        "lends": cm.counters["lends"],
        "lent_pages": cm.counters["lent_pages"],
        "lend_tokens": cm.counters["lend_tokens"],
        "lend_degradations": cm.counters["lend_degradations"],
        "rewarmed_prefixes": cm.counters["rewarmed_prefixes"],
        "lend_rate": round(cm.counters["lends"]
                           / max(args.requests, 1), 4),
        "lend_us_per_page_mean": None if lp.mean is None
        else round(lp.mean, 1),
    }), file=sys.stderr)
if workload_spec is not None or slo_policy is not None:
    # per-class fleet aggregate (ISSUE 14): summed over alive replicas
    agg_cls: dict[str, dict[str, int]] = {}
    throttled = 0
    for rep in cluster.replicas:
        if rep.engine is None:
            continue
        throttled += rep.engine.metrics.counters.get("quota_throttled", 0)
        for c, row in rep.engine.metrics.per_class().items():
            dst = agg_cls.setdefault(c, {"finished": 0, "rejections": 0,
                                         "expirations": 0})
            for k in dst:
                dst[k] += row[k]
    print(json.dumps({"per_class": agg_cls,
                      "quota_throttled": throttled}), file=sys.stderr)
    if workload_spec is not None and workload_spec.long > 0:
        # long-class panel (ISSUE 19): the long tenants' fleet view —
        # whether 64k-class prompts finished inside their TTL, how often
        # the chunk budget clamped a dispatch to protect decode ITL, and
        # the long-vs-fleet TTFT tail the clamp is trading against
        from triton_dist_tpu.serving.metrics import Histogram  # noqa: E402
        _lt, _li = Histogram(), Histogram()
        _shrinks = 0
        for rep in cluster.replicas:
            if rep.engine is None:
                continue
            m = rep.engine.metrics
            _shrinks += m.counters.get("chunk_shrinks", 0)
            for src, dst in ((m.hist.get(m.class_key("ttft_s", "long")),
                              _lt),
                             (m.hist.get(m.class_key("itl_s", "long")),
                              _li)):
                for v in (src._samples if src is not None else ()):
                    dst.observe(v)
        _us = lambda v: (None if v is None  # noqa: E731
                         else round(v * 1e6, 1))
        _row = agg_cls.get("long", {})
        print(json.dumps({
            "long_class": True,
            "long_share": workload_spec.long,
            "lplen": list(workload_spec.lplen),
            "finished": _row.get("finished", 0),
            "rejections": _row.get("rejections", 0),
            "expirations": _row.get("expirations", 0),
            "chunk_shrinks": _shrinks,
            "ttft_long_p50_us": _us(_lt.percentile(50)),
            "ttft_long_p99_us": _us(_lt.percentile(99)),
            "itl_long_p99_us": _us(_li.percentile(99)),
        }), file=sys.stderr)
# cold-start summary (ISSUE 15): fleet-wide fresh traces paid before any
# token, plus wall time from cold start (artifact load / replica builds)
# to the cluster's first token. Printed for every --engine colocated run
# so artifact-on vs artifact-off compare 1:1; restored replicas are
# included — their compiles land in the same aggregate.
if args.engine == "colocated":
    _alive = [rep.engine for rep in cluster.replicas
              if rep.engine is not None]
    _stats = [e.compile_stats for e in _alive]
    print(json.dumps({"cold_start": {
        "artifact": args.artifact,
        "replicas_alive": len(_alive),
        "cold_start_compiles": sum(
            v for s in _stats for k, v in s.items()
            if k.endswith("_compiles")),
        "aot_programs": sum(s.get("aot_programs", 0) for s in _stats),
        "cold_start_to_first_token_s":
            None if _t_first is None else round(_t_first - _t_cold0, 4),
    }}), file=sys.stderr)

if args.autoscale:
    # autoscale panel (ISSUE 18): the fleet-size timeline against the
    # offered rate, per-class attainment, the replica-steps-saved row
    # against the static-peak counterfactual (a fleet of --max-replicas
    # stepping every cluster step — the provisioning the autoscaler
    # replaces; counterfactual, not a second run), and the scale-up-to-
    # first-token split (replica build/artifact-load wall time vs fresh
    # compiles — the latter must be zero with an artifact)
    from triton_dist_tpu.serving.workload import rate_at  # noqa: E402
    cm = cluster.metrics
    csteps = cluster._cluster_steps
    rsteps = cm.counters["replica_steps"]
    static_peak = args.max_replicas * csteps
    att_rows = {}
    for _cls in sorted(budgets):
        b_ttft, b_itl = budgets[_cls]
        for _kind, _budget in (("ttft", b_ttft), ("itl", b_itl)):
            if _budget is None:
                continue
            _key = (_kind, _cls)
            if asc.attain.count(_key):
                att_rows[f"{_kind}_{_cls}_attainment"] = round(
                    asc.attain.attainment(_key, _budget), 3)
        # whole-run step-space tail next to the windowed attainment — the
        # window only remembers the newest --slo-window finishes
        _h = cm.hist.get(cm.class_key("ttft_steps", _cls))
        if _h is not None and _h.count:
            att_rows[f"ttft_{_cls}_p99_steps"] = _h.percentile(99)
    _bs = asc.scale_up_build_s
    panel = {
        "autoscale": True,
        "min_replicas": args.min_replicas,
        "max_replicas": args.max_replicas,
        "fleet_final": cluster.lifecycle_counts(),
        "scale_ups": cm.counters["scale_ups"],
        "drains_done": cm.counters["drains_done"],
        "retires": cm.counters["retires"],
        "requeues": cm.counters["requeues"],
        "lend_aheads": cm.counters["lend_aheads"],
        "lend_ahead_pages": cm.counters["lend_ahead_pages"],
        "lend_ahead_noops": cm.counters["lend_ahead_noops"],
        "cluster_steps": csteps,
        "replica_steps": rsteps,
        "static_peak_replica_steps": static_peak,
        "replica_steps_saved_pct": round(
            100.0 * (1 - rsteps / max(static_peak, 1)), 1),
        "warm_steps": args.warm_steps,
        "scale_up_build_s_mean": None if not _bs
        else round(sum(_bs) / len(_bs), 6),
        **att_rows,
        "controller_journal": None if asc.journal is None
        else asc.journal.path,
        "crash_mid_drain": None if not args.crash_mid_drain else (
            None if _crash_fired_at is None
            else {"replica": _crash_fired_at[0],
                  "at_step": _crash_fired_at[1]}),
        # every membership event with the offered rate at that step —
        # rate_at is the SAME function the generator drew arrivals from,
        # so the two timelines always agree
        "timeline": [
            {"step": s, "kind": k, "replica": i,
             "offered_rate": rate_at(workload_spec, s)}
            for s, k, i in cluster.scale_history],
    }
    if args.engine == "colocated":
        # the split's other half: late joiners must seed from the
        # artifact — fresh traces at scale-up time would put compile
        # latency inside the scale-up-to-first-token window
        _late = [r.engine for r in cluster.replicas
                 if r.index >= args.min_replicas and r.engine is not None]
        panel["scale_up_aot_programs"] = sum(
            e.compile_stats.get("aot_programs", 0) for e in _late)
        panel["scale_up_fresh_compiles"] = sum(
            v for e in _late for k, v in e.compile_stats.items()
            if k.endswith("_compiles"))
    print(json.dumps(panel), file=sys.stderr)

if args.mesh is not None:
    # overlap panel (ISSUE 16): fleet-aggregated per-step EP wire split
    # under the wire-fit model (serving/sharded.py _comm_split_us) —
    # modeled, labeled as such: CPU wall clock serializes ranks and can
    # never show real overlap. overlap=off replicas report all-exposed.
    _exp = _ovl = 0.0
    _cnt = 0
    _mb = None
    for rep in cluster.replicas:
        if rep.engine is None:
            continue
        _h = rep.engine.metrics.hist
        _exp += _h["exposed_comm_us"].total
        _ovl += _h["overlapped_comm_us"].total
        _cnt += _h["exposed_comm_us"].count
        _mb = rep.engine.overlap_microbatches
    print(json.dumps({
        "overlap": args.overlap, "mesh": args.mesh,
        "overlap_microbatches": _mb,
        "exposed_comm_us_mean": round(_exp / max(_cnt, 1), 2),
        "overlapped_comm_us_mean": round(_ovl / max(_cnt, 1), 2),
    }), file=sys.stderr)

if args.speculate is not None:
    # spec panel (ISSUE 20): fleet-aggregated draft economics. The
    # golden reference is speculate-OFF, so the verified_bit_identical
    # count in the summary below is the spec-transparency witness.
    from triton_dist_tpu.serving.metrics import Histogram  # noqa: E402
    _acc = Histogram()
    _drafted = _accepted = _rewinds = _sdisp = 0
    for rep in cluster.replicas:
        if rep.engine is None:
            continue
        _c = rep.engine.metrics.counters
        _drafted += _c["draft_tokens"]
        _accepted += _c["draft_accepted"]
        _rewinds += _c["spec_rewinds"]
        _sdisp += _c["spec_dispatches"]
        for v in rep.engine.metrics.hist["accepted_per_dispatch"]._samples:
            _acc.observe(v)
    print(json.dumps({
        "speculate": args.speculate,
        "spec_dispatches": _sdisp,
        "accepted_per_dispatch_mean": None if _acc.mean is None
        else round(_acc.mean, 3),
        "draft_hit_rate": round(_accepted / _drafted, 4)
        if _drafted else None,
        "spec_rewinds": _rewinds,
    }), file=sys.stderr)

toks_total = sum(len(t) for t in results.values())
ttft = cluster.metrics.hist["ttft_s"]
us = lambda v: None if v is None else round(v * 1e6, 1)  # noqa: E731
print(json.dumps({
    "engine": args.engine,
    "replicas": args.replicas,
    "requests": args.requests,
    "finished": len(results),
    "failed": len(cluster.failed_gids),
    "verified_bit_identical": len(results) - len(mismatched),
    "mismatched": len(mismatched),
    "missing": len(missing),
    "wall_s": round(wall, 3),
    "agg_tok_per_s": round(toks_total / wall, 1) if wall else None,
    "ttft_p50_us": us(ttft.percentile(50)),
    "ttft_p99_us": us(ttft.percentile(99)),
    "per_replica_requests": per_replica,
    "kill": None if args.no_kill else {
        "replica": args.kill_replica, "at_request": killed_step,
        "restored_at_request": restored_step,
        "failover_us": None if failover_s is None
        else round(failover_s * 1e6, 1)},
    "journal_dir": journal_dir,
}))
if not ok:
    print(json.dumps({"error": "trace verification failed",
                      "missing": missing[:10],
                      "mismatched": mismatched[:10]}), file=sys.stderr)
    sys.exit(1)
