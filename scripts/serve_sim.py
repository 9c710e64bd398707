"""Synthetic-trace replay through the continuous-batching serving engine
(docs/serving.md). Generates a deterministic request trace (seeded prompt
lengths / decode budgets / staggered arrivals), drives ``ServingEngine``
to completion, and prints the metrics snapshot as ONE JSON line — the same
counters/histograms bench.py's ``serving_*`` extras are built from, with
matching knobs (--slots/--page-size/--layers mirror bench_serving's).

    python scripts/serve_sim.py --sim 50
    python scripts/serve_sim.py --sim 20 --slots 8 --pages 12  # preempts
    python scripts/serve_sim.py --sim 20 --model moe --mesh 1x2x2
    python scripts/serve_sim.py --sim 20 --disagg --mesh 1x2x1  # composed
    python scripts/serve_sim.py --sim 30 --crash-at 25 --recover  # ISSUE 9
    python scripts/serve_sim.py --sim 40 --queue-cap 6 --ttl 50  # overload
    python scripts/serve_sim.py --preset mistral_7b --layers 28 \
        --workload 'n=12,plen=128:1024,mnt=32:64' ...   # on the chip

``--sim N`` replays N uniform requests ON THE SIMULATOR: with ``--mesh`` /
``--disagg`` it provisions the virtual CPU mesh they need. Without it
(``--workload``) the live devices are driven, and too few of them is an
error naming the count — never a silent CPU mesh (chip_smoke.py runs the
full-width presets this way).

A deliberately small --pages forces preemption-by-eviction; the replay is
bit-deterministic (same seed => same tokens, same metrics counters), which
is also how tests/test_serving.py pins the trace down. ``--mesh TPxSPxEP``
serves the MoE model through ``ShardedServingEngine`` under shard_map
(docs/serving.md "Sharded serving"); the replay stays bit-identical across
mesh shapes when --wire is pinned (``auto`` resolves per rank count).
"""
import argparse
import json
import sys

REPO = __file__.rsplit("/", 2)[0]
sys.path.insert(0, REPO)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from triton_dist_tpu.models import (init_moe_params, init_params,  # noqa: E402
                                    preset_config)
from triton_dist_tpu.serving import ServingEngine  # noqa: E402
from triton_dist_tpu.utils.env import (configure_compile_cache,  # noqa: E402
                                       force_virtual_cpu_devices,
                                       require_devices)

p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
p.add_argument("--sim", type=int, default=None,
               help="replay this many uniform synthetic requests (default "
                    "50) on the SIMULATOR: --mesh/--disagg then get the "
                    "virtual CPU mesh they need. Omitted (--workload), the "
                    "live devices are used and too few is an error")
p.add_argument("--slots", type=int, default=4,
               help="continuous-batching slots (engine batch rows)")
p.add_argument("--page-size", type=int, default=8,
               help="KV pool page size in tokens (multiple of 8)")
p.add_argument("--pages", type=int, default=24,
               help="usable KV pool pages (small => forced preemption)")
p.add_argument("--pages-per-seq", type=int, default=8,
               help="block-table width (max pages one request may own)")
p.add_argument("--preset", default="tiny",
               help="model preset: a LlamaConfig/MoEConfig classmethod "
                    "name (tiny, mistral_7b, mixtral_8x7b, deepseek_infer, "
                    "...) at its published widths; an MoE-only name "
                    "implies --model moe")
p.add_argument("--layers", type=int, default=None,
               help="depth override — the one cut that fits a full-width "
                    "preset beside a KV pool (default: the preset's own)")
p.add_argument("--max-new", type=int, default=12,
               help="max decode budget per request (uniform 2..max-new)")
p.add_argument("--arrive-every", type=int, default=2,
               help="one new request submitted every N engine steps")
p.add_argument("--seed", type=int, default=0, help="trace RNG seed")
p.add_argument("--tokens", action="store_true",
               help="also print one JSON line per finished request")
p.add_argument("--decode-horizon", type=int, default=1,
               help="K: scanned decode steps per host dispatch")
p.add_argument("--speculate", default=None, metavar="K",
               help="model-free speculative decoding (ISSUE 20): draft up "
                    "to K-1 tokens per slot from the bigram prompt-lookup "
                    "drafter and verify ALL K positions in the one decode "
                    "dispatch (exact-match-greedy accept) — an integer K "
                    "or 'auto' (tuned registry, workload-bucketed). "
                    "Tokens stay bit-identical to greedy; only the "
                    "dispatch count moves. Prints a spec panel "
                    "(accepted/dispatch, draft hit rate, rewinds) to "
                    "stderr. Owns the horizon (needs --decode-horizon 1); "
                    "not plumbed through --disagg")
p.add_argument("--prefill-chunk", type=int, default=16,
               help="prompt tokens per prefill chunk: the one chunk "
                    "program's row count (≤1 chunk per step rides beside "
                    "the decode dispatch)")
p.add_argument("--disagg", action="store_true",
               help="disaggregated prefill/decode over a 2-rank role mesh "
                    "(KV handed off by page migration; needs >= 2 devices; "
                    "a prefill chunk is the migration unit)")
p.add_argument("--model", choices=("llama", "moe"), default=None,
               help="'moe' serves the MoE preset through the sharded "
                    "engine (EP MoE FFN; defaults --mesh to 1x1x1); "
                    "default: the --preset's family, dense first")
p.add_argument("--mesh", default=None, metavar="TPxSPxEP",
               help="serve under shard_map on this TP/SP/EP mesh, e.g. "
                    "2x2x2 (implies --model moe; needs tp*sp*ep devices "
                    "— real ones, or simulated under --sim). "
                    "Combine with --disagg "
                    "for the COMPOSED engine: disaggregated prefill "
                    "feeding a sharded decode fleet on this one mesh")
p.add_argument("--wire", choices=("auto", "fp8", "none"), default="auto",
               help="A2A wire dtype for --mesh: 'auto' (wire-fit driven, "
                    "resolves PER RANK COUNT), 'fp8' (pinned e4m3 — use "
                    "this when comparing tokens across mesh shapes), "
                    "'none' (full-width wire)")
p.add_argument("--long-context", action="store_true",
               help="distributed flash-decode (ISSUE 19) for --mesh: the "
                    "KV pool is laid out interleaved so each SP rank owns "
                    "every sp-th page of EVERY request, decode attention "
                    "runs flash_decode_dist (per-page softmax partials, "
                    "one-sided folds), and one request's context may span "
                    "the whole mesh. Tokens stay bit-identical to the "
                    "replicated layout at any rank count. Prints a MODELED "
                    "per-step attention split (local scan vs fold wait) "
                    "to stderr")
p.add_argument("--overlap", choices=("off", "ep", "ep+sp"), default="off",
               help="fine-grained compute/comm overlap for --mesh "
                    "(ISSUE 16): 'ep' microbatches each EP dispatch so "
                    "expert FFN overlaps the segmented a2a, 'ep+sp' also "
                    "starts local attention-pool assembly under the "
                    "allgather. Tokens stay bit-identical to 'off' — the "
                    "schedule moves, the reduction order never does")
p.add_argument("--chaos", default=None, metavar="SPEC",
               help="seeded fault injection on the migration signal plane "
                    "(implies --disagg): a bare integer seed (default "
                    "drop/delay probabilities) or a FaultPlan spec like "
                    "'seed=3,drop=0.2,dup=0.05,delay=0.3,dead=40,"
                    "rids=1|4|7'. Replays are bit-deterministic per spec; "
                    "a chaos summary line (retries / degradations / "
                    "failures / recovery latencies) is printed to stderr")
p.add_argument("--crash-at", type=int, default=None, metavar="STEP",
               help="inject a hard crash (InjectedCrash) at this engine "
                    "step; with --recover a FRESH engine is rebuilt from "
                    "the journal and the replay continues (the crash-"
                    "consistency demo, docs/robustness.md). Without "
                    "--recover the crash propagates (exit 1)")
p.add_argument("--recover", action="store_true",
               help="after --crash-at fires, restore a fresh engine from "
                    "the journal (checkpoint + WAL-suffix replay, zero new "
                    "compiles) and finish the trace; prints a recovery "
                    "summary line to stderr. Tokens stay bit-identical to "
                    "the crash-free replay")
p.add_argument("--checkpoint-every", type=int, default=16, metavar="N",
               help="control-plane checkpoint cadence in engine steps "
                    "(journaled runs only; 0 disables checkpoints — "
                    "recovery then replays the whole journal)")
p.add_argument("--queue-cap", type=int, default=None, metavar="N",
               help="bounded admission queue: submissions past N queued "
                    "requests are REJECTED with a typed terminal "
                    "(overload shedding; counted in 'rejections')")
p.add_argument("--ttl", type=int, default=None, metavar="STEPS",
               help="per-request TTL in engine steps: queued requests "
                    "never admitted within the budget EXPIRE with a typed "
                    "terminal (counted in 'expirations')")
p.add_argument("--prefix-cache", action="store_true",
               help="ref-counted copy-on-write prefix caching (ISSUE 13): "
                    "finished prompts' full KV pages stay indexed in a "
                    "radix trie and later shared-prefix prompts adopt them "
                    "instead of re-prefilling; prints a hit-rate + "
                    "cached/cold TTFT summary line to stderr (implies the "
                    "chunked prefill path)")
p.add_argument("--prompt-zipf", default=None, metavar="ALPHA:POOL",
               help="Zipf-shared-prompt generator: draw each request's "
                    "prefix from a POOL of shared page-aligned prefixes "
                    "with Zipf(ALPHA) popularity and append a short "
                    "random tail — the workload prefix caching exists "
                    "for (e.g. 1.1:8). Deterministic per --seed")
p.add_argument("--lend-warm", type=int, default=None, metavar="N",
               help="cluster-wide prefix sharing (ISSUE 17) in one "
                    "process: a peer LENDER engine prefills the top-N "
                    "--prompt-zipf pool prefixes, then lends them to the "
                    "serving engine over the export/adopt page surface "
                    "BEFORE the trace starts — head-of-pool prompts hit "
                    "as REWARMED (peer-adopted) pages instead of paying "
                    "a cold prefill; prints a lend panel to stderr. "
                    "Needs --prefix-cache + --prompt-zipf on the plain "
                    "engine (no --mesh/--disagg)")
p.add_argument("--workload", default=None, metavar="SPEC",
               help="bursty two-class trace (ISSUE 14) replacing the "
                    "uniform generator: key=value pairs, e.g. 'n=200,"
                    "seed=7,chat=0.7,rate=0.5,burst_every=64,burst_len="
                    "16,burst_x=4,zipf=1.2,prefixes=8,tenants=3,plen="
                    "4:20,mnt=2:10' — Zipf prompt sharing x chat-vs-"
                    "batch heterogeneity x diurnal bursts, every request "
                    "stamped (tenant, class). Bad fields fail loudly BY "
                    "NAME. Overrides --sim/--arrive-every/--prompt-zipf")
p.add_argument("--artifact", default=None, metavar="DIR",
               help="load a persisted AOT serving artifact (built by "
                    "tools/compile_aot.py) and seed the engine's compiled "
                    "programs from it — zero fresh jit traces from cold "
                    "start to first token. A stale or mismatched artifact "
                    "is a loud typed error, never a silent re-trace. The "
                    "cold-start summary line on stderr reports "
                    "cold_start_compiles and cold-start-to-first-token "
                    "time either way; with --recover the restarted "
                    "incarnation seeds from the same artifact")
p.add_argument("--slo", default=None, metavar="SPEC",
               help="multi-tenant SLO policy (ISSUE 14): chat/batch WFQ "
                    "weights, per-class overrides and token-bucket "
                    "quotas, e.g. 'chat_weight=4,batch_weight=1,"
                    "batch_cap=8,batch_ttl=40,chat_stall=4,quota="
                    "b0:1:4|b1:2:8'. Adds a per-class summary panel "
                    "(TTFT/ITL p50/p99, shed counts) to stderr")
args = p.parse_args()
if args.recover and args.crash_at is None:
    p.error("--recover needs --crash-at")
if args.chaos is not None:
    args.disagg = True
simulate = args.sim is not None
if args.sim is None:
    args.sim = 50
if args.mesh is not None:
    args.model = "moe"
try:
    args.model, cfg = preset_config(args.preset, args.model, args.layers)
except ValueError as e:
    p.error(str(e))
if args.model == "moe" and args.mesh is None:
    args.mesh = "1x1x1"
if args.overlap != "off" and (args.mesh is None or args.disagg):
    p.error("--overlap rides the sharded engine: needs --mesh (or "
            "--model moe) and is not plumbed through --disagg")
if args.long_context and (args.mesh is None or args.disagg):
    p.error("--long-context rides the sharded engine: needs --mesh (or "
            "--model moe) and is not plumbed through --disagg")
if args.speculate is not None:
    if args.speculate != "auto":
        try:
            args.speculate = int(args.speculate)
        except ValueError:
            p.error("--speculate wants an integer K or 'auto'")
    if args.disagg:
        p.error("--speculate is not plumbed through --disagg (the verify "
                "dispatch is the colocated/sharded ONE-decode program)")
    if args.decode_horizon != 1:
        p.error("--speculate owns the decode horizon (the verify row "
                "block IS the multistep machinery): needs "
                "--decode-horizon 1")
if args.lend_warm is not None and (
        not args.prefix_cache or args.prompt_zipf is None
        or args.disagg or args.mesh is not None):
    p.error("--lend-warm needs --prefix-cache + --prompt-zipf on the "
            "plain engine (no --mesh/--disagg): lending moves CACHED "
            "prefix pages between two engines of the same model")
configure_compile_cache()

# device gate: with --disagg on top of --mesh the composed engine runs
# BOTH fleets on the one mesh (ISSUE 12) — the count is still tp*sp*ep;
# plain --disagg needs the 2-rank role mesh
mesh_ctx = None
if args.mesh is not None:
    tp, sp, ep = (int(d) for d in args.mesh.lower().split("x"))
    n_devices, what = tp * sp * ep, f"--mesh {args.mesh}"
else:
    n_devices, what = (2 if args.disagg else 1), "--disagg"
if n_devices > 1:
    if simulate:
        force_virtual_cpu_devices(n_devices)
    else:
        require_devices(n_devices, what)
if args.mesh is not None:
    # the mesh comes FIRST: weights are then born sharded on it
    from triton_dist_tpu.serving import (serving_mesh,  # noqa: E402
                                         serving_param_shardings)
    mesh_ctx = serving_mesh(tp, sp, ep)

# init under jit: each weight is generated straight into its final dtype
# and placement — no f32 [L, D, F] transient, and on a mesh no device
# ever holds more than its own shard
init_fn = init_moe_params if args.model == "moe" else init_params
params = jax.jit(
    init_fn, static_argnums=1,
    out_shardings=None if mesh_ctx is None
    else serving_param_shardings(mesh_ctx))(
    jax.random.PRNGKey(args.seed), cfg)
vocab = (cfg.base if args.model == "moe" else cfg).vocab_size

# multi-tenant SLO policy (ISSUE 14): both specs fail loudly NAMING the
# bad field (argparse-style) instead of replaying a default-shaped trace
slo_policy = None
if args.slo is not None:
    from triton_dist_tpu.serving import parse_slo  # noqa: E402
    try:
        slo_policy = parse_slo(args.slo)
    except ValueError as e:
        p.error(str(e))
workload_spec = None
if args.workload is not None:
    from triton_dist_tpu.serving import parse_workload  # noqa: E402
    try:
        workload_spec = parse_workload(args.workload)
    except ValueError as e:
        p.error(str(e))
    args.sim = workload_spec.n

# speculative decoding (ISSUE 20): the kwargs ride beside `common`
# instead of inside it so the disagg branches (already p.error-fenced
# above) never see the knob; 'auto' resolution is bucketed by the
# workload shape when a --workload spec is in play
spec_kwargs = {}
if args.speculate is not None:
    bucket = 0
    if workload_spec is not None:
        from triton_dist_tpu.serving import spec_bucket_of  # noqa: E402
        bucket = spec_bucket_of(workload_spec)
    spec_kwargs = dict(speculate=args.speculate, spec_bucket=bucket)

# crash-consistency plumbing: journaled runs get a WAL + periodic
# checkpoints; --crash-at adds an engine-tier fault plan on top of any
# --chaos signal-plane plan (the two tiers compose, see test_chaos.py)
journaled = (args.crash_at is not None or args.queue_cap is not None
             or args.ttl is not None)
journal = None
if journaled:
    from triton_dist_tpu.serving import ControlJournal  # noqa: E402
    journal = ControlJournal()
ckpt_every = args.checkpoint_every or None if journaled else None


def _fault_plan():
    from triton_dist_tpu.shmem import FaultPlan  # noqa: E402
    plan = FaultPlan.from_spec(args.chaos) if args.chaos else None
    if args.crash_at is not None:
        import dataclasses as _dc  # noqa: E402
        plan = (_dc.replace(plan, crash_at=(args.crash_at,)) if plan
                else FaultPlan(seed=args.seed, crash_at=(args.crash_at,)))
    return plan


# AOT artifact (ISSUE 15): load BEFORE any engine is built so the
# engine's jit caches seed from persisted programs instead of tracing.
# The wall clock starts here — cold-start-to-first-token covers the
# artifact load (or the fresh traces it replaces) plus the first dispatch.
import time as _time  # noqa: E402

_t_cold0 = _time.perf_counter()
artifact = None
if args.artifact is not None:
    from triton_dist_tpu.aot import load_artifact  # noqa: E402
    artifact = load_artifact(args.artifact)


def mk_engine(fresh=False):
    """Build the selected engine. ``fresh=True`` is the restarted
    incarnation after a crash: same configuration, same journal — the
    fault plan rides along unchanged (crash injection is incarnation-
    gated, so it fires only once)."""
    common = dict(num_slots=args.slots, page_size=args.page_size,
                  num_pages=args.pages, pages_per_seq=args.pages_per_seq,
                  decode_horizon=args.decode_horizon, journal=journal,
                  checkpoint_every=ckpt_every, queue_cap=args.queue_cap,
                  ttl_steps=args.ttl, fault_plan=_fault_plan(),
                  prefix_cache=args.prefix_cache, slo=slo_policy,
                  artifact=artifact)
    if args.mesh is not None and args.disagg:
        # ISSUE 12: the composed engine — disaggregated prefill feeding a
        # ShardedServingEngine decode fleet on ONE TP/SP/EP mesh (the
        # unified pool contract made the old mutual exclusion obsolete)
        import jax.numpy as jnp  # noqa: E402

        from triton_dist_tpu.serving import DisaggShardedEngine  # noqa: E402
        wire = {"auto": "auto", "fp8": jnp.float8_e4m3fn,
                "none": None}[args.wire]
        eng = DisaggShardedEngine(params, cfg, mesh_ctx,
                                  prefill_chunk=args.prefill_chunk,
                                  wire_dtype=wire, **common)
        if not fresh:
            print(json.dumps({"mesh": eng.mesh_desc, "disagg": True,
                              "wire": eng.wire_dtype}), file=sys.stderr)
        if args.chaos is not None and not fresh:
            print(json.dumps({"chaos": eng._fault_plan.describe()}),
                  file=sys.stderr)
    elif args.mesh is not None:
        import jax.numpy as jnp  # noqa: E402

        from triton_dist_tpu.serving import ShardedServingEngine  # noqa: E402
        wire = {"auto": "auto", "fp8": jnp.float8_e4m3fn,
                "none": None}[args.wire]
        eng = ShardedServingEngine(params, cfg, mesh_ctx,
                                   prefill_chunk=args.prefill_chunk,
                                   wire_dtype=wire, overlap=args.overlap,
                                   long_context=args.long_context,
                                   **spec_kwargs, **common)
        if not fresh:
            # wire=auto resolves PER DISPATCH SIZE and rank count (PR 8
            # caveat), so decode and chunk can land on different wire
            # dtypes at the same mesh — print both resolutions so an
            # --wire auto run is auditable without rerunning pinned
            print(json.dumps({"mesh": eng.mesh_desc,
                              "wire_requested": args.wire,
                              "wire": eng.wire_dtype,
                              "wire_chunk": eng.wire_dtype_chunk,
                              "overlap": eng.overlap,
                              "overlap_microbatches":
                                  eng.overlap_microbatches}),
                  file=sys.stderr)
    elif args.disagg:
        from triton_dist_tpu.serving import DisaggServingEngine  # noqa: E402
        eng = DisaggServingEngine(params, cfg,
                                  prefill_chunk=args.prefill_chunk, **common)
        if args.chaos is not None and not fresh:
            print(json.dumps({"chaos": eng._fault_plan.describe()}),
                  file=sys.stderr)
    else:
        eng = ServingEngine(params, cfg, prefill_chunk=args.prefill_chunk,
                            **spec_kwargs, **common)
    return eng


eng = mk_engine()

rng = np.random.RandomState(args.seed)
max_plen = min(args.pages_per_seq * args.page_size - args.max_new, 24)
arrivals = []
if workload_spec is not None:
    # the bursty two-class trace (ISSUE 14): 5-tuple arrivals carrying
    # (tenant, class) stamps; run() feeds them through submit()
    from triton_dist_tpu.serving import generate_arrivals  # noqa: E402
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
    arrivals = generate_arrivals(workload_spec, vocab=vocab,
                                 page_size=args.page_size)
elif args.prompt_zipf is not None:
    # the shared-prompt workload: page-aligned prefixes drawn from a
    # small pool with Zipf popularity, plus a short random tail — head
    # prefixes repeat often enough that a prefix cache serves most of
    # their prompt tokens from adopted pages
    alpha_s, pool_s = args.prompt_zipf.split(":")
    alpha, pool_n = float(alpha_s), int(pool_s)
    assert alpha > 0 and pool_n >= 1, "--prompt-zipf wants ALPHA:POOL > 0"
    prefix_len = max(args.page_size,
                     (max(max_plen - 5, args.page_size)
                      // args.page_size) * args.page_size)
    pool = [rng.randint(1, vocab, size=prefix_len).tolist()
            for _ in range(pool_n)]
    w = np.arange(1, pool_n + 1, dtype=np.float64) ** -alpha
    w /= w.sum()
    for i in range(args.sim):
        k = int(rng.choice(pool_n, p=w))
        tail = rng.randint(1, vocab,
                           size=int(rng.randint(1, 5))).tolist()
        mnt = int(rng.randint(2, max(3, args.max_new + 1)))
        arrivals.append((i * args.arrive_every // max(args.arrive_every, 1),
                         pool[k] + tail, mnt))
else:
    for i in range(args.sim):
        plen = int(rng.randint(3, max(4, max_plen)))
        mnt = int(rng.randint(2, max(3, args.max_new + 1)))
        prompt = rng.randint(1, vocab, size=plen).tolist()
        arrivals.append((i * args.arrive_every // max(args.arrive_every, 1),
                         prompt, mnt))

lend_stats = None
if args.lend_warm is not None:
    # ISSUE 17 demo: a peer lender (same params, its OWN page pool, no
    # journal) earns the head prefixes' KV by prefilling them, then the
    # serving engine adopts the pages over the export/adopt surface —
    # the host twin of ops.lend_pages. Head-of-pool prompts in the trace
    # below then hit as rewarmed pages before any local prefill ran.
    from triton_dist_tpu.serving import ServingEngine  # noqa: E402
    lender = ServingEngine(params, cfg, num_slots=args.slots,
                           page_size=args.page_size, num_pages=args.pages,
                           pages_per_seq=args.pages_per_seq,
                           prefill_chunk=args.prefill_chunk,
                           prefix_cache=True)
    n_warm = min(args.lend_warm, len(pool))
    for pre in pool[:n_warm]:
        lender.submit(pre + [1], 2)
    lender.run(max_steps=200_000)
    _t_lend = _time.perf_counter()
    lent_pages = lent_tokens = 0
    for pre in pool[:n_warm]:
        toks, _ids, payload = lender.export_prefix(pre)
        if toks > 0:
            got = eng.adopt_prefix(pre, toks, payload)
            lent_pages += got
            lent_tokens += got * args.page_size
    lend_stats = {
        "lend_warm": n_warm,
        "lent_pages": lent_pages,
        "lend_tokens": lent_tokens,
        "lend_us_per_page": round(
            (_time.perf_counter() - _t_lend) * 1e6 / max(lent_pages, 1),
            1),
    }

if args.crash_at is not None:
    from triton_dist_tpu.shmem.faults import InjectedCrash  # noqa: E402
    try:
        results = eng.run(max_steps=200_000, arrivals=arrivals)
    except InjectedCrash as crash:
        if not args.recover:
            print(json.dumps({"crashed": str(crash)}), file=sys.stderr)
            sys.exit(1)
        # process "restart": the journal is the only surviving artifact.
        # Submissions already journaled (admitted or rejected) replay
        # from the WAL; only the rest of the trace is re-fed.
        done = sum(1 for e in journal.entries
                   if e["kind"] in ("submit", "reject"))
        eng = mk_engine(fresh=True)
        results = eng.run(max_steps=200_000, arrivals=arrivals[done:],
                          recover=True)
        ck = journal.last_checkpoint_entry()
        print(json.dumps({
            "recovery": True,
            "crash": str(crash),
            "checkpoint_step": None if ck is None else ck["step"],
            "journal_entries": len(journal),
            "restores": eng.metrics.counters["restores"],
            "replayed_submits": done,
            "final_step": eng._steps,
        }), file=sys.stderr)
else:
    results = eng.run(max_steps=200_000, arrivals=arrivals)
# run() returns FINISHED requests only. Under --chaos a request may
# instead have FAILED (typed, per-request — the ladder ran dry); under
# --queue-cap/--ttl it may have been REJECTED/EXPIRED (typed overload
# terminals); those are accounted for, not "unfinished". Anything else
# absent ran out of steps — a real error.
failed = {r.rid: r for r in getattr(eng, "failed", [])}
unfinished = sorted(set(range(args.sim)) - set(results) - set(failed))
if unfinished:
    print(json.dumps({"error": "unfinished requests", "rids": unfinished}),
          file=sys.stderr)
    sys.exit(1)
for rid in sorted(failed):
    print(json.dumps({"failed_rid": rid,
                      "reason": type(failed[rid].failure).__name__,
                      "detail": str(failed[rid].failure)}), file=sys.stderr)
if args.queue_cap is not None or args.ttl is not None:
    c = eng.metrics.counters
    print(json.dumps({
        "overload": True,
        "queue_cap": args.queue_cap, "ttl_steps": args.ttl,
        "submitted": c["requests_submitted"],
        "admitted_finished": len(results),
        "rejections": c["rejections"],
        "expirations": c["expirations"],
    }), file=sys.stderr)

if args.tokens:
    for req in sorted(eng._finished, key=lambda r: r.rid):
        print(json.dumps({
            "rid": req.rid, "prompt_len": len(req.prompt),
            "tokens": list(req.generated),
            "preemptions": req.preemptions,
            "ttft_steps": req.first_token_step - req.submit_step,
        }))
print(json.dumps({"compile_stats": eng.compile_stats}), file=sys.stderr)

# cold-start summary (ISSUE 15): fresh traces paid before the first token
# and the wall time from process cold start (engine build / artifact
# load) to the first token out. With --artifact both columns should read
# zero-compiles and the ~10x-smaller wall time bench.py's `aot` extras
# pin; printed unconditionally so artifact-on vs artifact-off runs (and
# --recover restarts, which seed from the same artifact) compare 1:1.
_stats = eng.compile_stats
_ftt = [r.first_token_time for r in eng._finished
        if r.first_token_time is not None]
print(json.dumps({"cold_start": {
    "artifact": args.artifact,
    "cold_start_compiles": sum(
        v for k, v in _stats.items() if k.endswith("_compiles")),
    "aot_programs": _stats.get("aot_programs", 0),
    "cold_start_to_first_token_s":
        None if not _ftt else round(min(_ftt) - _t_cold0, 4),
}}), file=sys.stderr)

# prefill-stall / TTFT-split summary: the numbers chunked prefill moves
# (per-step decode stall bound, queue-vs-prefill TTFT split)
snap = eng.metrics.snapshot()
us = lambda v: None if v is None else round(v * 1e6, 1)

# per-class panel (ISSUE 14): TTFT lives on the intake panel, ITL on the
# decode panel for the split engines — merge both per_class() views
# (ints sum, None yields) into one summary line
per_cls = eng.metrics.per_class()
_md = getattr(eng, "metrics_decode", None)
if _md is not None:
    for _c, _row in _md.per_class().items():
        _base = per_cls.setdefault(_c, dict.fromkeys(_row))
        for _k, _v in _row.items():
            if isinstance(_v, int) and isinstance(_base.get(_k), int):
                _base[_k] += _v
            elif _base.get(_k) is None:
                _base[_k] = _v
if per_cls:
    print(json.dumps({
        "per_class": {
            c: {"ttft_p50_us": us(r.get("ttft_p50_s")),
                "ttft_p99_us": us(r.get("ttft_p99_s")),
                "itl_p50_us": us(r.get("itl_p50_s")),
                "itl_p99_us": us(r.get("itl_p99_s")),
                "finished": r.get("finished"),
                "rejections": r.get("rejections"),
                "expirations": r.get("expirations")}
            for c, r in per_cls.items()},
        "quota_throttled": snap["quota_throttled"],
        "chunk_shrinks": snap["chunk_shrinks"],
    }), file=sys.stderr)
if args.prefix_cache:
    # hit-rate + cached/cold TTFT split (ISSUE 13): the point of the
    # cache is the cached-TTFT column sitting far below the cold one on
    # shared-prefix workloads (--prompt-zipf)
    hits, misses = snap["prefix_hits"], snap["prefix_misses"]
    print(json.dumps({
        "prefix_cache": True,
        "hits": hits, "misses": misses,
        "hit_rate": round(hits / max(hits + misses, 1), 3),
        "hit_tokens": snap["prefix_hit_tokens"],
        "cow_copies": snap["cow_copies"],
        "evictions": snap["prefix_evictions"],
        "skipped_chunks": snap["prefix_skipped_chunks"],
        "ttft_cached_us": {k: us(snap["ttft_cached_s"][k])
                           for k in ("mean", "p99")},
        "ttft_cold_us": {k: us(snap["ttft_cold_s"][k])
                         for k in ("mean", "p99")},
        # the ISSUE 17 third band: first hit on pages adopted FROM A
        # PEER (--lend-warm) — the acceptance is rewarmed ≈ cached
        "ttft_rewarmed_us": {k: us(snap["ttft_rewarmed_s"][k])
                             for k in ("mean", "p99")},
    }), file=sys.stderr)
if lend_stats is not None:
    print(json.dumps({"lend": True, **lend_stats}), file=sys.stderr)
if args.disagg:
    # two panels: TTFT lives on the prefill worker, ITL/stall on the
    # decode worker — whose decode stall carries ZERO prefill work (the
    # step_prefill_tokens_max field is the proof, not a wall clock)
    snap_d = eng.metrics_decode.snapshot()
    print(json.dumps({
        "disagg": True,
        "prefill_chunks": snap["prefill_chunks"],
        "pages_migrated": snap["pages_migrated"],
        "migrate_us": {k: us(snap["migrate_s"][k])
                       for k in ("mean", "p99", "max")},
        "migrate_wait_steps_max": snap_d["migrate_wait_steps"]["max"],
        "decode_stall_us": {k: us(snap_d["decode_stall_s"][k])
                            for k in ("mean", "p50", "p99", "max")},
        "decode_step_prefill_tokens_max":
            snap_d["step_prefill_tokens"]["max"],
        "itl_us": {k: us(snap_d["tok_latency_s"][k])
                   for k in ("mean", "p99")},
        "ttft_queue_us": {k: us(snap["ttft_queue_s"][k])
                          for k in ("mean", "p99")},
        "ttft_prefill_us": {k: us(snap["ttft_prefill_s"][k])
                            for k in ("mean", "p99")},
    }), file=sys.stderr)
    if args.chaos is not None:
        # the chaos summary: what the ladder absorbed and what it cost
        print(json.dumps({
            "chaos_summary": True,
            "faults_injected": snap["faults_injected"],
            "stale_signals": snap["stale_signals"],
            "retries": snap_d["retries"],
            "degradations": snap_d["degradations"],
            "failed_requests": snap_d["failed_requests"],
            "recovered_ttft_us": {k: us(snap_d["recovered_ttft_s"][k])
                                  for k in ("mean", "p99")},
            "degraded_ttft_us": {k: us(snap_d["degraded_ttft_s"][k])
                                 for k in ("mean", "p99")},
        }), file=sys.stderr)
    eng.metrics.emit()
    eng.metrics_decode.emit()
else:
    if args.mesh is not None:
        # the replicated-decision guard's coverage for this replay
        print(json.dumps({"digest_checks": snap["digest_checks"]}),
              file=sys.stderr)
        # overlap panel (ISSUE 16): per-step EP wire split under the
        # wire-fit model — comm still exposed on the critical path vs
        # comm hidden behind expert FFN (serving/sharded.py; modeled,
        # labeled as such — CPU wall clock cannot show real overlap)
        print(json.dumps({
            "overlap": eng.overlap,
            "overlap_microbatches": eng.overlap_microbatches,
            "exposed_comm_us_mean": round(
                snap["exposed_comm_us"]["mean"] or 0.0, 2),
            "overlapped_comm_us_mean": round(
                snap["overlapped_comm_us"]["mean"] or 0.0, 2),
        }), file=sys.stderr)
        if args.long_context:
            # long-context panel (ISSUE 19): the per-step decode attention
            # split under the wire-fit model — local page scan (shrinks
            # with SP rank count, each rank walks 1/n of the pages) vs
            # fold wait (the fixed-order partial merge). MODELED, labeled
            # as such — CPU interpret wall clock cannot show the split
            print(json.dumps({
                "long_context": True,
                "kv_layout": eng.alloc.layout,
                "attn_local_us_mean": round(
                    snap["attn_local_us"]["mean"] or 0.0, 3),
                "attn_fold_wait_us_mean": round(
                    snap["attn_fold_wait_us"]["mean"] or 0.0, 3),
            }), file=sys.stderr)
    if args.speculate is not None:
        # spec panel (ISSUE 20): accepted/dispatch > 1 is the whole
        # point — every accepted draft token is a decode dispatch the
        # host never paid for, at bit-identical tokens
        print(json.dumps({
            "speculate": eng.spec_k,
            "spec_dispatches": snap["spec_dispatches"],
            "accepted_per_dispatch_mean": round(
                snap["accepted_per_dispatch"]["mean"] or 0.0, 3),
            "draft_hit_rate": snap["draft_hit_rate"],
            "spec_rewinds": snap["spec_rewinds"],
        }), file=sys.stderr)
    print(json.dumps({
        "prefill_chunk": args.prefill_chunk,
        "prefill_chunks": snap["prefill_chunks"],
        "prefill_stall_us": {k: us(snap["prefill_stall_s"][k])
                             for k in ("mean", "p50", "p99", "max")},
        "decode_stall_us": {k: us(snap["decode_stall_s"][k])
                            for k in ("mean", "p50", "p99", "max")},
        "step_prefill_tokens_max": snap["step_prefill_tokens"]["max"],
        "ttft_queue_us": {k: us(snap["ttft_queue_s"][k])
                          for k in ("mean", "p99")},
        "ttft_prefill_us": {k: us(snap["ttft_prefill_s"][k])
                            for k in ("mean", "p99")},
    }), file=sys.stderr)
    eng.metrics.emit()
