"""Sharded serving (ISSUE 8 tentpole): the continuous-batching engine's two
compiled programs — ``prefill_chunk_paged`` and ``decode_multistep_paged`` —
run over a TP/SP/EP mesh, with every sharded layer routed through the
overlap-kernel library via the model hooks:

- **attention (SP)**: the page pool is sharded on its PAGE dim per
  ``page_pool_pspec`` and each layer's KV-write + paged-GQA-attention pair
  runs ``ops.flash_decode.sp_paged_attend_write`` — per-rank masked local
  writes, tiled pool allgather, replicated attention walk.
- **dense projections (TP)**: wq/wk/wv/wo/lm_head run
  ``ops.allgather_gemm.tp_column_linear`` — column-sharded weights,
  last-dim allgather (``tp_impl="ag_gemm"`` swaps in the Pallas
  AllGather-GEMM overlap kernel).
- **MoE FFN (EP)**: ``models.moe.moe_mlp_ep_overlap`` — router →
  low-latency A2A dispatch (fp8 on the wire with ``wire_dtype="auto"``) →
  grouped expert FFN on local experts → A2A combine.

Host control plane stays REPLICATED-DECISION: one ``KVPagePool`` +
``ContinuousBatchingScheduler`` instance makes every allocation/admission/
preemption choice from device-independent inputs (token ids, counters), so
all ranks agree on block tables by construction — and the per-step digest
cross-check (``check_replicated_decisions``) turns "by construction" into a
loud runtime guarantee.

THE numerical contract (tests/test_sharded_serving.py): served tokens are
BITWISE identical across mesh sizes — the n>1 trace replays the n=1 golden
exactly, preemptions and all. This falls out of three exactness facts:

1. column-split matmul + concat allgather == the unsplit matmul (TP);
2. per-row EP dispatch/quant/combine with a fixed k-order fold is
   independent of which rank computed the row (EP, incl. the fp8 wire —
   the n=1 path runs the SAME quantize/dequantize round trip);
3. the SP pool allgather is a pure page-order concatenation (SP).

No cross-rank floating-point REDUCTION exists anywhere in the hot loop —
which is also why ``gemm_rs`` is refused here (docs/serving.md).
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.aot.registry import TunedKey, get_default_registry
from triton_dist_tpu.layers.ep_a2a_layer import EPAll2AllLayer
from triton_dist_tpu.models.llama import require_config
from triton_dist_tpu.models.moe import MoEConfig, moe_mlp_ep_overlap
from triton_dist_tpu.ops.all_to_all import _DEFAULT_WIRE_FIT, a2a_wire_bytes
from triton_dist_tpu.ops.allgather_gemm import GemmConfig, tp_column_linear
from triton_dist_tpu.ops.flash_decode import (flash_decode_dist,
                                              sp_paged_attend_write)
from triton_dist_tpu.serving import checkpoint as ckpt_mod
from triton_dist_tpu.serving.engine import (ServingEngine,
                                            check_prefill_chunk)
from triton_dist_tpu.serving.journal import ControlJournal
from triton_dist_tpu.serving.kv_pool import page_pool_pspec, shard_pool_arrays
from triton_dist_tpu.serving.metrics import ServingMetrics
from triton_dist_tpu.serving.speculate import resolve_spec_k
from triton_dist_tpu.shmem import faults as faults_mod
from triton_dist_tpu.shmem.context import ShmemContext, initialize_distributed

MESH_AXES = ("tp", "sp", "ep")


class ReplicatedDecisionError(AssertionError):
    """The per-rank control-plane digests diverged: some rank's allocator/
    scheduler made a different decision than rank 0's. Block tables are
    about to disagree across ranks — fail loudly BEFORE a wrong-rank page
    write corrupts live KV, not after."""


def serving_mesh(tp: int = 1, sp: int = 1, ep: int = 1) -> ShmemContext:
    """Build the TP×SP×EP serving mesh (axis names fixed to ``MESH_AXES``
    so the engine, bench rows, and serve_sim all agree on spelling)."""
    return initialize_distributed(axis_names=MESH_AXES,
                                  mesh_shape=(tp, sp, ep))


def serving_param_shardings(ctx: ShmemContext) -> dict:
    """Where each ``init_moe_params`` leaf lives on the serving mesh — the
    layout the engine's hooks consume without moving a byte: dense
    projections column-sharded over ``tp`` (``tp_column_linear``; ``wo``
    too — the serving path has no row-parallel reduction), expert tables
    sharded over ``ep`` on their expert dim (``moe_mlp_ep_overlap``),
    everything else replicated. Not ``models.moe.moe_param_specs``: that
    is the training layout (row-parallel ``wo``, experts split over ``tp``),
    which these hooks would reshard on every dispatch."""
    ns = lambda *spec: jax.sharding.NamedSharding(ctx.mesh, P(*spec))  # noqa: E731
    col, expert = ns(None, None, "tp"), ns(None, "ep")
    return {
        "embed": ns(),
        "blocks": {"attn_norm": ns(), "wq": col, "wk": col, "wv": col,
                   "wo": col, "mlp_norm": ns(), "w_router": ns(),
                   "we_gate": expert, "we_up": expert, "we_down": expert},
        "final_norm": ns(),
        "lm_head": ns(None, "tp"),
    }


def fd_attn_split_us(n_sp: int, n_layers: int, slots: int, steps: int,
                     page_kv_bytes: int, slab_row_bytes: int
                     ) -> tuple[float, float]:
    """Modeled per-decode-step attention split for ``flash_decode_dist``
    (ISSUE 19) — the long-context twin of ``_comm_split_us``, priced on
    the SAME PR 8 wire fit (t = t0 + bytes/BW) so serve_sim, bench.py and
    the engine metrics all quote one model:

    - ``attn_local_us``: the per-page partial walk. Each rank streams
      only its own slice of the block-table pages — ``ceil(steps/n_sp)``
      pages per slot per layer at ``page_kv_bytes`` each. This is the
      half that shrinks as the SP mesh grows (∝ kv_len / n).
    - ``attn_fold_wait_us``: the fixed-order fold's wait on remote
      partial slabs — (n−1) slabs of ``slots·steps·slab_row_bytes``
      behind one launch overhead per layer. Grows with n; sublinearity
      of the TOTAL therefore holds exactly when a page's KV bytes
      outweigh its partial-slab row (true for real page sizes — bench.py
      asserts it at {8k, 32k, 64k}-token contexts).

    MODELED, not wall clock: CPU runs serialize ranks and cannot exhibit
    the overlap (docs/serving.md labels every consumer)."""
    fit = _DEFAULT_WIRE_FIT["bf16"]
    bw = fit["gb_per_s"] * 1e3          # bytes per microsecond
    local = n_layers * slots * (-(-steps // n_sp)) * page_kv_bytes / bw
    if n_sp == 1:
        return local, 0.0
    fold = n_layers * (fit["t0_us"]
                       + (n_sp - 1) * slots * steps * slab_row_bytes / bw)
    return local, fold


@functools.lru_cache(maxsize=None)
def _hooks(ctx, a2a_decode, a2a_chunk, block_m, mb, tp_impl, tp_cfg,
           sp_overlap, long_context):
    """``(ffn, ffn_chunk, attn_io, linear)``: ONE set of hook functions for
    everything they close over (all static records), so that sharded engines
    of a mesh and a shape meet in ``serving.programs``' memo, which keys
    hooks by identity."""
    def moe_ffn(a2a):
        def ffn(h, p):
            # p is the unrolled loop's LayerParams view: the expert
            # tables go in STACKED and are indexed in place
            b = p.blocks
            return moe_mlp_ep_overlap(ctx, a2a, h, p["w_router"],
                                      b["we_gate"], b["we_up"],
                                      b["we_down"], block_m=block_m,
                                      microbatches=mb, layer=p.layer)
        return ffn

    if long_context:
        def attn_io(q, k, v, kp, vp, bt, pos, kv_len, active):
            return flash_decode_dist(ctx, q, k, v, kp, vp, bt, pos,
                                     kv_len, axis="sp", active=active)
    else:
        def attn_io(q, k, v, kp, vp, bt, pos, kv_len, active):
            return sp_paged_attend_write(ctx, q, k, v, kp, vp, bt,
                                         pos, kv_len, axis="sp",
                                         active=active, overlap=sp_overlap)

    def linear(h, w, name):
        return tp_column_linear(ctx, h, w, axis="tp", impl=tp_impl,
                                cfg=tp_cfg)

    return moe_ffn(a2a_decode), moe_ffn(a2a_chunk), attn_io, linear


class ShardedServingEngine(ServingEngine):
    """``ServingEngine`` on a TP/SP/EP mesh serving an MoE model (see the
    module docstring for the layer→kernel map and the bitwise contract).

    ``cfg`` is a ``MoEConfig`` (params from ``init_moe_params``); the
    flagship target is ``MoEConfig.deepseek_infer()``, the reference's
    A2A benchmark shape. ``ctx`` must carry all three ``MESH_AXES``
    (``serving_mesh``); size-1 axes degrade each path to its exact
    single-rank form — the SAME code (hooks set, loops unrolled, fp8 wire
    round-tripped) at every mesh size, which is what makes the n=1 run a
    valid golden for n>1.

    Requirements beyond the base engine:
    - the EP FFN is shape-specialized per row count — decode serves
      ``num_slots`` rows, a chunk serves ``prefill_chunk``;
    - ``num_slots % ep == 0`` and ``prefill_chunk % ep == 0`` (the A2A
      context splits token rows evenly over EP ranks);
    - ``d_model % 128 == 0`` (A2A wire lane alignment, asserted there).

    ``wire_dtype="auto"`` picks fp8 for the A2A payload when the platform
    supports it; ``tp_impl="ag_gemm"`` routes the TP projections through
    the Pallas overlap kernel (allclose-only — excluded from the bitwise
    contract; see ``tp_column_linear``). ``digest_every=k`` runs the
    replicated-decision guard every k-th step (0 disables).
    ``long_context=True`` (ISSUE 19) serves 64k–100k-token prompts: the
    SP attention leg becomes ``flash_decode_dist`` over an interleaved
    pool layout (one request's pages round-robined across the SP
    shards), so per-rank attention compute shrinks ∝ 1/|sp| instead of
    replicating — same two compiled programs, same bitwise contract
    (the long-context n=1 run is the golden for every mesh size).

    Disaggregation COMPOSES with this engine (ISSUE 12): the pool carries
    the unified contract — ``sp_ranks``-aware ledger (padding pages are
    allocator-invisible AND ``check_migratable``-refused) over the same
    SP-sharded arrays — so ``DisaggShardedEngine`` (serving/compose.py)
    runs this engine as the decode role of a disaggregated pair, landing
    migrated prefill pages into the sharded pool host-side.
    """

    def __init__(self, params: dict, cfg: MoEConfig, ctx: ShmemContext,
                 num_slots: int = 4, page_size: int = 16,
                 num_pages: int = 64, pages_per_seq: int = 8,
                 metrics: ServingMetrics | None = None,
                 decode_horizon: int = 1, eos_id: int | None = None,
                 prefill_chunk: int = 16,
                 stall_deadline_steps: int = 256,
                 wire_dtype: str | None = "auto", tp_impl: str = "xla",
                 tp_cfg: GemmConfig | None = None, moe_block_m: int = 128,
                 overlap: str = "off",
                 overlap_microbatches: int | None = None,
                 digest_every: int = 1,
                 journal: ControlJournal | None = None,
                 checkpoint_every: int | None = None,
                 queue_cap: int | None = None,
                 ttl_steps: int | None = None,
                 fault_plan=None,
                 prefix_cache: bool = False,
                 slo=None,
                 artifact=None, artifact_key: str | None = None,
                 long_context: bool = False,
                 speculate: int | str | None = None,
                 spec_hist: int = 64, spec_bucket: int = 0):
        require_config(cfg, MoEConfig, "ShardedServingEngine")
        for ax in MESH_AXES:
            assert ax in ctx.axis_names, (
                f"mesh is missing axis {ax!r} — build it with "
                f"serving_mesh(tp, sp, ep); got {ctx.axis_names}")
        prefill_chunk = check_prefill_chunk(prefill_chunk)
        self.ctx = ctx
        self.moe_cfg = cfg
        n_tp = ctx.axis_size("tp")
        n_sp = ctx.axis_size("sp")
        n_ep = ctx.axis_size("ep")
        self.mesh_desc = f"{n_tp}x{n_sp}x{n_ep}"
        assert num_slots % n_ep == 0, (
            f"num_slots {num_slots} must split evenly over ep={n_ep}")
        assert prefill_chunk % n_ep == 0, (
            f"prefill_chunk {prefill_chunk} must split evenly over "
            f"ep={n_ep}")

        # speculative decoding (ISSUE 20): resolve the draft length K
        # BEFORE the A2A layers — a verify dispatch runs num_slots * K
        # token rows through the row-count-specialized EP dispatch, so K
        # must be known when the decode layer is sized. Resolution ladder
        # = explicit int → tuned registry (keyed on this mesh + the model
        # dtype + the workload bucket, sigcheck-gated like
        # serving_overlap_mb) → default; the resolved int is handed to
        # the base ctor so it never re-consults the registry.
        self._spec_mesh_shape = (n_tp, n_sp, n_ep)
        spec_k = 0
        if speculate not in (None, 0, "off"):
            spec_k = resolve_spec_k(speculate, self._spec_mesh_shape,
                                    str(jnp.dtype(cfg.base.dtype)),
                                    spec_bucket)
        decode_rows = num_slots * max(1, spec_k)
        assert decode_rows % n_ep == 0

        # TWO A2A layers: the EP dispatch is row-count-specialized, and the
        # engine's two programs run different row counts (decode: the
        # num_slots batch — times K verify rows under speculation; chunk:
        # the prefill_chunk rows)
        mk = lambda rows: EPAll2AllLayer.create(  # noqa: E731
            ctx, max_tokens=rows // n_ep, hidden=cfg.base.d_model,
            topk=cfg.topk, num_experts=cfg.num_experts, axis="ep",
            dtype=cfg.base.dtype, wire_dtype=wire_dtype)
        self.a2a_decode = mk(decode_rows)
        self.a2a_chunk = (self.a2a_decode if prefill_chunk == decode_rows
                          else mk(prefill_chunk))
        self.wire_dtype = str(jnp.dtype(self.a2a_decode.a2a.wire_dtype)) \
            if self.a2a_decode.a2a.wire_dtype is not None else None
        # per-program resolved wire (satellite 6): ``auto`` resolves per
        # dispatch size, so decode and chunk can disagree — serve_sim
        # prints both so "wire=auto" is auditable per mesh (PR 8 caveat).
        self.wire_dtype_chunk = \
            str(jnp.dtype(self.a2a_chunk.a2a.wire_dtype)) \
            if self.a2a_chunk.a2a.wire_dtype is not None else None

        # -- fine-grained compute/comm overlap (ISSUE 16) ------------------
        # ``overlap`` gates the SCHEDULE only, never the math: the EP leg
        # microbatches each dispatch/combine (segmented counted-signal
        # wire, FFN(i) overlapping a2a(i+1)) and the ``ep+sp`` leg starts
        # local attention-pool assembly under the tiled allgather. Every
        # combine stays a concat or fixed-order fold, so the bitwise trace
        # contract above is untouched — asserted by bench.py and
        # tests/test_overlap_serving.py against the overlap=off golden.
        assert overlap in ("off", "ep", "ep+sp"), (
            f"overlap must be 'off', 'ep' or 'ep+sp', got {overlap!r}")
        self.overlap = overlap
        mb = 1
        if overlap != "off":
            mb = overlap_microbatches
            if mb is None:
                # tuned depth: the sigcheck-gated registry key PR 15
                # persists (aot/registry.py GATE_RUNNERS
                # ``serving_overlap_mb``); default 2 = double-buffering
                reg = get_default_registry()
                if reg is not None:
                    mb = reg.get(TunedKey("serving_overlap_mb",
                                          mesh_shape=(n_tp, n_sp, n_ep),
                                          dtype=self.wire_dtype or "none"))
                mb = 2 if mb is None else int(mb)
            mb = int(mb)
            assert mb >= 1, f"overlap_microbatches must be >= 1, got {mb}"
            assert (decode_rows // n_ep) % mb == 0, (
                f"decode rows per rank {decode_rows // n_ep} must split "
                f"evenly into {mb} overlap microbatches")
            assert (prefill_chunk // n_ep) % mb == 0, (
                f"chunk rows per rank {prefill_chunk // n_ep} must split "
                f"evenly into {mb} overlap microbatches")
            if mb > 1:
                # ride the segmented counted-signal wire kernel so each
                # microbatch's put is gated per segment (ops/all_to_all.py
                # ``all_to_all_push_seg``) — same bytes, same slots
                shared = self.a2a_chunk is self.a2a_decode
                seg = lambda l: dataclasses.replace(  # noqa: E731
                    l, a2a=dataclasses.replace(l.a2a, seg_push=2))
                self.a2a_decode = seg(self.a2a_decode)
                self.a2a_chunk = (self.a2a_decode if shared
                                  else seg(self.a2a_chunk))
        self.overlap_microbatches = mb

        # long-context mode (ISSUE 19): swap the SP attention leg from
        # the across-REQUESTS pool-allgather walk (every rank attends
        # over the full pool — per-rank cost ∝ full kv_len) to
        # ``flash_decode_dist`` (each rank walks only its own slice of
        # one request's pages and ships a partial slab — per-rank cost
        # ∝ kv_len/n). The pool layout flips to "interleaved" so one
        # sequence's pages round-robin across the SP shards; the fixed-
        # order page fold makes the attention result placement-
        # invariant, so tokens stay bitwise identical at every mesh size
        # AND across the two layouts' n=1 forms. Same hook surface, same
        # two compiled programs.
        self.long_context = long_context
        if long_context:
            self._pool_layout = "interleaved"
        ffn, ffn_chunk, attn_io, linear = _hooks(
            ctx, self.a2a_decode, self.a2a_chunk, moe_block_m, mb, tp_impl,
            tp_cfg, overlap == "ep+sp", long_context)

        # modeled per-decode-step wire split (satellite 2): price each EP
        # a2a with the PR 8 wire fit (t = t0 + bytes/BW). With M overlap
        # microbatches the software pipeline hides all but one round per
        # a2a, so exposed = t0 + B/(M*BW) while the total pays the extra
        # (M-1) launch overheads. CPU wall clock serializes ranks and can
        # never show real overlap, so the split is an HONEST MODELED
        # number (docs/serving.md), observed per step into the metrics.
        self._exposed_comm_us, self._overlapped_comm_us = \
            self._comm_split_us(cfg.base.n_layers, mb)
        # modeled long-context attention split (ISSUE 19): zeros unless
        # long_context — the pool-allgather path's wire cost is already
        # priced by the overlap split above
        base = cfg.base
        self._attn_local_us, self._attn_fold_wait_us = (
            fd_attn_split_us(
                n_sp, base.n_layers, num_slots, pages_per_seq,
                2 * base.n_kv_heads * page_size * base.head_dim
                * jnp.dtype(base.dtype).itemsize,
                base.n_heads * (base.head_dim + 128) * 4)
            if long_context else (0.0, 0.0))

        # pool-output sharding pin: must exist BEFORE super().__init__
        # asks for the jitted programs (it becomes their out_shardings for
        # the pool pytree — see ``programs.engine_programs``)
        self._pool_out_sharding = jax.sharding.NamedSharding(
            ctx.mesh, page_pool_pspec("sp"))
        # replicated sharding for the control-plane mirrors (_upload
        # commits every upload so pjit's executable cache sees ONE input
        # signature across all dispatches)
        self._rep_sharding = jax.sharding.NamedSharding(ctx.mesh, P())
        # unified pool contract (ISSUE 12): the base engine threads this
        # into KVPagePool(sp_ranks=...) so the ledger knows the device
        # page range (real + SP padding) and refuses padding ids in
        # check_migratable while the allocator never hands them out.
        self._pool_sp_ranks = n_sp

        super().__init__(params, cfg.base, num_slots=num_slots,
                         page_size=page_size, num_pages=num_pages,
                         pages_per_seq=pages_per_seq,
                         ffn=ffn, ffn_chunk=ffn_chunk,
                         attn_io=attn_io, linear=linear,
                         metrics=metrics, decode_horizon=decode_horizon,
                         eos_id=eos_id, prefill_chunk=prefill_chunk,
                         stall_deadline_steps=stall_deadline_steps,
                         journal=journal, checkpoint_every=checkpoint_every,
                         queue_cap=queue_cap, ttl_steps=ttl_steps,
                         fault_plan=fault_plan, prefix_cache=prefix_cache,
                         slo=slo, artifact=artifact,
                         artifact_key=artifact_key,
                         speculate=(spec_k or None), spec_hist=spec_hist,
                         spec_bucket=spec_bucket)

        # commit the weights to the mesh ONCE (a no-op for params already
        # initialized sharded): uncommitted params would land whole on
        # device 0 and be resharded by every dispatch
        self.params = jax.device_put(self.params,
                                     serving_param_shardings(ctx))

        # shard the pool arrays over SP on the page dim, padding the page
        # count up to a multiple of |sp|. The ALLOCATOR never learns about
        # the padding pages — they are never handed out, every block-table
        # fill entry stays the scratch page — so allocation/preemption
        # schedules are identical at every mesh size (part of the bitwise
        # contract). Zero-init padding matches the live pages' init.
        self.pool = shard_pool_arrays(self.pool, n_sp,
                                      self._pool_out_sharding)

        # replicated-decision guard: every rank carries (conceptually) its
        # own copy of the host control plane; the check all-gathers the
        # per-rank digests ON DEVICE (through the same mesh the model
        # runs on) and compares against rank 0. ``_digest_skew`` is the
        # test hook that injects a per-rank divergence to prove the guard
        # trips (there is no organic way to fork a replicated digest in a
        # single-controller process).
        self.digest_every = digest_every
        self.n_ranks = ctx.num_ranks
        self._digest_skew = np.zeros(self.n_ranks, np.uint32)
        # digest-divergence recovery rung (ISSUE 9): per-step count of
        # divergences already recovered (keys FaultPlan.digest_skew's
        # ``attempt`` so a scheduled transient fires exactly once), plus
        # the escalation latch — a second divergence with ZERO clean
        # checks since the last restore means the skew is persistent and
        # the rung must escalate, not loop.
        self._digest_attempts: dict[int, int] = {}
        self._recovered_once = False
        self._checks_since_recovery = 0

        def gather_cmp(v):                       # v [1] int32, my digest
            g = v
            for ax in MESH_AXES:
                g = lax.all_gather(g, ax, axis=0, tiled=True)
            return jnp.any(g != g[0])[None].astype(jnp.int32)

        self._digest_check = jax.jit(ctx.shard_map(
            gather_cmp, in_specs=P(MESH_AXES), out_specs=P(MESH_AXES)))

    def _comm_split_us(self, n_layers: int, mb: int) -> tuple[float, float]:
        """(exposed_us, overlapped_us) per decode step under the wire fit.
        ``mb == 1`` (overlap off) exposes everything; n_ep == 1 has no
        wire at all, so both halves are zero there — which is also why
        overlap can only LOSE at n=1 (it still pays the extra microbatch
        launches while hiding nothing)."""
        a2a = self.a2a_decode.a2a
        if a2a.n_ranks == 1:
            return 0.0, 0.0
        wire = a2a.wire_dtype
        fit = _DEFAULT_WIRE_FIT["fp8" if wire is not None and
                                jnp.dtype(wire).itemsize == 1 else "bf16"]
        bw_us = fit["gb_per_s"] * 1e3          # bytes per microsecond
        b = a2a_wire_bytes(a2a.n_ranks, a2a.max_tokens, a2a.hidden,
                           a2a.topk, wire)
        total = n_layers * (mb * fit["t0_us"] + b / bw_us)
        exposed = n_layers * (fit["t0_us"] + b / (mb * bw_us))
        return exposed, max(0.0, total - exposed)

    def _default_artifact_key(self) -> str:
        return f"sharded:{self.mesh_desc}"

    def _upload(self, mirror):
        return jax.device_put(jnp.asarray(mirror), self._rep_sharding)

    # -- replicated-decision guard ----------------------------------------
    # ``control_digest`` lives on the base engine now (ISSUE 9: journal
    # entries on every engine carry it); this class adds the cross-rank
    # comparison and the recovery rung on top.

    def check_replicated_decisions(self) -> None:
        """Cross-rank digest assertion (satellite 1): all-gather each
        rank's control digest over the full mesh and compare to rank 0's.
        Raises ``ReplicatedDecisionError`` on divergence.

        Divergence sources: the ``_digest_skew`` per-rank array (the
        direct test hook) and — ISSUE 9 — an active ``FaultPlan``'s
        ``digest_skew`` schedule, which corrupts one keyed rank's word at
        scheduled/probabilistic steps so seeds can drive the restore rung.
        """
        h = self.control_digest()
        vals = np.full(self.n_ranks, h, np.uint32) + self._digest_skew
        plan = self._fault_plan if self._fault_plan is not None \
            else faults_mod.active_plan()
        if plan is not None and self.n_ranks > 1:
            w = plan.digest_skew(self._steps,
                                 self._digest_attempts.get(self._steps, 0))
            if w:
                vals[plan.skew_rank(self._steps, self.n_ranks)] += \
                    np.uint32(w)
                self.metrics.inc("faults_injected")
        vals = vals.view(np.int32)
        mismatch = np.asarray(self._digest_check(jnp.asarray(vals)))
        self.metrics.inc("digest_checks")
        if mismatch.any():
            bad = np.nonzero(vals != vals[0])[0].tolist()
            raise ReplicatedDecisionError(
                f"control-plane digest diverged across ranks at step "
                f"{self._steps}: ranks {bad or '<device-side only>'} "
                f"disagree with rank 0 (digest 0x{h:08x}, mesh "
                f"{self.mesh_desc}). A replicated-decision input leaked "
                "rank-dependent state — block tables are no longer "
                "trustworthy." + self._postmortem())

    def _post_step(self) -> None:
        """Digest cross-check first (same cadence the pre-ISSUE-9 ``step``
        override ran it on), then the base checkpoint cadence — so a
        checkpoint is only ever captured at a step whose digest all ranks
        just agreed on."""
        self.metrics.observe("exposed_comm_us", self._exposed_comm_us)
        self.metrics.observe("overlapped_comm_us",
                             self._overlapped_comm_us)
        self.metrics.observe("attn_local_us", self._attn_local_us)
        self.metrics.observe("attn_fold_wait_us", self._attn_fold_wait_us)
        if self.digest_every and self._steps % self.digest_every == 0:
            try:
                self.check_replicated_decisions()
            except ReplicatedDecisionError as err:
                self._recover_divergence(err)
                return          # quarantined step: no checkpoint here
            self._checks_since_recovery += 1
        super()._post_step()

    def _recover_divergence(self, err: ReplicatedDecisionError) -> None:
        """The top recovery rung (ISSUE 9 tentpole): quarantine the
        diverged step in the journal, restore every rank's control plane
        from the last agreed checkpoint + journal replay, and keep
        serving. Escalates (re-raises) when there is no journal to
        restore from, or on REPEAT divergence — a second trip with zero
        clean checks since the last restore means the skew is persistent,
        and looping restores would never converge."""
        if self.journal is None:
            raise err
        if self._recovered_once and self._checks_since_recovery == 0:
            raise ReplicatedDecisionError(
                "repeat digest divergence with no agreed step since the "
                "last restore — persistent skew, escalating instead of "
                "looping the restore rung.\nfirst divergence:\n"
                + str(err)) from err
        step = self._steps
        self._digest_attempts[step] = self._digest_attempts.get(step, 0) + 1
        self._jlog("digest_divergence",
                   error=str(err).splitlines()[0])
        self._recovered_once = True
        self._checks_since_recovery = 0
        self.metrics.inc("digest_recoveries")
        t0 = time.perf_counter()
        ckpt_mod.restore(self, ckpt_mod.latest(self.journal), self.journal)
        self.metrics.observe("digest_recovery_s", time.perf_counter() - t0)


__all__ = ["ShardedServingEngine", "ReplicatedDecisionError",
           "serving_mesh", "serving_param_shardings", "fd_attn_split_us",
           "MESH_AXES"]
