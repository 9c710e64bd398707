"""MoE model family (Mixtral/DeepSeek-style) over the EP kernel stack.

The reference's MoE story is kernel-level: EP All-to-All dispatch/combine
(reference python/triton_dist/kernels/nvidia/low_latency_all_to_all.py,
ep_a2a.py) and MoE-TP grouped-GEMM overlap ops (allgather_group_gemm.py,
moe_reduce_rs.py), exercised end-to-end by test_ep_moe_inference.py (an MoE
block: router → dispatch → grouped FFN → combine). This module provides that
same end-to-end MoE block as part of a full model, two ways:

- ``moe_mlp_gshard``: differentiable GShard-style einsum dispatch with
  experts sharded over an ``ep`` mesh axis — the *training* path. XLA turns
  the dispatch/combine einsums into all-to-alls over ICI and overlaps them
  with the expert GEMMs (async collectives); grads flow through everything.
- ``moe_mlp_ep_overlap``: the *inference* path through the hand-overlapped
  Pallas A2A dispatch/combine + grouped-GEMM kernels (the reference's
  showcase pipeline, low_latency_all_to_all.py:189-270 + ep_a2a_layer.py).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from triton_dist_tpu.models.llama import (LlamaConfig, rmsnorm, rope,
                                          _attention)
from triton_dist_tpu.shmem.context import ShmemContext


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    base: LlamaConfig = dataclasses.field(default_factory=LlamaConfig)
    num_experts: int = 8
    topk: int = 2
    moe_d_ff: int = 2048           # per-expert FFN width
    capacity_factor: float = 1.25  # train-path expert capacity
    router_aux_coef: float = 0.01  # load-balance loss weight

    @classmethod
    def tiny(cls, n_layers: int = 2, num_experts: int = 4):
        return cls(base=LlamaConfig.tiny(n_layers), num_experts=num_experts,
                   topk=2, moe_d_ff=128)

    @classmethod
    def mixtral_8x7b(cls):
        return cls(base=LlamaConfig(vocab_size=32000, d_model=4096,
                                    n_layers=32, n_heads=32, n_kv_heads=8,
                                    d_ff=14336),
                   num_experts=8, topk=2, moe_d_ff=14336)

    @classmethod
    def deepseek_infer(cls):
        """The reference's A2A benchmark shape: hidden 7168, topk 8
        (BASELINE.md / reference README.md:55)."""
        return cls(base=LlamaConfig(vocab_size=129280, d_model=7168,
                                    n_layers=4, n_heads=56, n_kv_heads=8,
                                    d_ff=18432),
                   num_experts=64, topk=8, moe_d_ff=2048)


def init_moe_params(key: jax.Array, cfg: MoEConfig) -> dict:
    """Llama-style attention params + per-layer MoE FFN params (router +
    stacked expert weights)."""
    from triton_dist_tpu.models.llama import init_params
    b = cfg.base
    L, D, F, E = b.n_layers, b.d_model, cfg.moe_d_ff, cfg.num_experts
    params = init_params(key, b)
    blocks = dict(params["blocks"])
    for k in ("w_gate", "w_up", "w_down"):
        del blocks[k]
    keys = jax.random.split(jax.random.fold_in(key, 1), 4)
    s = 0.02

    def norm(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(b.dtype)

    blocks["w_router"] = jnp.asarray(
        jax.random.normal(keys[0], (L, D, E), jnp.float32) * s)
    blocks["we_gate"] = norm(keys[1], L, E, D, F)
    blocks["we_up"] = norm(keys[2], L, E, D, F)
    blocks["we_down"] = norm(keys[3], L, E, F, D)
    params["blocks"] = blocks
    return params


def moe_param_specs(cfg: MoEConfig, tp: str | None = "tp",
                    ep: str | None = "ep", pp: str | None = None) -> dict:
    """Specs tree matching ``init_moe_params``: experts sharded over ``ep``,
    attention Megatron-TP over ``tp``."""
    from triton_dist_tpu.models.llama import param_specs
    specs = param_specs(cfg.base, tp=tp, pp=pp)
    blocks = dict(specs["blocks"])
    for k in ("w_gate", "w_up", "w_down"):
        del blocks[k]
    blocks["w_router"] = P(pp, None, None)
    blocks["we_gate"] = P(pp, ep, None, tp)
    blocks["we_up"] = P(pp, ep, None, tp)
    blocks["we_down"] = P(pp, ep, tp, None)
    specs["blocks"] = blocks
    return specs


# ---------------------------------------------------------------------------
# training path: GShard-style differentiable dispatch (ep via GSPMD)
# ---------------------------------------------------------------------------

def moe_mlp_gshard(x2d: jax.Array, p: dict, cfg: MoEConfig):
    """Capacity-bounded top-k MoE FFN as dispatch/combine einsums
    (GShard/Switch formulation). x2d [T, D] → ([T, D], aux_loss). With
    ``we_*`` sharded over an ``ep`` axis, XLA lowers the ``tec``-contractions
    to all-to-alls over the expert axis — the differentiable twin of the
    Pallas dispatch/combine path below."""
    T, D = x2d.shape
    E, k = cfg.num_experts, cfg.topk
    C = max(int(cfg.capacity_factor * T * k / E), 1)
    C = min(C, T)

    logits = (x2d.astype(jnp.float32) @ p["w_router"])          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_ids = lax.top_k(probs, k)                   # [T, k]
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    # position of each (token, k) in its expert's capacity buffer
    e_oh = jax.nn.one_hot(gate_ids, E, dtype=jnp.int32)         # [T, k, E]
    flat = e_oh.reshape(T * k, E)
    pos_flat = jnp.cumsum(flat, axis=0) - flat                  # exclusive
    pos = jnp.take_along_axis(
        pos_flat.reshape(T, k, E), gate_ids[..., None], -1)[..., 0]  # [T, k]
    keep = pos < C
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, C), C, dtype=x2d.dtype)
    disp = jnp.einsum("tke,tkc->tec", e_oh.astype(x2d.dtype), pos_oh)
    comb = jnp.einsum("tke,tkc,tk->tec", e_oh.astype(jnp.float32),
                      pos_oh.astype(jnp.float32),
                      gate_vals * keep.astype(jnp.float32))

    xe = jnp.einsum("td,tec->ecd", x2d, disp)                   # [E, C, D]
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["we_gate"],
                               preferred_element_type=jnp.float32)
                    ).astype(x2d.dtype) \
        * jnp.einsum("ecd,edf->ecf", xe, p["we_up"])
    ye = jnp.einsum("ecf,efd->ecd", h, p["we_down"])            # [E, C, D]
    y = jnp.einsum("ecd,tec->td", ye.astype(jnp.float32), comb)

    # Switch-style load-balance aux loss
    me = jnp.mean(probs, axis=0)                                 # [E]
    ce = jnp.mean(e_oh[:, 0].astype(jnp.float32), axis=0)        # top-1 frac
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)
    return y.astype(x2d.dtype), aux


def moe_block_apply(cfg: MoEConfig, x: jax.Array, p: dict,
                    positions: jax.Array, act_spec: P | None = None):
    """One MoE transformer block → (x, aux_loss). x [B, S, D]."""
    import math as _math
    b = cfg.base
    B, S, D = x.shape
    Hq, Hkv, Dh = b.n_heads, b.n_kv_heads, b.head_dim

    def pin(h):
        if act_spec is not None:
            h = lax.with_sharding_constraint(h, act_spec)
        return h

    h = rmsnorm(x, p["attn_norm"], b.norm_eps)
    q = rope((h @ p["wq"]).reshape(B, S, Hq, Dh), positions, b.rope_theta)
    kk = rope((h @ p["wk"]).reshape(B, S, Hkv, Dh), positions, b.rope_theta)
    v = (h @ p["wv"]).reshape(B, S, Hkv, Dh)
    attn = _attention(q, kk, v, 1.0 / _math.sqrt(Dh))
    x = pin(x + attn.reshape(B, S, Hq * Dh) @ p["wo"])

    h = rmsnorm(x, p["mlp_norm"], b.norm_eps)
    y, aux = moe_mlp_gshard(h.reshape(B * S, D), p, cfg)
    x = pin(x + y.reshape(B, S, D))
    return x, aux


def moe_forward(params: dict, tokens: jax.Array, cfg: MoEConfig,
                act_spec: P | None = None, remat: bool = False):
    """Full MoE forward → (logits [B,S,V] f32, aux_loss scalar)."""
    b = cfg.base
    B, S = tokens.shape
    x = params["embed"][tokens].astype(b.dtype)
    positions = jnp.arange(S)[None, :].repeat(B, 0)

    def body(carry, p):
        x, aux = carry
        x, a = moe_block_apply(cfg, x, p, positions, act_spec)
        return (x, aux + a), None

    if remat:
        body = jax.checkpoint(body)
    (x, aux), _ = lax.scan(body, (x, jnp.float32(0)), params["blocks"])
    x = rmsnorm(x, params["final_norm"], b.norm_eps)
    return (x @ params["lm_head"]).astype(jnp.float32), aux


# ---------------------------------------------------------------------------
# inference path: Pallas EP overlap kernels
# ---------------------------------------------------------------------------

def moe_mlp_ep_overlap(ctx: ShmemContext, a2a_layer, x2d: jax.Array,
                       router_w: jax.Array, we_gate: jax.Array,
                       we_up: jax.Array, we_down: jax.Array,
                       axis: str | None = None, block_m: int = 128,
                       block_n: int = 128, block_k: int | None = None,
                       down_block_n: int | None = None,
                       we_gate_up_packed: jax.Array | None = None,
                       microbatches: int = 1,
                       layer: int | None = None
                       ) -> jax.Array:
    """The reference's EP MoE inference block (test_ep_moe_inference.py /
    tutorial 04) on the Pallas kernel stack: router → low-latency A2A
    dispatch → grouped expert FFN on each rank's local experts → A2A combine
    with top-k weights.

    x2d [T, D] globally P(axis)-sharded token rows; router_w [D, E];
    we_* [E, D, F]/[E, F, D] — consumed P(axis)-sharded on the expert dim,
    so each rank holds and uses only its local expert slice
    we_*[me*Elocal:(me+1)*Elocal].

    ``layer=i``: ``we_*`` are the STACKED per-layer tables [L, E, ., .]
    (``init_moe_params``' layout) and layer ``i`` is indexed IN PLACE —
    the kernels stream expert ``i*Elocal + e`` of the flattened
    [L*Elocal, ., .] view, so no layer-sized slice is ever materialized
    for the Pallas operands (a slice XLA cannot fuse into a custom call:
    one HBM copy of the layer's experts per layer per call otherwise).

    With a 2-tier layer (``EPAll2AllLayer.create(axis=(major, minor))``)
    the dispatch/combine run the hierarchical path and ``axis`` is taken
    from the layer; ``x2d`` is P((major, minor))-sharded.

    ``microbatches=M > 1`` runs ISSUE 16's double-buffered schedule: the
    router still scores the FULL batch (identical math), then the per-rank
    token rows are split into M contiguous row blocks, each dispatched
    through an M-times-smaller (still drop-proof) a2a context, with block
    i+1's dispatch issued BEFORE block i's expert FFN — the grouped FFN on
    microbatch i overlaps the a2a of microbatch i+1 (gated per-segment by
    the counted-signal wire when the layer sets ``seg_push``). The output
    is the FIXED-ORDER per-rank concatenation of the block outputs; since
    every per-row quantity (routing decision, gather, quant round-trip,
    expert FFN row, fixed k-order combine fold) is bitwise invariant to
    which rows share its batch, the result is BITWISE identical to
    ``microbatches=1`` — the schedule overlaps, the reduction order never
    moves.
    """
    from triton_dist_tpu.ops.all_to_all import QuantTokens
    from triton_dist_tpu.ops.group_gemm import (PackedGatedWeights,
                                                apply_grouped, fit_block_k,
                                                grouped_gemm,
                                                grouped_gemm_gated)

    a2a = a2a_layer.a2a
    is_2d = getattr(a2a_layer, "is_2d", False)
    if is_2d:
        group = a2a.axes
        shard_spec = P(group)
    else:
        group = axis or a2a.axis or ctx.axis_names[0]
        shard_spec = P(group)
    E, k = a2a.num_experts, a2a.topk
    e_local = a2a.experts_per_rank

    if isinstance(we_gate_up_packed, PackedGatedWeights):
        # layer-level contract check of the serving weight layout: the
        # interleave is invisible in the array's shape, so mismatches are
        # only catchable while the pack width still rides the type
        assert we_gate_up_packed.block_n == block_n, (
            f"we_gate_up_packed was packed with "
            f"block_n={we_gate_up_packed.block_n} but the layer runs "
            f"block_n={block_n} — repack with pack_gated_weights(..., "
            f"block_n={block_n})")
        we_gate_up_packed = we_gate_up_packed.w

    # expert-major recv layout (1d contexts): rows [e*cap_e, (e+1)*cap_e) of
    # every src block belong to local expert e by construction, so the
    # block→expert table is a static constant and the align gather/scatter
    # passes are skipped entirely (the roofline attributed ~25 % extra
    # weight traffic to their ragged block padding)
    expert_major = (not is_2d) and getattr(a2a, "expert_major", False)
    cap_e = a2a.capacity_per_expert if expert_major else None
    em_fast = expert_major and cap_e % block_m == 0

    logits = x2d.astype(jnp.float32) @ router_w
    gate_vals, gate_ids = lax.top_k(jax.nn.softmax(logits, -1), k)
    gate_vals = (gate_vals / jnp.sum(gate_vals, -1, keepdims=True))

    mbs = int(microbatches)
    if mbs > 1:
        import dataclasses as _dc
        from triton_dist_tpu.ops.all_to_all import _cap_round
        assert not is_2d, "microbatched overlap is a 1d-EP schedule"
        assert not expert_major, (
            "microbatched overlap needs the rank-major layout: the per-"
            "expert budget of an expert-major context is not drop-proof "
            "per microbatch, so drops could differ from the unsplit path")
        T = a2a.max_tokens
        assert T % mbs == 0, (
            f"per-rank rows {T} not divisible by microbatches={mbs}")
        itemsize = jnp.dtype(a2a.wire_dtype or a2a.dtype).itemsize
        assert a2a.capacity >= _cap_round(T * k, itemsize), (
            "microbatched overlap requires a drop-proof capacity "
            f"(>= {T}*{k} rounded) — a tuned sub-worst-case capacity "
            "drops per-microbatch routing spill differently from the "
            "unsplit dispatch and breaks bit-identity")
        mbT = T // mbs
        # the microbatch context: same wire dtype / edges / seg_push, an
        # M-times-smaller (still drop-proof) slot budget. Reusing the FULL
        # layer's resolved wire_dtype is what keeps a "auto" wire decision
        # independent of M (it was resolved at the full dispatch size).
        mb_a2a = _dc.replace(a2a, max_tokens=mbT,
                             capacity=_cap_round(mbT * k, itemsize))
        mb_layer = _dc.replace(a2a_layer, a2a=mb_a2a)

        def _mb_part(i):
            def f(x, gv, gi):
                s = lambda a: lax.dynamic_slice_in_dim(a, i * mbT, mbT, 0)
                return s(x), s(gv), s(gi)
            return ctx.shard_map(f, in_specs=(shard_spec,) * 3,
                                 out_specs=(shard_spec,) * 3)(
                x2d, gate_vals, gate_ids)

        parts = [_mb_part(i) for i in range(mbs)]
    else:
        mb_layer = a2a_layer
        parts = [(x2d, gate_vals, gate_ids)]

    # software pipeline prologue: microbatch 0's a2a is in flight before
    # any expert FFN is traced (at mbs == 1 this is exactly the original
    # dispatch call)
    disp = [mb_layer.dispatch(parts[0][0], parts[0][2])]
    quant = isinstance(disp[0][0], QuantTokens)

    n = ctx.axis_size(group)

    packed = we_gate_up_packed is not None
    assert layer is None or not packed, (
        "the packed gate|up layout is per-layer: pass layer=None")

    def expert_ffn(tok, ids, wg, wu, wd, *sc):
        H = tok.shape[-1]
        rows = 1
        for d in tok.shape[:-1]:
            rows *= d
        tflat = tok.reshape(rows, H)
        iflat = ids.reshape(rows)
        sflat = sc[0].reshape(rows) if sc else None
        # wg/wu/wd arrive as this rank's expert slice (w_spec below).
        # packed serving layout: wg carries the pre-interleaved [E, H, 2F]
        # gate‖up weights (pack_gated_weights — one double-width tile
        # stream, measured 538.9→381.5 µs for the gate+up kernel at the
        # deployed full-K (128,128) config; wu unused)
        wg_l, wu_l, wd_l = wg, (None if packed else wu), wd
        e_off = 0
        if layer is not None:
            # [L, Elocal, ., .] -> [L*Elocal, ., .] is a free view; layer
            # i's experts start at row i*Elocal of it
            flat = lambda w: w.reshape((-1,) + w.shape[2:])  # noqa: E731
            wg_l, wu_l, wd_l = flat(wg), flat(wu), flat(wd)
            e_off = layer * e_local
        if packed:
            # re-carry the pack width on the per-rank slice so the kernel
            # re-validates it (the layer-level check above ran on the full
            # table; the slice is a fresh bare array)
            wg_l = PackedGatedWeights(wg_l, block_n)

        # gated FFN: silu(x@wg) * (x@wu) @ wd over local experts, as TWO
        # fused kernels: gate+up+act in one (each x-tile read once,
        # activation on the f32 accumulators in VMEM — no gate/up arrays
        # or elementwise pass in HBM), then the down grouped GEMM. On the
        # expert-edge quantized wire, xs stays fp8/int8 and the per-row
        # scale folds into both accumulators — silu(s·(q@wg)) · s·(q@wu)
        # == the dequantized math, row scaling commutes with the matmul.
        # masked=False: apply_grouped's scatter drops invalid rows by
        # index, so the zeroing pass over each output is skipped.
        def ffn(xs, be, nb, *ss):
            be = be + e_off
            # K-splits adapt to the widths: full-K strips where they fit
            # scoped VMEM (the measured-best DeepSeek config), a fitted
            # split where they cannot (Mixtral's F=14336 down projection)
            wsize = jnp.dtype(wd_l.dtype).itemsize
            kw = dict(block_m=block_m, block_n=block_n, n_blocks_used=nb,
                      masked=False, packed=packed,
                      block_k=block_k or fit_block_k(
                          H, block_m, block_n, wsize, n_weights=2))
            if ss:
                kw["row_scale"] = ss[0]
                kw["out_dtype"] = a2a.dtype
            hh = grouped_gemm_gated(xs, wg_l, wu_l, be, **kw)
            # down default bn=512: measured best on-chip at the DeepSeek
            # serving shape (432.7 µs at bn=128 -> 199.8 at bn=512 — the
            # (F, 128) weight tiles were DMA-overhead-bound; 1024/1792
            # overshoot: 336/357 µs; scripts/moe_probe.py round 5)
            dbn = down_block_n or 512
            return grouped_gemm(hh, wd_l, be, block_m=block_m, block_n=dbn,
                                n_blocks_used=nb, masked=False,
                                block_k=fit_block_k(wd_l.shape[1], block_m,
                                                    dbn, wsize))

        # fp8 wire rows are cast to the compute dtype inside the gather
        # pass (Mosaic rejects fp8 x-strips in the grouped pipelines on
        # the current toolchain — measured round 5; int8 rows feed the
        # kernels directly and use the convert-once scratch). The scale
        # keeps riding the accumulators either way.
        gdt = (a2a.dtype if (quant and jnp.issubdtype(tflat.dtype,
                                                      jnp.floating))
               else None)
        if em_fast:
            # expert-major fast path: the recv buffer IS expert-aligned.
            # Block b sits at row offset (b·bm) mod cap of its src block,
            # whose expert segment is that offset // cap_e — a static
            # constant (cap_e % block_m == 0 means no block straddles a
            # segment). No align gather, no inverse scatter: the slots are
            # already the combine order, and unfilled slots are zero rows
            # whose FFN output is zero (scale 1 on the quantized wire).
            # ALL row blocks run (the per-expert budget makes that the
            # roofline count — vs the ragged-padding blocks the align
            # pass added on the rank-major layout).
            cap = a2a.capacity
            be = jnp.asarray([(b * block_m % cap) // cap_e
                              for b in range(rows // block_m)], jnp.int32)
            xs = tflat if gdt is None else tflat.astype(gdt)
            out = (ffn(xs, be, rows // block_m, sflat)
                   if sflat is not None else ffn(xs, be, rows // block_m))
        else:
            out = apply_grouped(tflat, iflat, e_local, ffn, block_m=block_m,
                                row_scale=sflat, gather_dtype=gdt)
        if is_2d:
            return out.reshape(tok.shape[:-1] + (-1,))
        return out.reshape(n, tok.shape[-2], -1)

    # expert weights enter sharded on their expert dim: each rank holds
    # only its e_local experts. Weights committed that way (the sharded
    # serving engine's layout) never move; replicated ones are sliced in
    # place — no rank ever needs the whole table
    w_spec = (P(group, None, None) if layer is None
              else P(None, group, None, None))
    sm = ctx.shard_map(expert_ffn,
                       in_specs=(shard_spec,) * 2 + (w_spec,) * 3
                       + (shard_spec,) * (1 if quant else 0),
                       out_specs=shard_spec)
    # packed mode: the interleaved weights ride the wg slot; wu is passed
    # as a zero-size placeholder the ffn never touches
    wgu = we_gate_up_packed if packed else we_gate
    wup = (jnp.zeros((a2a.num_experts, 1, 1), we_gate.dtype) if packed
           else we_up)

    outs = []
    for i in range(len(parts)):
        if i + 1 < len(parts):
            # issue microbatch i+1's dispatch BEFORE microbatch i's FFN:
            # the grouped GEMMs below overlap the next block's wire time
            disp.append(mb_layer.dispatch(parts[i + 1][0], parts[i + 1][2]))
        recv_tok, recv_ids, layout = disp[i]
        args = ((recv_tok.q, recv_ids, wgu, wup, we_down, recv_tok.scale)
                if quant else (recv_tok, recv_ids, wgu, wup, we_down))
        processed = sm(*args)
        outs.append(mb_layer.combine(processed, layout, parts[i][1]))
    if len(outs) == 1:
        return outs[0]
    # fixed-order per-rank concatenation restores the original row order —
    # a concat, never a reduction, so the bitwise contract holds
    return ctx.shard_map(lambda *os: jnp.concatenate(os, axis=0),
                         in_specs=(shard_spec,) * len(outs),
                         out_specs=shard_spec)(*outs)


def moe_mlp_tp_overlap(ctx: ShmemContext, x2d: jax.Array,
                       router_w: jax.Array, we_up: jax.Array,
                       we_down: jax.Array, topk: int,
                       axis: str | None = None,
                       block_m: int = 128) -> jax.Array:
    """The reference's MoE-TP inference block on the FUSED overlap kernels
    (test_ag_moe + test_moe_reduce_rs composed, the
    "AG+GroupGEMM → GroupGEMM+topk-reduce+RS" pipeline of
    allgather_group_gemm.py + moe_reduce_rs.py):

    1. router → top-k experts per token,
    2. ``ag_moe_group_gemm``: tokens allgathered across the TP group while
       the grouped up-projection streams arrived segments (weights
       column-sharded [E, D, F] P(None, None, axis)),
    3. activation,
    4. ``moe_reduce_rs``: grouped down-projection on the F-shard
       (weights row-sharded [E, F, D] P(None, axis, None)) ring-scattered
       to token owners with the topk-weighted fold at the end.

    x2d [T, D] sharded P(axis) on T; returns [T, D] sharded P(axis).
    Every (token, k) pair is one row through both grouped GEMMs — the
    reference's row expansion (moe_reduce_rs.py select_experts)."""
    from triton_dist_tpu.ops.moe import ag_moe_group_gemm, moe_reduce_rs

    axis = axis or ctx.axis_names[0]
    D = x2d.shape[1]
    k = topk

    logits = x2d.astype(jnp.float32) @ router_w
    gate_vals, gate_ids = lax.top_k(jax.nn.softmax(logits, -1), k)
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    # one row per (token, k) pair, keeping rows of one token adjacent
    def expand(x_shard, ids_shard):
        rep = jnp.repeat(x_shard[:, None, :], k, axis=1).reshape(-1, D)
        return rep, ids_shard.reshape(-1)

    rep, ids_flat = ctx.shard_map(
        expand, in_specs=(P(axis), P(axis)),
        out_specs=(P(axis), P(axis)))(x2d, gate_ids)

    # up-projection overlapped with the token allgather; output
    # [T*k, F] sharded P(None, axis)
    h = ag_moe_group_gemm(ctx, rep, ids_flat, we_up, axis=axis,
                          block_m=block_m)
    h = jax.nn.silu(h.astype(jnp.float32)).astype(x2d.dtype)

    # moe_reduce_rs needs the replicated global row→expert map; the fused
    # kernel path gathered it internally already, here once more for the
    # second stage (control-plane-sized: T*k ints)
    ids_rep = ctx.shard_map(
        lambda i: lax.all_gather(i, axis, tiled=True),
        in_specs=P(axis), out_specs=P(None))(ids_flat)

    return moe_reduce_rs(ctx, h, ids_rep, gate_vals, we_down, axis=axis,
                         block_m=block_m)


def moe_decode_step_sp(ctx: ShmemContext, a2a_layer, params: dict,
                       token: jax.Array, pos: jax.Array, cfg: MoEConfig,
                       cache: dict, sp_axis: str | None = None,
                       ag_method: str = "fused"
                       ) -> tuple[jax.Array, dict]:
    """DeepSeek-style serving decode step — BOTH showcase paths in one
    jitted step: sequence-parallel distributed flash-decode attention over
    the KV cache sharded on ``sp_axis`` (reference
    sp_flash_decode_layer.py:78-184) and the expert-parallel MoE FFN
    through the low-latency A2A dispatch/combine (test_ep_moe_inference.py
    composition). The single-axis deployment uses ONE axis for both: KV
    sequence shards and expert shards live on the same devices, which is
    the reference's serving topology (SP decode ranks == EP ranks).

    ``token`` [B] int32 with B = n_ranks * a2a.max_tokens;
    ``pos`` scalar int32; ``cache`` as ``init_kv_cache(cfg.base, ...)``
    stacked per layer, k/v sharded P(None, None, None, sp_axis, None).
    Returns (logits [B, V] f32, updated cache).

    Thin composition over ``llama.decode_step_sp``'s ``ffn`` hook — the
    attention/cache plumbing lives in exactly one place."""
    from triton_dist_tpu.models.llama import decode_step_sp

    a2a = a2a_layer.a2a
    assert a2a.num_experts == cfg.num_experts, (
        f"a2a layer built for {a2a.num_experts} experts but cfg routes "
        f"over {cfg.num_experts} — gate ids would address nonexistent "
        "ranks/slots")
    assert a2a.topk == cfg.topk, (a2a.topk, cfg.topk)

    def moe_ffn(h, p):
        return moe_mlp_ep_overlap(ctx, a2a_layer, h, p["w_router"],
                                  p["we_gate"], p["we_up"], p["we_down"])

    return decode_step_sp(ctx, params, token, pos, cfg.base, cache,
                          axis=sp_axis, ag_method=ag_method, ffn=moe_ffn)


__all__ = ["MoEConfig", "init_moe_params", "moe_param_specs",
           "moe_mlp_gshard", "moe_block_apply", "moe_forward",
           "moe_mlp_ep_overlap", "moe_mlp_tp_overlap", "moe_decode_step_sp"]
