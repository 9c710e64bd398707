"""An engine's programs belong to a configuration and a shape, not to an
engine (ISSUE 42): they are built HERE, once a process for everything their
trace and compile read, and every engine of that key holds the SAME
``jax.jit`` objects (on one chip: the same compiled decode program, chunk
``jit`` and formats). A second engine of a key traces and compiles nothing;
each still commits its own weights and pool, which the programs take as
arguments. The shapes the programs are called at are in the key, so one
``jit`` sees one signature and ``compile_stats`` reads 1 and 1 for the first
engine of a shape and the tenth. ``_MEMO`` holds functions, ``jit`` objects
and formats (no array) for the life of the process, an entry a distinct key;
hooks are keyed by identity, so fresh closures an engine would be an entry an
engine (``sharded._hooks`` hands out one set for what they close over). There
is no switch: a test that wants a trace of its own wraps the attribute on ITS
engine, or starts from an empty ``_MEMO``.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from triton_dist_tpu.models.llama import (decode_multistep_paged,
                                          prefill_chunk_paged)
from triton_dist_tpu.ops.page_migrate import migrate_pages
from triton_dist_tpu.serving import layouts

_MEMO: dict = {}


def _memoised(build):
    """``build(*key)``, once a process for a key and the platform (which
    :func:`jit` and the builders observe)."""
    def get(*key):
        key = (build, jax.default_backend(), *key)
        if key not in _MEMO:
            _MEMO[key] = build(*key[2:])
        return _MEMO[key]
    return get


def signature(tree) -> tuple:
    """What a trace and a compile read of a tree of arrays (or of
    ``jax.ShapeDtypeStruct``), hashable: its structure and every leaf's
    shape, dtype, sharding and committedness."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, tuple(
        (a.shape, jnp.dtype(a.dtype).name, getattr(a, "sharding", None),
         getattr(a, "committed", None)) for a in leaves)


def abstract(sig, placed=False):
    """The tree of ``jax.ShapeDtypeStruct`` a :func:`signature` describes."""
    return sig[0].unflatten([
        jax.ShapeDtypeStruct(s, d, sharding=where if placed else None)
        for s, d, where, _ in sig[1]])


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def compiles(program, fallback: int) -> int:
    """The signatures ``program`` has compiled for (``fallback`` where it is
    no ``jax.jit``: a program compiled ahead, or loaded from an artifact)."""
    try:
        return int(program._cache_size())
    except Exception:
        return fallback


def lint_if_asked(lint, owner) -> None:
    """``TDT_SIGCHECK=1``: lint an engine's programs (name -> (function,
    abstract arguments)) against the trace-determinism contract at BUILD time
    (sigcheck rung 0, docs/debugging.md). Trace-only: a rank-count-dependent
    reduction or a host callback in the hot path raises here, before any
    request is admitted."""
    if os.environ.get("TDT_SIGCHECK") == "1":
        from triton_dist_tpu.analysis.lint import lint_engine_programs
        lint_engine_programs(lint, type(owner).__name__)


def jit(fn, donate, **kw):
    """``jax.jit`` as the platform takes it. The CPU donates nothing, and its
    concurrency-optimised schedule may order a collective and an interpreted
    kernel differently on two virtual devices, which aborts the simulator
    (ROADMAP C9): it gets the plain schedule."""
    if jax.default_backend() == "cpu":
        return jax.jit(fn, compiler_options={
            "xla_cpu_enable_concurrency_optimized_scheduler": False}, **kw)
    return jax.jit(fn, donate_argnums=donate, **kw)


@_memoised
def engine_programs(cfg, horizon, eos_id, spec_k, spec_hist, prefill_chunk,
                    num_slots, table_width, pool, params, hooks,
                    pool_sharding, held):
    """``ServingEngine``'s ``(decode, chunk, formats, lint)`` for a (bound)
    configuration, horizon, ``eos_id``, the speculation pair (``spec_bucket``
    only picks ``spec_k``), the chunk's rows, the slots, the table's width,
    the :func:`signature` of the pool AS THE PROGRAMS MEET IT and of the
    parameters, the hooks ``(ffn, ffn_chunk, attn_io, linear)``, the pool's
    output sharding on a mesh, and ``held``: off the CPU (one chip, no
    artifact) the decode program is compiled at once and chooses every
    parameter leaf's layout, the chunk program follows its ``formats``
    (``serving/layouts.py``; elsewhere two plain :func:`jit`, and ``formats``
    is None). ``lint``: name -> (the function under the program, its abstract
    arguments), for :func:`lint_if_asked`."""
    fam = cfg.paged
    ffn, ffn_chunk, attn_io, linear = hooks
    B, pages = num_slots, abstract(pool)
    decode_rest = (_i32(B), _i32(B), pages, _i32(B, table_width), _i32(B))
    if spec_k:
        decode_rest += (_i32(B, spec_hist), _i32(B))

        def step(p, t, pos, pages, bt, lim, hist, hlen):
            return fam.decode_speculate(
                p, t, pos, cfg, pages, bt, lim, horizon=horizon, hist=hist,
                hist_len=hlen, eos_id=eos_id, ffn=ffn, attn_io=attn_io,
                linear=linear)
    else:
        def step(p, t, pos, pages, bt, lim):
            return decode_multistep_paged(
                p, t, pos, cfg, pages, bt, lim, horizon=horizon,
                eos_id=eos_id, ffn=ffn, attn_io=attn_io, linear=linear)

    # ONE program for every prompt length/position: chunk size is the only
    # shape; cursor and prompt length ride as runtime scalars
    def chunk(p, t, s, n, pages, bt):
        return prefill_chunk_paged(
            p, t, s, n, cfg, pages, bt, ffn=ffn_chunk or ffn,
            attn_io=attn_io, linear=linear)

    w = abstract(params)
    lint = {
        "decode_speculate_paged" if spec_k else "decode_multistep_paged":
            (step, (w, *decode_rest)),
        "prefill_chunk_paged": (chunk, (
            w, _i32(prefill_chunk), _i32(), _i32(), pages,
            _i32(table_width)))}
    if held and jax.default_backend() != "cpu":
        return (*layouts.held_layout_programs(
            step, chunk, abstract(params, placed=True), decode_rest), lint)
    # the pool's output sharding is pinned at the jit boundary: left to
    # GSPMD it may differ from the committed SP input sharding (the a2a's
    # all_to_all regions perturb the propagation) and the SECOND dispatch
    # would recompile. The fed-back token/pos carries are pinned replicated
    # for the same reason.
    step_kw, chunk_kw = {}, {}
    if pool_sharding is not None:
        ps = {"k": pool_sharding, "v": pool_sharding}
        rep = jax.sharding.NamedSharding(pool_sharding.mesh,
                                         jax.sharding.PartitionSpec())
        step_kw["out_shardings"] = ((None, None, rep, rep, rep, rep, ps)
                                    if spec_k else (None, rep, rep, ps))
        chunk_kw["out_shardings"] = (None, ps)
    return (jit(step, (3,), **step_kw), jit(chunk, (4,), **chunk_kw), None,
            lint)


@_memoised
def disagg_programs(cfg, horizon, eos_id, ffn, ctx, axis, roles,
                    prefill_chunk, num_slots, table_width, pmax, pools,
                    params):
    """``DisaggServingEngine``'s ``(chunk, decode, migrate, lint)``: three
    SPMD programs (both roles enter each; the off-role shard runs on parked
    inputs) on ``ctx``'s mesh (a context is its mesh) along ``axis``,
    ``roles`` the (producer, consumer) of a migration; the rest of the key as
    above, ``pmax`` a migration's widest page list."""
    P = jax.sharding.PartitionSpec

    def chunk_f(p, toks, start, plen, kp, vp, bt):
        pages = {"k": kp[0], "v": vp[0]}
        tok, pages = prefill_chunk_paged(
            p, toks[0], start[0], plen[0], cfg, pages, bt[0], ffn=ffn)
        return tok[None], pages["k"][None], pages["v"][None]

    def dec_f(p, tok, pos, kp, vp, bt, lim):
        pages = {"k": kp[0], "v": vp[0]}
        toks, tok2, pos2, pages = decode_multistep_paged(
            p, tok[0], pos[0], cfg, pages, bt[0], lim[0],
            horizon=horizon, eos_id=eos_id, ffn=ffn)
        return (toks[None], tok2[None], pos2[None],
                pages["k"][None], pages["v"][None])

    def mig_f(src, dst, n, tag, kp, vp):
        return migrate_pages(ctx, kp, vp, src, dst, n, axis=axis,
                             producer=roles[0], consumer=roles[1], tag=tag)

    chunk_sm = ctx.shard_map(chunk_f, in_specs=(P(),) + (P(axis),) * 6,
                             out_specs=(P(axis),) * 3)
    dec_sm = ctx.shard_map(dec_f, in_specs=(P(),) + (P(axis),) * 6,
                           out_specs=(P(axis),) * 5)
    B, kv, w = num_slots, abstract(pools), abstract(params)
    lint = {
        "prefill_chunk_paged": (chunk_sm, (
            w, _i32(2, prefill_chunk), _i32(2), _i32(2), *kv,
            _i32(2, table_width))),
        "decode_multistep_paged": (dec_sm, (
            w, _i32(2, B), _i32(2, B), *kv, _i32(2, B, table_width),
            _i32(2, B))),
        "migrate_pages": (mig_f, (
            _i32(pmax), _i32(pmax), _i32(1), _i32(), *kv))}
    return (jit(chunk_sm, (4, 5)), jit(dec_sm, (3, 4)), jit(mig_f, (4, 5)),
            lint)


__all__ = ["abstract", "compiles", "disagg_programs", "engine_programs", "jit",
           "lint_if_asked", "signature"]
