"""Distributed train-step composition tests: dp/tp/sp GSPMD sharding, GPipe
pipeline parallelism, and GShard MoE expert parallelism — one jitted step
each on the virtual CPU mesh (this is what the driver's multi-chip dryrun
compiles; beyond the reference's kernel-library scope, SURVEY.md §2.4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TEST_WORLD  # noqa: F401  (conftest sets up the mesh)
from triton_dist_tpu.models.llama import LlamaConfig
from triton_dist_tpu.models.moe import MoEConfig
from triton_dist_tpu.parallel import (ParallelPlan, factorize_devices,
                                      make_mesh, make_train_step)


def _tokens(cfg, B=4, S=16):
    vocab = cfg.base.vocab_size if isinstance(cfg, MoEConfig) else cfg.vocab_size
    return jax.random.randint(jax.random.key(7), (B, S), 0, vocab)


def test_factorize_devices():
    assert factorize_devices(8) == {"dp": 2, "pp": 2, "tp": 2}
    assert factorize_devices(4) == {"dp": 1, "pp": 2, "tp": 2}
    assert factorize_devices(2) == {"dp": 1, "pp": 1, "tp": 2}
    assert factorize_devices(1) == {"dp": 1, "pp": 1, "tp": 1}


def test_dense_dp_tp_sp_step():
    cfg = LlamaConfig.tiny(n_layers=2)
    mesh = make_mesh({"dp": 2, "tp": 2})
    plan = ParallelPlan(dp="dp", tp="tp", sp=True)
    init_fn, step_fn = make_train_step(cfg, mesh, plan)
    with jax.set_mesh(mesh):
        state = init_fn(jax.random.key(0))
        tokens = _tokens(cfg)
        losses = []
        for _ in range(3):
            state, loss = step_fn(state, tokens)
            losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    # same batch re-fed: the optimizer must reduce the loss
    assert losses[-1] < losses[0], losses


def test_dense_pp_matches_no_pp():
    """GPipe pipeline forward/backward must be numerically equivalent to the
    sequential layer scan."""
    cfg = LlamaConfig.tiny(n_layers=2)
    tokens = _tokens(cfg)

    mesh1 = make_mesh({"dp": 1, "tp": 2})
    init1, step1 = make_train_step(cfg, mesh1, ParallelPlan(dp="dp", tp="tp"))
    mesh2 = make_mesh({"pp": 2, "tp": 2})
    init2, step2 = make_train_step(
        cfg, mesh2, ParallelPlan(dp=None, tp="tp", pp="pp", n_micro=2))

    with jax.set_mesh(mesh1):
        s1 = init1(jax.random.key(0))
        _, loss1 = step1(s1, tokens)
    with jax.set_mesh(mesh2):
        s2 = init2(jax.random.key(0))
        _, loss2 = step2(s2, tokens)
    np.testing.assert_allclose(float(loss1), float(loss2), rtol=2e-2)


def test_moe_ep_step():
    cfg = MoEConfig.tiny(n_layers=2, num_experts=4)
    mesh = make_mesh({"dp": 2, "ep": 2})
    plan = ParallelPlan(dp="dp", tp=None, ep="ep", sp=False)
    init_fn, step_fn = make_train_step(cfg, mesh, plan)
    with jax.set_mesh(mesh):
        state = init_fn(jax.random.key(0))
        state, loss = step_fn(state, _tokens(cfg))
    assert np.isfinite(float(loss))


def _cp_cfg():
    # ring attention needs lane-multiple head_dim: 512 / 4 = 128
    return LlamaConfig(vocab_size=512, d_model=512, n_layers=2, n_heads=4,
                       n_kv_heads=2, d_ff=256, max_seq_len=64)


def test_dense_dp_cp_step():
    """Context-parallel training: ring attention over the cp axis, batch
    over dp (sequence dim sharded end-to-end; the long-context training
    composition the reference lacks, SURVEY §5.7)."""
    cfg = _cp_cfg()
    mesh = make_mesh({"dp": 2, "cp": 2})
    plan = ParallelPlan(dp="dp", tp=None, cp="cp", sp=False)
    init_fn, step_fn = make_train_step(cfg, mesh, plan)
    with jax.set_mesh(mesh):
        state = init_fn(jax.random.key(0))
        tokens = _tokens(cfg, B=4, S=32)
        losses = []
        for _ in range(2):      # ~20 s a step on the interpreter
            state, loss = step_fn(state, tokens)
            losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


def test_dense_tp_cp_step():
    """cp composes with tp: heads ride the tp axis (independent rings per
    tp row), params Megatron-sharded."""
    cfg = _cp_cfg()
    mesh = make_mesh({"tp": 2, "cp": 2})
    plan = ParallelPlan(dp=None, tp="tp", cp="cp", sp=False)
    init_fn, step_fn = make_train_step(cfg, mesh, plan)
    with jax.set_mesh(mesh):
        state = init_fn(jax.random.key(0))
        tokens = _tokens(cfg, B=2, S=32)
        state, loss = step_fn(state, tokens)
        state, loss2 = step_fn(state, tokens)
    assert np.isfinite(float(loss)) and float(loss2) < float(loss)


def test_cp_matches_dense_forward():
    """The cp loss equals the no-cp loss on identical params/tokens."""
    cfg = _cp_cfg()
    mesh = make_mesh({"cp": 2})
    plan_cp = ParallelPlan(dp=None, tp=None, cp="cp", sp=False)
    plan_ref = ParallelPlan(dp=None, tp=None, sp=False)
    init_cp, step_cp = make_train_step(cfg, mesh, plan_cp)
    init_ref, step_ref = make_train_step(cfg, mesh, plan_ref)
    with jax.set_mesh(mesh):
        tokens = _tokens(cfg, B=2, S=32)
        s_cp = init_cp(jax.random.key(0))
        s_ref = init_ref(jax.random.key(0))
        _, l_cp = step_cp(s_cp, tokens)
        _, l_ref = step_ref(s_ref, tokens)
    np.testing.assert_allclose(float(l_cp), float(l_ref), rtol=2e-3)


def test_moe_pp_step():
    """PP+MoE composition: GPipe wavefront with per-stage MoE blocks and
    bubble-masked aux-loss accumulation."""
    cfg = MoEConfig.tiny(n_layers=2, num_experts=4)
    mesh = make_mesh({"pp": 2})
    plan = ParallelPlan(dp=None, tp=None, pp="pp", ep=None, sp=False,
                        n_micro=2)
    init_fn, step_fn = make_train_step(cfg, mesh, plan)
    with jax.set_mesh(mesh):
        state = init_fn(jax.random.key(0))
        tokens = _tokens(cfg.base, B=4, S=16)
        losses = []
        for _ in range(3):
            state, loss = step_fn(state, tokens)
            losses.append(float(loss))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses


def test_moe_pp_matches_no_pp():
    """PP+MoE loss ~= the no-pp MoE loss on identical params/tokens (the
    balance aux is microbatch-averaged under pp — tolerance covers it)."""
    cfg = MoEConfig.tiny(n_layers=2, num_experts=4)
    mesh = make_mesh({"pp": 2})
    plan_pp = ParallelPlan(dp=None, tp=None, pp="pp", ep=None, sp=False,
                          n_micro=2)
    plan_ref = ParallelPlan(dp=None, tp=None, ep=None, sp=False)
    init_pp, step_pp = make_train_step(cfg, mesh, plan_pp)
    init_ref, step_ref = make_train_step(cfg, mesh, plan_ref)
    with jax.set_mesh(mesh):
        tokens = _tokens(cfg.base, B=4, S=16)
        s_pp = init_pp(jax.random.key(0))
        s_ref = init_ref(jax.random.key(0))
        _, l_pp = step_pp(s_pp, tokens)
        _, l_ref = step_ref(s_ref, tokens)
    np.testing.assert_allclose(float(l_pp), float(l_ref), rtol=5e-2)
