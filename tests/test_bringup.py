"""Bring-up contract (ISSUE 21): nothing may make a CPU, interpret-mode or
simulated path pass for the chip, and the entry points reach the
full-width presets.

- ``chip_smoke.py --cpu-rehearsal`` (the explicit tiny CPU rehearsal) exits
  0 with its JSON lines; without the flag a non-TPU platform is exit != 0
  and no result on stdout;
- ``serve_sim.py --mesh`` with too few devices and no ``--sim`` fails
  naming the device count — never a silently provisioned CPU mesh;
- the compile-cache helper leaves a set ``JAX_COMPILATION_CACHE_DIR`` alone
  and otherwise picks ``<checkout>/.jax_cache``;
- ``--preset mistral_7b --layers 1`` resolves to the published widths.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from conftest import REPO_ROOT

pytestmark = pytest.mark.quick


def _run(*argv, timeout=240):
    """A child the way a user's shell starts it on this sandbox: CPU
    platform, and NOT the suite's forced 12-device XLA_FLAGS."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, *argv], cwd=REPO_ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_chip_smoke_cpu_rehearsal():
    r = _run("chip_smoke.py", "--cpu-rehearsal")
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [json.loads(ln) for ln in r.stdout.splitlines() if ln.strip()]
    probe, summary, last = lines[0], lines[-2], lines[-1]
    assert probe["device"]["platform"] == "cpu"
    assert {"jax", "jaxlib", "libtpu", "native_host_ops",
            "compile_cache"} <= set(probe)
    one = next(ln for ln in lines if ln.get("leg") == "one_chip")
    assert one["requests_finished"] == one["requests_submitted"] >= 4
    assert one["compile_stats"]["decode_compiles"] == 1
    assert one["compile_stats"]["prefill_chunk_compiles"] == 1
    assert one["logits_ok"] and one["logits_max_abs_err"] <= one["logits_tol"]
    assert one["reduced"] == {"n_layers": 2}
    four = next(ln for ln in lines if ln.get("leg") == "four_chip")
    assert four["skipped"] == "1 device"       # loud, never silent
    assert summary["leg"] == "summary" and summary["rehearsal"] is True
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    # the driver's contract: LAST line, exactly these keys
    assert last == {"ok": True, "device": probe["device"]}
    assert set(last["device"]) == {"platform", "kind", "count"}


def test_chip_smoke_refuses_non_tpu():
    r = _run("chip_smoke.py")
    assert r.returncode != 0
    assert r.stdout.strip() == "", "a refused run must print no result"
    assert "not 'tpu'" in r.stderr


def test_serve_sim_mesh_names_the_device_count():
    r = _run("scripts/serve_sim.py", "--mesh", "1x2x2", "--workload", "n=2")
    assert r.returncode != 0
    assert "needs 4 devices but jax sees 1" in r.stderr, r.stderr[-2000:]


def test_compile_cache_helper(monkeypatch, tmp_path):
    from triton_dist_tpu.utils.env import configure_compile_cache
    saved = jax.config.jax_compilation_cache_dir
    try:
        # placed from outside: nothing is touched, no other dir set in code
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == saved
        # not placed: the checkout's fixed path, never a temp name
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO_ROOT, ".jax_cache")
        assert configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)


def test_presets_resolve_to_published_widths():
    """Shapes only (``jax.eval_shape``): nothing is allocated."""
    from triton_dist_tpu.models import (init_moe_params, init_params,
                                        preset_config)
    key = jax.random.PRNGKey(0)
    family, cfg = preset_config("mistral_7b", None, 1)
    assert family == "llama" and cfg.n_layers == 1
    p = jax.eval_shape(lambda k: init_params(k, cfg), key)
    assert p["embed"].shape == (32000, 4096)
    b = p["blocks"]
    assert b["wq"].shape == (1, 4096, 32 * 128)
    assert b["wk"].shape == b["wv"].shape == (1, 4096, 8 * 128)
    assert b["w_gate"].shape == b["w_up"].shape == (1, 4096, 14336)
    assert b["w_down"].shape == (1, 14336, 4096)
    assert b["wq"].dtype == jax.numpy.bfloat16

    family, moe = preset_config("mixtral_8x7b", None, 1)
    assert family == "moe" and (moe.num_experts, moe.topk) == (8, 2)
    b = jax.eval_shape(lambda k: init_moe_params(k, moe), key)["blocks"]
    assert b["we_gate"].shape == (1, 8, 4096, 14336)
    assert b["we_down"].shape == (1, 8, 14336, 4096)

    with pytest.raises(ValueError, match="unknown preset"):
        preset_config("mistral_7b", "moe")
    assert preset_config("tiny")[1].n_layers == 2        # default depth
