"""Tuned-config registry + persisted AOT serving artifact (ISSUE 15).

Two contracts under test:

1. **Registry**: winners persist as JSON keyed on
   ``(op, mesh_shape, dtype, shape_bucket)``; sigcheck is the ADMISSION
   gate — a mesh-keyed config whose kernel the verifier flags never
   becomes a persisted default (proved with a gallery-broken kernel
   through the ``run=`` override); a torn/tampered file is a typed
   ``RegistryIntegrityError``, never a silently-default sweep.

2. **Artifact**: ``build_artifact`` → fresh ``load_artifact`` →
   ``make_engine(artifact=...)`` reaches its first token with ZERO fresh
   jit traces (every ``*_compiles`` stat pinned to 0, ``aot_programs``
   pinned to the program-set size), and a forced-preemption
   trace is BIT-IDENTICAL artifact-on vs artifact-off — on the colocated
   engine and the sharded engine at n∈{1,2} (n=4 rides the slow tier).
   A stale key (spec digest, topology, jax version) is a typed
   ``ArtifactMissError``; a tampered manifest or program file is a typed
   ``ArtifactIntegrityError``.
"""

import json
import os
import shutil

import jax
import pytest

from conftest import N_REQUESTS, seeded_trace, xfail_on_cpu
from triton_dist_tpu.aot import (ArtifactIntegrityError, ArtifactMissError,
                                 ArtifactSpec, RegistryAdmissionError,
                                 RegistryIntegrityError, TunedConfigRegistry,
                                 TunedKey, build_artifact, load_artifact,
                                 make_engine, shape_bucket_of)
from triton_dist_tpu.ops.gemm import GemmConfig

pytestmark = [pytest.mark.aot, pytest.mark.serving]

MAX_STEPS = 100_000


@pytest.fixture(scope="module", autouse=True)
def _private_xla_cache(tmp_path_factory):
    """Run this module against a module-PRIVATE XLA persistent cache.

    ``build_artifact``/``load_artifact`` deliberately redirect and seed the
    process's persistent compilation cache — that IS the cold-start feature
    under test. Under pytest the conftest installs ONE cache dir shared by
    the whole run, so without isolation this module's rehearsals and
    artifact-entry copies would change which compile instance later test
    modules hit, breaking their run-order hermeticity (observed as a
    bit-identity failure in test_slo.py only in full-suite order)."""
    from triton_dist_tpu.aot.artifact import _reset_xla_cache

    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("aot-private-xla-cache")))
    _reset_xla_cache()
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        _reset_xla_cache()


# -- 1. the tuned-config registry --------------------------------------------

def _local_key(op="grouped_gemm", bucket=((64, 128),)):
    """A single-device key (no mesh → no signal protocol → ungated)."""
    return TunedKey(op=op, mesh_shape=(), dtype="float32",
                    shape_bucket=bucket)


def test_registry_round_trip(tmp_path):
    """put → save → load → get returns the SAME configs, every key type."""
    reg = TunedConfigRegistry()
    k1 = _local_key()
    k2 = _local_key(op="moe_ffn_gated",
                    bucket=shape_bucket_of((48, 100), (4, 100, 60)))
    reg.put(k1, GemmConfig(64, 64, 64))
    reg.put(k2, 128)
    path = str(tmp_path / "tuned.json")
    reg.save(path)

    reg2 = TunedConfigRegistry.load(path)
    assert len(reg2) == 2
    assert reg2.get(k1) == GemmConfig(64, 64, 64)
    assert reg2.get(k2) == 128
    assert reg2.get(_local_key(op="nope")) is None
    assert reg2.hit_rate == pytest.approx(2 / 3)


def test_registry_tamper_is_typed(tmp_path):
    """A flipped byte in the persisted file is a RegistryIntegrityError —
    a torn registry must never silently feed default configs."""
    reg = TunedConfigRegistry()
    reg.put(_local_key(), GemmConfig(64, 64, 64))
    path = str(tmp_path / "tuned.json")
    reg.save(path)

    with open(path, encoding="utf-8") as f:
        text = f.read()
    assert '"block_m": 64' in text
    with open(path, "w", encoding="utf-8") as f:
        f.write(text.replace('"block_m": 64', '"block_m": 65', 1))
    with pytest.raises(RegistryIntegrityError, match="torn or tampered"):
        TunedConfigRegistry.load(path)


def test_registry_admits_verified_mesh_config():
    """The happy path through the admission gate: a real op's config is
    sigcheck-captured on the gate meshes and recorded as checked."""
    reg = TunedConfigRegistry()
    key = TunedKey(op="ag_gemm", mesh_shape=(2,), dtype="float32",
                   shape_bucket=((128, 128), (128, 128)))
    reg.put(key, GemmConfig(8, 16, 0))
    assert reg.get(key) == GemmConfig(8, 16, 0)
    assert reg.checked(key)


def test_registry_gate_refuses_flagged_kernel():
    """THE admission contract: a gallery-broken kernel pushed through the
    ``run=`` override is refused with a typed finding — a flagged config
    never becomes a persisted default."""
    from triton_dist_tpu.analysis.checker import UNORDERED_READ
    from triton_dist_tpu.analysis.gallery import GALLERY
    reg = TunedConfigRegistry()
    key = TunedKey(op="ag_gemm", mesh_shape=(2,), dtype="float32",
                   shape_bucket=((128, 128), (128, 128)))
    with pytest.raises(RegistryAdmissionError) as ei:
        reg.put(key, GemmConfig(8, 16, 0),
                run=GALLERY["missing_wait"].run)
    assert UNORDERED_READ in ei.value.finding_kinds
    assert reg.get(key) is None          # nothing persisted


def test_registry_refuses_unverifiable_mesh_op():
    """A mesh-keyed op with NO gate runner cannot enter a sigcheck-gated
    registry: unverified-by-construction is refused, not waved through."""
    reg = TunedConfigRegistry()
    key = TunedKey(op="mystery_op", mesh_shape=(2,), dtype="float32",
                   shape_bucket=((8, 8),))
    with pytest.raises(RegistryAdmissionError, match="no sigcheck gate"):
        reg.put(key, 64)
    # the same put is fine on an explicitly ungated registry — recorded
    # as unchecked, the caller opted out
    reg2 = TunedConfigRegistry(require_sigcheck=False)
    reg2.put(key, 64)
    assert reg2.get(key) == 64
    assert not reg2.checked(key)


# -- 2. the persisted AOT artifact -------------------------------------------
# Tight pools (9 pages, 4 slots) force growth-driven preemption in every
# trace — the bit-identity claim covers the preemption path, not a
# steady-state decode loop.

_LLAMA = {"kind": "llama", "vocab_size": 128, "d_model": 32,
          "n_layers": 1, "n_heads": 2, "n_kv_heads": 1, "d_ff": 64,
          "max_seq_len": 64, "dtype": "float32"}
_MOE = {"kind": "moe",
        "base": {"vocab_size": 128, "d_model": 128, "n_layers": 1,
                 "n_heads": 4, "n_kv_heads": 2, "d_ff": 128,
                 "max_seq_len": 128, "dtype": "float32"},
        "num_experts": 4, "topk": 2, "moe_d_ff": 64}
_POOL = {"num_slots": 4, "page_size": 8, "num_pages": 9,
         "pages_per_seq": 4, "prefill_chunk": 8}


needs_export = xfail_on_cpu(
    "jax.export refuses the 0.9 Pallas interpreter's host callbacks "
    "(NotImplementedError: serialization of host_callbacks is not yet "
    "implemented), so build_artifact cannot persist an interpret-mode "
    "program; on a chip the kernels lower to Mosaic and these must pass")


def _spec(model, kind, mesh=None):
    decl = dict(_POOL, kind=kind)
    if mesh is not None:
        decl["mesh"] = mesh
    return ArtifactSpec(model=model, engines=[decl], seed=0)


def _build(tmp_path_factory, name, spec):
    out = str(tmp_path_factory.mktemp(name) / "artifact")
    build_artifact(spec, out)
    return out


@pytest.fixture(scope="module")
def colocated_art(tmp_path_factory):
    return _spec(_LLAMA, "colocated"), _build(
        tmp_path_factory, "aot-colo", _spec(_LLAMA, "colocated"))


@pytest.fixture(scope="module")
def sharded_arts(tmp_path_factory):
    """One artifact per rank count n∈{1,2} (sp is the split axis — the
    MoE's 2 KV heads cap tp at 2 but sp scales freely)."""
    out = {}
    for n in (1, 2):
        spec = _spec(_MOE, "sharded", mesh={"tp": 1, "sp": n, "ep": 1})
        out[n] = (spec, _build(tmp_path_factory, f"aot-sh{n}", spec))
    return out


def _serve(spec, art_dir=None):
    """Build the spec's engine (artifact-seeded when ``art_dir`` is set),
    serve the trace, return tokens + compile stats."""
    cfg = spec.model_config()
    params = spec.init_params()
    artifact = load_artifact(art_dir, spec=spec) if art_dir else None
    eng = make_engine(spec.engines[0], params, cfg, artifact=artifact)
    tokens = eng.run(max_steps=MAX_STEPS, arrivals=seeded_trace(N_REQUESTS))
    return tokens, eng.compile_stats, dict(eng.metrics.counters)


def _assert_zero_traces(stats, n_programs):
    """THE cold-start guard: no compile stat moved, every dispatched
    program came out of the artifact."""
    fresh = {k: v for k, v in stats.items()
             if k.endswith("_compiles") and v}
    assert not fresh, f"artifact cold start paid fresh traces: {fresh}"
    assert stats["aot_programs"] == n_programs, stats


@needs_export
def test_colocated_zero_trace_and_bit_identity(colocated_art):
    spec, art = colocated_art
    golden, g_stats, g_counters = _serve(spec)
    tokens, stats, counters = _serve(spec, art)

    assert sum(v for k, v in g_stats.items()
               if k.endswith("_compiles")) > 0     # the baseline DID trace
    _assert_zero_traces(stats, n_programs=2)       # chunk + decode
    assert g_counters["preemptions"] > 0           # the trace preempts
    assert counters["preemptions"] == g_counters["preemptions"]
    assert tokens == golden                # bit-identical, every request


@needs_export
@pytest.mark.parametrize("n", [1, 2])
def test_sharded_zero_trace_and_bit_identity(sharded_arts, n):
    spec, art = sharded_arts[n]
    golden, _, g_counters = _serve(spec)
    tokens, stats, counters = _serve(spec, art)
    _assert_zero_traces(stats, n_programs=2)       # chunk + decode
    assert counters["preemptions"] == g_counters["preemptions"] > 0
    assert tokens == golden


@pytest.mark.slow
@needs_export
def test_sharded_zero_trace_and_bit_identity_n4(tmp_path_factory):
    spec = _spec(_MOE, "sharded", mesh={"tp": 1, "sp": 4, "ep": 1})
    art = _build(tmp_path_factory, "aot-sh4", spec)
    golden, _, g_counters = _serve(spec)
    tokens, stats, counters = _serve(spec, art)
    _assert_zero_traces(stats, n_programs=2)
    assert counters["preemptions"] == g_counters["preemptions"] > 0
    assert tokens == golden


@needs_export
def test_stale_spec_is_typed_miss(colocated_art):
    """A changed fleet declaration = a different spec digest = a LOUD
    typed miss at load, never a shape error at dispatch."""
    _, art = colocated_art
    changed = _spec(dict(_LLAMA, d_model=64), "colocated")
    with pytest.raises(ArtifactMissError, match="spec digest"):
        load_artifact(art, spec=changed)


@needs_export
def test_missing_program_is_typed_miss(colocated_art):
    spec, art = colocated_art
    loaded = load_artifact(art, spec=spec)
    with pytest.raises(ArtifactMissError, match="holds no program"):
        loaded.program("colocated", "warp_drive")


@needs_export
def test_tampered_manifest_is_typed(colocated_art, tmp_path):
    """Editing the manifest without recomputing its digest is detected —
    the copy keeps the module-scoped fixture pristine."""
    _, art = colocated_art
    copy = str(tmp_path / "artifact")
    shutil.copytree(art, copy)
    mpath = os.path.join(copy, "MANIFEST.json")
    with open(mpath, encoding="utf-8") as f:
        manifest = json.load(f)
    manifest["device_count"] = 1
    with open(mpath, "w", encoding="utf-8") as f:
        json.dump(manifest, f)
    with pytest.raises(ArtifactIntegrityError, match="torn or tampered"):
        load_artifact(copy)


@needs_export
def test_tampered_program_is_typed(colocated_art, tmp_path):
    spec, art = colocated_art
    copy = str(tmp_path / "artifact")
    shutil.copytree(art, copy)
    pdir = os.path.join(copy, "programs")
    fname = sorted(os.listdir(pdir))[0]
    with open(os.path.join(pdir, fname), "r+b") as f:
        f.seek(100)
        b = f.read(1)
        f.seek(100)
        f.write(bytes([b[0] ^ 0xFF]))
    loaded = load_artifact(copy, spec=spec)
    name = loaded.program_names("colocated")[0]
    with pytest.raises(ArtifactIntegrityError, match="torn or tampered"):
        loaded.program("colocated", name)


@needs_export
def test_jax_version_mismatch_is_typed_miss(colocated_art, tmp_path):
    """The load key covers the jax version — a manifest from another
    toolchain misses loudly (digest recomputed, so this is the MISS path,
    not the tamper path)."""
    _, art = colocated_art
    copy = str(tmp_path / "artifact")
    shutil.copytree(art, copy)
    mpath = os.path.join(copy, "MANIFEST.json")
    with open(mpath, encoding="utf-8") as f:
        manifest = json.load(f)
    manifest["jax"] = "0.0.1"
    from triton_dist_tpu.aot.artifact import _canon_digest
    manifest["digest"] = _canon_digest(
        {k: v for k, v in manifest.items() if k != "digest"})
    with open(mpath, "w", encoding="utf-8") as f:
        json.dump(manifest, f)
    with pytest.raises(ArtifactMissError, match="jax 0.0.1"):
        load_artifact(copy)
