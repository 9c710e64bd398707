"""Persisted AOT serving artifact: the full compiled-program set of a
declared engine fleet, serialized to one versioned directory so a replica
restart reaches its first token with ZERO fresh jit traces.

Two mechanisms compose (probed on this toolchain, both required):

1. **Serialized programs** — every engine program (prefill chunk, decode
   multistep or speculate, migrate, and the sharded variants at each
   declared mesh shape) is exported through ``jax.export`` at build time
   with the exact dispatch-time argument signature, recorded by driving a
   tiny probe workload through the real engine. Loading deserializes the
   StableHLO — the Python model code is never re-traced.
2. **The persisted XLA compilation cache** — deserialized programs still
   XLA-compile for the local topology, so the build rehearses the load
   path (``jit(exported.call).lower(...).compile()``) with the artifact's
   own ``xla-cache/`` directory active. A cold process installs that cache
   and the load-path compile becomes a disk hit.

Loading is keyed on (jax version, backend, topology, spec digest); any
mismatch raises a typed :class:`ArtifactMissError` — a stale artifact is a
loud miss, never a silent fresh trace. Program bytes are FNV-1a-digest
audited (the PR 13 snapshot-audit idiom, same as the registry file).
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from triton_dist_tpu.aot.registry import (TunedConfigRegistry, _fnv1a_bytes)

FORMAT_VERSION = 1
_MANIFEST = "MANIFEST.json"
_REGISTRY = "registry.json"
_PROGRAMS = "programs"
_XLA_CACHE = "xla-cache"


class ArtifactMissError(RuntimeError):
    """The artifact does not match this process (jax version / backend /
    topology / spec digest) or lacks a program the engine needs. Loud and
    typed: the caller decides between fresh-trace fallback and abort."""


class ArtifactIntegrityError(RuntimeError):
    """Persisted program bytes or the manifest fail their digest audit —
    the artifact directory is torn or tampered."""


# -- loaded programs ---------------------------------------------------------

class LoadedProgram:
    """One deserialized AOT program standing in for an engine's ``jax.jit``
    object. Dispatches go through the exported StableHLO via a thin
    ``jit(exported.call)`` wrapper — the SOURCE program (the model code the
    engine would otherwise trace) is never traced in this process, which
    is what ``_cache_size() == 0`` reports to ``compile_stats`` and the
    cold-start guards. The wrapper's own first call XLA-compiles the
    deserialized module; with the artifact's xla-cache installed that is a
    disk hit, not a compile."""

    def __init__(self, name: str, exported):
        self.name = name
        self.exported = exported
        self._fn = jax.jit(exported.call)

    def __call__(self, *args):
        return self._fn(*args)

    def _cache_size(self) -> int:
        # fresh traces of the source program: zero by construction
        return 0


# -- specs -------------------------------------------------------------------

def _canon_digest(obj) -> str:
    return f"{_fnv1a_bytes(json.dumps(obj, sort_keys=True).encode()):08x}"


@dataclasses.dataclass
class ArtifactSpec:
    """Declares what the artifact compiles: one model and a list of engine
    declarations. Each engine entry is a plain dict::

        {"kind": "colocated" | "sharded" | "disagg" | "disagg_sharded",
         "mesh": {"tp": 1, "sp": 2, "ep": 2},     # sharded kinds only
         ...engine ctor kwargs (num_slots, page_size, num_pages,
            pages_per_seq, prefill_chunk, ...)}

    ``model`` is ``{"kind": "llama"|"moe", ...config fields}`` (dtype as a
    string). The spec digest keys artifact staleness: change the fleet
    declaration and every consumer sees a typed miss, not a shape error.
    """

    model: dict
    engines: List[dict]
    seed: int = 0

    def to_json(self) -> dict:
        return {"model": self.model, "engines": self.engines,
                "seed": self.seed}

    @classmethod
    def from_json(cls, d: dict) -> "ArtifactSpec":
        return cls(model=d["model"], engines=d["engines"],
                   seed=d.get("seed", 0))

    def digest(self) -> str:
        return _canon_digest(self.to_json())

    # -- model materialization -------------------------------------------
    def model_config(self):
        from triton_dist_tpu.models.llama import LlamaConfig
        m = dict(self.model)
        kind = m.pop("kind")
        if kind == "llama":
            m["dtype"] = jnp.dtype(m.get("dtype", "float32")).type
            return LlamaConfig(**m)
        if kind == "moe":
            from triton_dist_tpu.models.moe import MoEConfig
            base = dict(m.pop("base"))
            base["dtype"] = jnp.dtype(base.get("dtype", "float32")).type
            return MoEConfig(base=LlamaConfig(**base), **m)
        raise ValueError(f"unknown model kind {kind!r}")

    def init_params(self) -> dict:
        cfg = self.model_config()
        key = jax.random.PRNGKey(self.seed)
        if self.model["kind"] == "moe":
            from triton_dist_tpu.models.moe import init_moe_params
            return init_moe_params(key, cfg)
        from triton_dist_tpu.models.llama import init_params
        return init_params(key, cfg)


def engine_artifact_key(kind: str, mesh: Optional[dict] = None) -> str:
    """Canonical program-set key for one engine declaration — the string
    the engines themselves derive at seed time."""
    if kind in ("colocated", "disagg"):
        return kind
    mesh = mesh or {}
    desc = f"{mesh.get('tp', 1)}x{mesh.get('sp', 1)}x{mesh.get('ep', 1)}"
    return f"{kind}:{desc}"


def make_engine(decl: dict, params: dict, cfg, journal=None,
                artifact: "ServingArtifact | None" = None, **overrides):
    """Construct the engine a spec entry declares. Shared by the artifact
    builder, ``tools/compile_aot.py``, the sims' ``--artifact`` restart
    path, and the tests — one decl, one construction rule."""
    decl = dict(decl)
    kind = decl.pop("kind")
    decl.pop("probe", None)
    mesh = decl.pop("mesh", None)
    decl.update(overrides)
    if kind == "colocated":
        from triton_dist_tpu.serving.engine import ServingEngine
        return ServingEngine(params, cfg, journal=journal,
                             artifact=artifact, **decl)
    if kind == "sharded":
        from triton_dist_tpu.serving.sharded import (ShardedServingEngine,
                                                     serving_mesh)
        mesh = mesh or {}
        ctx = serving_mesh(**mesh)
        return ShardedServingEngine(params, cfg, ctx, journal=journal,
                                    artifact=artifact, **decl)
    if kind == "disagg":
        from triton_dist_tpu.serving.disagg import DisaggServingEngine
        return DisaggServingEngine(params, cfg, journal=journal,
                                   artifact=artifact, **decl)
    if kind == "disagg_sharded":
        from triton_dist_tpu.serving.compose import DisaggShardedEngine
        from triton_dist_tpu.serving.sharded import serving_mesh
        mesh = mesh or {}
        ctx = serving_mesh(**mesh)
        return DisaggShardedEngine(params, cfg, ctx, journal=journal,
                                   artifact=artifact, **decl)
    raise ValueError(f"unknown engine kind {kind!r}")


# -- build: signature recording ---------------------------------------------

def _aval_of(x, mesh=None):
    """Dispatch-time aval: shape/dtype plus the committed sharding when one
    exists. Uncommitted args on a multi-device engine are pinned replicated
    (that is how GSPMD places them in the source program too)."""
    sharding = None
    if isinstance(x, jax.Array) and getattr(x, "_committed", False):
        sharding = x.sharding
    if sharding is None and mesh is not None:
        sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
    if sharding is None:
        return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype
                                    if not hasattr(x, "dtype") else x.dtype)
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


class _Recorder:
    """Wraps one engine jit object during the artifact build: the first
    dispatch records the exact argument avals (committed shardings
    included) that the export and the load-path rehearsal then reuse."""

    def __init__(self, fn, mesh=None):
        self._fn = fn
        self._mesh = mesh
        self.avals: Optional[tuple] = None

    def __call__(self, *args):
        if self.avals is None:
            self.avals = jax.tree_util.tree_map(
                lambda a: _aval_of(a, self._mesh), args)
        return self._fn(*args)

    def __getattr__(self, name):
        return getattr(self._fn, name)


def _instrument(engine) -> Dict[str, _Recorder]:
    """Swap every program attribute the engine dispatches through for a
    recorder. Returns program-name → recorder (avals filled once the probe
    workload has exercised the program)."""
    mesh = getattr(getattr(engine, "ctx", None), "mesh", None)
    recs: Dict[str, _Recorder] = {}

    def wrap(obj, attr, name):
        fn = getattr(obj, attr, None)
        if fn is None:
            return
        recs[name] = _Recorder(fn, mesh)
        setattr(obj, attr, recs[name])

    from triton_dist_tpu.serving.compose import DisaggShardedEngine
    from triton_dist_tpu.serving.disagg import DisaggServingEngine
    if isinstance(engine, DisaggShardedEngine):
        wrap(engine.decode, "_step", "decode")
        wrap(engine.decode, "_chunk_step", "chunk")
        wrap(engine, "_xmig", "xmig")
        # the migration channel launch closure captured self._xmig before
        # instrumentation — rebind it through the recorder
        return recs
    if isinstance(engine, DisaggServingEngine):
        wrap(engine, "_dec_step", "decode")
        wrap(engine, "_chunk_step", "chunk")
        wrap(engine, "_migrate", "migrate")
        engine.channel._launch = recs["migrate"]
        return recs

    wrap(engine, "_step", "decode")
    wrap(engine, "_chunk_step", "chunk")
    return recs


def _drive(engine, prompts: List[List[int]], max_new: int = 2,
           max_steps: int = 600) -> None:
    """Probe workload: run every prompt to completion so each program the
    engine owns dispatches at least once (prefill chunk, decode, and —
    on the disagg engines — the migration kernel)."""
    for p in prompts:
        engine.submit(p, max_new)
    steps = 0
    while len(engine._finished) < len(prompts):
        engine.step()
        steps += 1
        assert steps < max_steps, (
            "artifact probe workload did not finish: engine stalled "
            f"after {steps} steps ({len(engine._finished)}/{len(prompts)})")


def _probe_prompts(decl: dict, chunk: int) -> List[List[int]]:
    """The declaration's own ``probe`` prompts, else one prompt that spans
    a chunk boundary: the chunk program and the decode program are the
    whole set, and one such request dispatches both."""
    if decl.get("probe"):
        return [list(p) for p in decl["probe"]]
    return [[(i % 30) + 1 for i in range(chunk + 3)]]


# -- build -------------------------------------------------------------------

def build_artifact(spec: ArtifactSpec, out_dir: str,
                   params: Optional[dict] = None,
                   registry: Optional[TunedConfigRegistry] = None,
                   log: Callable[[str], None] = lambda s: None) -> str:
    """Compile the spec's full program set and persist it under
    ``out_dir``. Returns ``out_dir``. The build pays every fresh trace so
    no cold start ever does."""
    cfg = spec.model_config()
    if params is None:
        params = spec.init_params()
    os.makedirs(os.path.join(out_dir, _PROGRAMS), exist_ok=True)
    # The artifact bundles an XLA cache so a cold process finds its
    # executables ready: the build re-points jax at that directory and
    # rehearses the load path into it. Where the environment PLACED the
    # cache (JAX_COMPILATION_CACHE_DIR), the directory is left alone: no
    # re-pointing, no rehearsal, no bundled cache — a cold load then
    # compiles the StableHLO once, into the environment's cache.
    bundle_cache = not os.environ.get("JAX_COMPILATION_CACHE_DIR")
    old_cache = jax.config.jax_compilation_cache_dir
    old_floor = jax.config.jax_persistent_cache_min_compile_time_secs
    if bundle_cache:
        cache_dir = os.path.join(out_dir, _XLA_CACHE)
        os.makedirs(cache_dir, exist_ok=True)
        # the artifact's cache must hold EVERY load-path executable — drop
        # the min-compile-time floor for the build's duration
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        _reset_xla_cache()

    from jax import export as jax_export
    programs: Dict[str, Dict[str, dict]] = {}
    try:
        for decl in spec.engines:
            ekey = engine_artifact_key(decl["kind"], decl.get("mesh"))
            log(f"[aot] building {ekey}")
            engine = make_engine(decl, params, cfg)
            recs = _instrument(engine)
            _drive(engine, _probe_prompts(decl, engine.prefill_chunk))
            programs[ekey] = {}
            for name, rec in sorted(recs.items()):
                assert rec.avals is not None, (
                    f"probe workload never dispatched program {name!r} of "
                    f"{ekey} — widen the probe (see ArtifactSpec docs)")
                exp = jax_export.export(rec._fn)(*rec.avals)
                data = exp.serialize()
                fname = f"{ekey.replace(':', '_')}--{name}.stablehlo"
                with open(os.path.join(out_dir, _PROGRAMS, fname),
                          "wb") as f:
                    f.write(data)
                if bundle_cache:
                    # rehearse the LOAD path so its XLA compile lands in
                    # the artifact cache: deserialize + jit(call) + lower/
                    # compile is byte-for-byte what a cold process will do
                    g = jax_export.deserialize(data)
                    jax.jit(g.call).lower(*rec.avals).compile()
                programs[ekey][name] = {
                    "file": f"{_PROGRAMS}/{fname}",
                    "digest": f"{_fnv1a_bytes(data):08x}",
                    "nr_devices": exp.nr_devices,
                }
                log(f"[aot]   {name}: {len(data)} bytes, "
                    f"{exp.nr_devices} device(s)")
    finally:
        if bundle_cache:
            jax.config.update("jax_compilation_cache_dir", old_cache)
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              old_floor)
            _reset_xla_cache()

    if registry is not None:
        registry.save(os.path.join(out_dir, _REGISTRY))

    manifest = {
        "format": FORMAT_VERSION,
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "spec": spec.to_json(),
        "spec_digest": spec.digest(),
        "programs": programs,
    }
    manifest["digest"] = _canon_digest(
        {k: v for k, v in manifest.items() if k != "digest"})
    tmp = os.path.join(out_dir, _MANIFEST + ".tmp")
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
    os.replace(tmp, os.path.join(out_dir, _MANIFEST))
    return out_dir


# -- load --------------------------------------------------------------------

def _reset_xla_cache() -> None:
    """Re-initialize jax's persistent-cache singleton: it binds its
    directory at FIRST use and silently ignores later config updates — a
    process that compiled anything before the artifact dir was installed
    would otherwise never read (or write) a single artifact entry."""
    try:
        from jax._src import compilation_cache as _cc
        _cc.reset_cache()
    except Exception:
        pass      # private API moved — stale-cache-dir is a perf miss only


def _install_xla_cache(artifact_cache: str) -> None:
    """Make the artifact's persisted executables visible to this process:
    copy entries into the active compilation-cache dir when one is
    configured (by ``JAX_COMPILATION_CACHE_DIR``, an entry point's
    ``configure_compile_cache``, or the tests' per-suite temp cache) —
    that directory is never replaced — else point the process at the
    artifact's own cache directory."""
    if not os.path.isdir(artifact_cache):
        return
    active = jax.config.jax_compilation_cache_dir
    if active is None or active == "":
        jax.config.update("jax_compilation_cache_dir", artifact_cache)
        _reset_xla_cache()
        return
    if os.path.abspath(active) == os.path.abspath(artifact_cache):
        return
    os.makedirs(active, exist_ok=True)
    for fname in os.listdir(artifact_cache):
        dst = os.path.join(active, fname)
        if not os.path.exists(dst):
            shutil.copy2(os.path.join(artifact_cache, fname), dst)


class ServingArtifact:
    """A loaded artifact directory: validated manifest + lazy per-program
    deserialization. Engines pull their program set out of this handle at
    construction (``artifact=`` kwarg) instead of tracing."""

    def __init__(self, path: str, manifest: dict,
                 registry: Optional[TunedConfigRegistry]):
        self.path = path
        self.manifest = manifest
        self.registry = registry
        self._loaded: Dict[Tuple[str, str], LoadedProgram] = {}

    # -- keyed load -------------------------------------------------------
    @classmethod
    def load(cls, path: str,
             spec: Optional[ArtifactSpec] = None) -> "ServingArtifact":
        mpath = os.path.join(path, _MANIFEST)
        if not os.path.isfile(mpath):
            raise ArtifactMissError(
                f"no artifact manifest at {mpath} — build one with "
                f"tools/compile_aot.py")
        with open(mpath, encoding="utf-8") as f:
            manifest = json.load(f)
        body = {k: v for k, v in manifest.items() if k != "digest"}
        if _canon_digest(body) != manifest.get("digest"):
            raise ArtifactIntegrityError(
                f"artifact manifest at {mpath} is torn or tampered: "
                f"digest mismatch")
        if manifest.get("format") != FORMAT_VERSION:
            raise ArtifactMissError(
                f"artifact format {manifest.get('format')!r} != "
                f"{FORMAT_VERSION}")
        misses = []
        if manifest["jax"] != jax.__version__:
            misses.append(f"jax {manifest['jax']} != {jax.__version__}")
        if manifest["backend"] != jax.default_backend():
            misses.append(f"backend {manifest['backend']!r} != "
                          f"{jax.default_backend()!r}")
        if manifest["device_count"] > jax.device_count():
            misses.append(f"topology: built for {manifest['device_count']} "
                          f"devices, process has {jax.device_count()}")
        if spec is not None and spec.digest() != manifest["spec_digest"]:
            misses.append(f"spec digest {manifest['spec_digest']} != "
                          f"requested {spec.digest()}")
        if misses:
            raise ArtifactMissError(
                "stale artifact at " + path + ": " + "; ".join(misses))
        registry = None
        rpath = os.path.join(path, _REGISTRY)
        if os.path.isfile(rpath):
            registry = TunedConfigRegistry.load(rpath)
        _install_xla_cache(os.path.join(path, _XLA_CACHE))
        return cls(path, manifest, registry)

    @property
    def spec(self) -> ArtifactSpec:
        return ArtifactSpec.from_json(self.manifest["spec"])

    def engine_keys(self) -> List[str]:
        return sorted(self.manifest["programs"].keys())

    def program_names(self, ekey: str) -> List[str]:
        return sorted(self.manifest["programs"].get(ekey, {}).keys())

    def program(self, ekey: str, name: str) -> LoadedProgram:
        """Deserialize (once) and return the program; a missing key is a
        typed loud miss, never a silent fresh trace."""
        if (ekey, name) in self._loaded:
            return self._loaded[(ekey, name)]
        entry = self.manifest["programs"].get(ekey, {}).get(name)
        if entry is None:
            have = {k: self.program_names(k) for k in self.engine_keys()}
            raise ArtifactMissError(
                f"artifact at {self.path} holds no program "
                f"{ekey!r}/{name!r}; available: {have}")
        with open(os.path.join(self.path, entry["file"]), "rb") as f:
            data = f.read()
        if f"{_fnv1a_bytes(data):08x}" != entry["digest"]:
            raise ArtifactIntegrityError(
                f"program {ekey}/{name} at {entry['file']} is torn or "
                f"tampered: digest mismatch")
        from jax import export as jax_export
        prog = LoadedProgram(f"{ekey}/{name}", jax_export.deserialize(data))
        self._loaded[(ekey, name)] = prog
        return prog


def load_artifact(path: str,
                  spec: Optional[ArtifactSpec] = None) -> ServingArtifact:
    """Module-level convenience mirroring :meth:`ServingArtifact.load`."""
    return ServingArtifact.load(path, spec=spec)


__all__ = ["ArtifactSpec", "ServingArtifact", "LoadedProgram",
           "ArtifactMissError", "ArtifactIntegrityError", "build_artifact",
           "load_artifact", "make_engine", "engine_artifact_key"]
