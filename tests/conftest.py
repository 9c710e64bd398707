"""Test bootstrap: force an 8-device virtual CPU mesh.

The distributed kernels run in Pallas TPU interpret mode on CPU devices —
this is the single-process cluster simulator the reference lacks (its tests
need real GPUs + torchrun; see SURVEY.md §4).

The suite always runs on the CPU simulator: jax is re-pointed at the
virtual CPU platform (dropping any cached backend) before any test imports
run, whatever the environment's default platform is.
"""

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

import jax  # noqa: E402

from triton_dist_tpu.utils.env import force_virtual_cpu_devices  # noqa: E402

_N_DEVICES = int(os.environ.get("TDT_TEST_DEVICES", "12"))
force_virtual_cpu_devices(_N_DEVICES)

# Per-run XLA compile cache: many tests build fresh engines/kernels whose
# programs lower to byte-identical HLO (each engine owns its own jax.jit
# objects, so the trace-level cache cannot share them). A content-keyed
# persistent cache dedupes those XLA compiles within one suite run — it
# does NOT affect the compile-count guards, which count trace-cache
# entries, not XLA compiles. Fresh temp dir per run: nothing persists
# across runs, so the first run's numbers are every run's numbers. The
# 0.3 s threshold keeps the flood of tiny eager-op compiles out of the
# cache (caching those costs more in serialization than it saves).
import tempfile  # noqa: E402

_cache_dir = tempfile.mkdtemp(prefix="tdt_xla_cache_")
jax.config.update("jax_compilation_cache_dir", _cache_dir)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)

assert jax.device_count() == _N_DEVICES, (
    f"expected {_N_DEVICES} virtual CPU devices, got {jax.devices()}"
)

# Most tests use a 4-way mesh for speed; TEST_WORLD_WIDE exercises the
# driver's exact 8-way configuration (tests/test_eight_way.py, and the
# full-participation 8-of-8 sweep in test_full_participation.py via
# TDT_TEST_DEVICES=8). The default keeps 12 devices so the wide tests also
# cover the participants-<-devices subset shape users hit on real pods.
TEST_WORLD = 4
TEST_WORLD_WIDE = 8
