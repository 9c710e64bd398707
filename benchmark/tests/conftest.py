"""The benchmark's own tests (CPU; not part of tier-1):

    python3 -m pytest benchmark/tests -q            # seconds: arithmetic, generator, manifest, trace
    python3 -m pytest benchmark/tests -q -m slow    # + the CPU walk-through of run.py (minutes)
"""
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
REHEARSAL = os.path.join(ROOT, "benchmark", "tests", "rehearsal", "BENCHMARK.json")


def pytest_collection_modifyitems(config, items):
    """``slow`` tests run only when asked for by ``-m``."""
    if "slow" in (config.getoption("-m") or ""):
        return
    skip = pytest.mark.skip(reason="slow: run with -m slow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
