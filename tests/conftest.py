import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(scope="session")
def engine():
    """Shared unsharded SweepEngine: session-scoped so the jitted sweep
    kernels compile once and parity tests reuse one warm LRU instead of
    re-evaluating identical (GEMM, config) pairs per test."""
    from repro.core.sweep import SweepEngine
    return SweepEngine(mesh=None)
