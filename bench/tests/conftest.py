import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


@pytest.fixture(scope="session", autouse=True)
def no_compile_cache():
    """Runs here never write the persistent compile cache (the harness
    turns it on for the chip)."""
    import jax
    jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    from bench.tests import tiny
    return tiny.make(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture
def cpu_peaks(monkeypatch):
    """The CPU stands in for the chip in these runs: borrow the v5e row so
    the per-layer arithmetic runs (no CPU number is reported as a device
    metric anywhere)."""
    from bench.lib import peaks
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks._V5E))
