import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

FIXTURES = Path(__file__).resolve().parent / "fixtures"


@pytest.fixture(autouse=True)
def _no_persistent_compile_cache(monkeypatch, tmp_path):
    """A harness run in a test keeps the process's JAX configuration as it
    is, and writes its dataset under the test's own directory."""
    from bench import dataset
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(dataset, "CACHE", tmp_path / "datasets")
