import os
import sys

# Tests run on the single real CPU device (the dry-run, and only the dry-run,
# forces 512 placeholder devices — see src/repro/launch/dryrun.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import pytest


@pytest.fixture
def device_gather_on_cpu(monkeypatch):
    """Lets the SPMD executor's resident gather engage on the CPU backend,
    which keeps host staging otherwise, for the tests of that path."""
    from repro.api import executors

    monkeypatch.setattr(executors, "HOST_MEMORY_PLATFORMS", frozenset())
