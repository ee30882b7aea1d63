"""Persistent XLA compilation cache for the entry points.

A full-width training step compiles for tens of seconds on a TPU; the
persistent cache lets the next process (the next smoke run, the next CLI
invocation) load it instead.  The cache key includes the directory, so the
directory must not move between runs: it is ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it (JAX reads that variable itself, and nothing
here overrides it), and otherwise the fixed ``.jax_cache`` directory at the
root of the checkout (git-ignored).

Entry points (``repro.launch.train``, ``repro.launch.serve``,
``chip_smoke.py``) call :func:`enable_compile_cache` before their first
compile; library code and tests never do.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
