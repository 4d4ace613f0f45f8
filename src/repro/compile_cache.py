"""JAX's persistent compilation cache, kept at one fixed path.

A cached program is keyed partly by the cache directory, so a directory
that moves between runs never hits.  ``enable_compile_cache`` therefore
picks exactly one place:

  * ``$JAX_COMPILATION_CACHE_DIR`` when it is set — JAX reads that
    variable itself, and this helper sets no other path;
  * otherwise ``<checkout>/.jax_cache`` (git-ignored), never a name built
    from a temp directory, a pid or the time.

Entry points call it once at start-up (``chip_smoke.py``, the examples and
the benchmark drivers); library code and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache and return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
