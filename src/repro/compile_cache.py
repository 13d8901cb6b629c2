"""JAX's persistent compilation cache for the repo's entry points.

One rule, shared by ``chip_smoke.py``, ``examples/quickstart.py``,
``python -m repro.serve`` and ``benchmarks/run.py``:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and no other
  directory is set in code;
* otherwise the cache lives at ``<checkout>/.jax_cache`` (git-ignored).

The path is part of what makes a cache entry findable again, so it is
fixed — never built from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
