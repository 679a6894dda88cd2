"""JAX's persistent compilation cache for this repo's entry points.

``enable_compile_cache()`` is called from the ``main`` of ``chip_smoke.py``,
``repro.launch.serve`` and the benchmark scripts — never at import, so
tests and library users keep JAX's own defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

# a fixed path inside the checkout (gitignored): the cache directory is
# part of what a later run must find again
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout. Call before the first compilation.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
