"""Where JAX keeps its persistent compilation cache.

A chip run compiles every kernel cold unless the compiled programs of an
earlier process are found again.  :func:`enable` places the cache before
anything compiles; the entry points (``python -m repro``,
``python -m repro.serve``, ``chip_smoke.py``) call it first thing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
  else is set in code.
* Otherwise: :data:`CACHE_DIR`, a fixed ``.jax_cache/`` at the checkout
  root (listed in ``.gitignore``).  The path is part of the cache key, so
  it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the default cache directory: ``<checkout>/.jax_cache``
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory (module
    docstring) and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
