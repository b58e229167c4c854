"""Where JAX keeps its persistent compilation cache.

Entry points (the launchers, the examples, ``chip_smoke.py``) call
:func:`use_compile_cache` once before they compile anything. Importing
:mod:`repro` does not, so the tests never write a cache.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing is changed.
* Otherwise the cache sits at ``<checkout>/.jax_cache`` (git-ignored). The
  path is fixed on purpose: it is part of the cache key, so a directory built
  from a temp name, a pid or the time would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
