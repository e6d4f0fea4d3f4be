"""Where JAX keeps its persistent compilation cache.

Called by the entry points (``chip_smoke.py``, ``repro.launch.serve``)
before their first compile — never at import of a library module, so
tests and library users keep whatever cache policy they set.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["use_compile_cache", "REPO_CACHE_DIR"]

#: The fixed in-checkout default (listed in ``.gitignore``).  The path
#: is part of the cache key, so it never depends on a temp name, a pid
#: or the time: a second run from the same checkout hits.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set.  Otherwise the cache goes to
    :data:`REPO_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
