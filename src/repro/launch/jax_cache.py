"""Where JAX keeps its persistent compilation cache.

Called from the ``__main__`` blocks of the drivers and from
``chip_smoke.py`` — never at import and never from ``main()``, so tests
that call ``main()`` leave JAX's configuration alone.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing else is set here. Otherwise the cache goes to the fixed
    ``<repo>/.jax_cache``: a directory that moved between runs would
    never be hit again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
