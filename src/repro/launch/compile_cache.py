"""Where JAX keeps its persistent compilation cache.

A cold compile of a 32-layer prefill program at published widths takes
tens of seconds; the persistent cache lets the next process on the same
machine load it instead.  The cache key includes the directory, so the
directory must not move between runs.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# fixed, inside the checkout (listed in .gitignore)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Place the persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing.  Otherwise the cache goes to ``<repo>/.jax_cache``.
    Call before the first compile.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
