"""Where JAX keeps its persistent compilation cache for a run on the chip.

A function, called at the start of a run and never on import, so that
importing this module changes no JAX state.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Tuple

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# Fixed and inside the checkout: the directory is part of the cache's key,
# so a path that moved between runs (a tempdir, a pid, a time) never hits.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> Tuple[str, bool]:
    """Place the cache and return `(directory, came_from_environment)`.

    When `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to `<repo>/.jax_cache`."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env, True
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR), False
