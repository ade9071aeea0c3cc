"""JAX's persistent compilation cache for the repository's entry points.

Called from ``main()`` of each entry point, never at import, so tests and
library users keep whatever cache setting they have.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it as its own
    setting, and it stands.  Otherwise the cache is ``<repo>/.jax_cache``,
    a fixed path, so each run of a checkout finds what the last one
    compiled."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
