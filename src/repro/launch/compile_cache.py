"""Where the persistent XLA compilation cache lives for the entry points.

Called by the launchers and ``chip_smoke.py`` before their first compile,
never at library import: importing ``repro`` leaves JAX's configuration
alone.
"""
from __future__ import annotations

import os

import jax

# the repository root: src/repro/launch/ -> three levels up
REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return it.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: it is
    left to JAX and nothing is set here. Otherwise the cache goes to
    ``<repo>/.jax_cache`` — a fixed path, since the path is part of the
    cache key and a moving directory never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
