"""Where compiled executables persist between processes.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: the cache
stays there and no other directory is set in code.  Otherwise it lives
at a fixed directory inside the checkout, ``<repo>/.jax_cache``
(gitignored).  The path must not change between runs — no temp name, pid
or timestamp — or a later process never finds what an earlier one
compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    REPO_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
