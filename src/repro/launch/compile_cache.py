"""JAX's persistent compilation cache, set the same way by every entry point.

A call on a fresh machine starts with no compiled code, and the fused round
program takes tens of seconds to compile.  The cache lets the processes of
one run — and later runs on the same disk — reuse what an earlier process
compiled.  The directory is part of what makes an entry findable again, so
it is fixed: never built from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: where the cache lives unless JAX_COMPILATION_CACHE_DIR says otherwise —
#: inside the checkout, git-ignored
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing; otherwise the cache goes to ``REPO_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
