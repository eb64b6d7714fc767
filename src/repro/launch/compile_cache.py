"""JAX's persistent compilation cache, placed from outside the program.

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory, and no other
is set.  Otherwise the cache lives at
one fixed path inside the checkout, ``<repo>/.jax_cache`` — fixed because
the path is part of every entry's key, so a moving directory never hits.
Every compile is cached, however short: the executed path's super-steps
compile in well under JAX's default one-second threshold.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory.  Call before the
    first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
