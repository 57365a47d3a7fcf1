"""JAX's persistent compilation cache, shared by every launcher.

The cache key covers the program and the backend, not the directory, but a
directory that moves between runs is never found again.  So the cache lives
where ``$JAX_COMPILATION_CACHE_DIR`` says when that is set, and otherwise at
one fixed path inside the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
