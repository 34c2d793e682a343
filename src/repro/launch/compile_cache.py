"""One place that decides where JAX's persistent compilation cache lives.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing here
overrides it.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path, never built from a temp name, a PID or the time, because the
path is part of what a later process must match to hit the cache.
``.gitignore`` lists the directory.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
