"""Device probe and the persistent compile cache.

``on_tpu`` decides every kernel dispatch (``impl="auto"``): the compiled
Pallas kernel on a TPU, the jnp reference elsewhere. It lets a broken
device probe raise — a backend that fails to initialize must never read as
"not a TPU" and quietly move the served path to the reference code.

``use_compile_cache`` places JAX's persistent compilation cache. Entry
points call it before their first compile: when ``JAX_COMPILATION_CACHE_DIR``
is set, JAX already reads it and nothing is set here; otherwise the cache
lives at the fixed ``<repo>/.jax_cache`` (the directory is part of the
cache key, so it must not move between runs).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
COMPILE_CACHE_DIR = REPO_ROOT / ".jax_cache"


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
