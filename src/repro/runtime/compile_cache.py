"""Where the persistent XLA compilation cache lives.

Entry points that compile for a device (``repro.launch.serve_kernel``) call
``enable_compile_cache()`` before their first compile:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, nothing is set here;
- otherwise the cache goes to ``<checkout>/.jax_cache``, a fixed path (the
  path is part of the cache key, so a moving directory would never hit).
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout root: src/repro/runtime/ is three levels below it
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
CACHE_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
