"""Where compiled programs (and the autotune registry) persist.

One rule, shared by every script that runs on a chip: if
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
is set in code; otherwise the cache lives at ``<checkout>/.jax_cache``
— a fixed path (the path is part of the cache key, so a directory that
moves never hits).  Never called at ``import deepspeed_tpu`` and never
by the tests: entry-point scripts call ``enable_compile_cache()`` before
their first compile.
"""
from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on at ``cache_dir()``;
    returns the directory."""
    if not os.environ.get(_ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    return cache_dir()


__all__ = ["cache_dir", "enable_compile_cache"]
