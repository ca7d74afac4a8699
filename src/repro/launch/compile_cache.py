"""Where entry points keep JAX's persistent compilation cache.

Entry points (``chip_smoke.py``) call :func:`enable_compile_cache` before
their first compile; importing the library sets nothing.
"""
from __future__ import annotations

import os

__all__ = ["enable_compile_cache"]


def enable_compile_cache(checkout_root: str) -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and nothing is set in code
    (JAX reads the variable itself).  Otherwise the cache is
    ``<checkout_root>/.jax_cache``: a fixed path, because a cache whose
    directory moves between runs never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(os.path.abspath(checkout_root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
