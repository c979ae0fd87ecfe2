"""Persistent compilation cache placement for the entry points.

JAX keys a cached executable by, among other things, the cache directory, so
the directory must not move between runs: never derive it from a temp name,
a pid or the time.  Call :func:`place_compile_cache` from an entry point's
``main`` — never at import, where it would change the process-global JAX
config of whoever imports the package."""

from __future__ import annotations

import os

import jax

__all__ = ["place_compile_cache"]


def place_compile_cache(checkout: str) -> str:
    """Return the directory JAX's persistent compilation cache uses.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed; otherwise the cache goes to ``<checkout>/.jax_cache``
    (listed in the repository's ``.gitignore``)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
