"""Where JAX's persistent compilation cache lives, decided in one place.

``chip_smoke.py``, ``bench.py``'s phases and the bench scripts call
:func:`use_compile_cache` once, before their first jit.  The path is
part of the cache's key, so it must never move: a directory named after
a pid, a time or ``tempfile`` never hits.
"""

from __future__ import annotations

import os

__all__ = ["use_compile_cache"]

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it, and nothing
    is set in code.  Unset: ``<checkout>/.jax_cache`` (git-ignored)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
