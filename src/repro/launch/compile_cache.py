"""JAX's persistent compilation cache for the entry points that run on a chip.

``chip_smoke.py`` and the ``main()`` of ``serve``, ``loadgen`` and ``train``
call :func:`enable_compile_cache` once, at start-up — never at import, so
importing the library changes no JAX setting.
"""

from __future__ import annotations

import os

import jax

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout (git ignores it): a fixed path, because the
    cache only hits when a later process names the same directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
