"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` at the start of ``main()``;
importing this module changes nothing. The cache directory is part of a
cached program's key, so it must not move between runs: a temp dir, a pid
or a time in the path would never hit.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <repo>/.jax_cache: fixed, inside the checkout (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here; otherwise the cache goes to the fixed
    ``DEFAULT_DIR``.
    """
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
