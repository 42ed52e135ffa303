"""JAX's persistent compilation cache, placed from outside or at one fixed
path.

The cache directory is part of the cache key's environment: a directory that
moves never hits. So there is exactly one rule, applied where an engine is
built (``TpuEngine.build``) and by scripts that jit before that:

- ``JAX_COMPILATION_CACHE_DIR`` set → JAX already reads it; set nothing.
- unset → ``<checkout>/.jax_cache``, derived from this package's own
  location (git-ignored). Never a temp name, a pid or a time.

``tests/conftest.py`` keeps the cache off for tests.
"""

from __future__ import annotations

import os

import jax

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), ".jax_cache"
)


def enable_compile_cache() -> str:
    """Point JAX at the persistent cache (idempotent) and return its
    directory."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    if jax.config.jax_compilation_cache_dir != _DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return _DEFAULT_DIR
