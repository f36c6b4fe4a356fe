"""One place that decides where JAX's persistent compilation cache lives.

A sealed accelerator run compiles every program it touches, and the cache
directory is part of how a later process finds those programs again, so it
must not move between processes. The rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it at import; this
  module touches nothing.
- otherwise: ``<checkout>/.jax_cache`` (ignored by git), with no minimum
  compile time, so the many small GAME programs are kept too.

Entry points call :func:`configure_compile_cache` first in ``main()``,
before anything compiles.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX at the repo's cache unless the environment already named
    one; returns the directory in use."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_CACHE_DIR
