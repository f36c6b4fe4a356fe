"""One place that decides where JAX's persistent compilation cache lives.

A sealed accelerator run compiles every program it touches, and the cache
directory is part of how a later process finds those programs again, so it
must not move between processes. The rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it at import; this
  module sets no directory.
- otherwise: ``<checkout>/.jax_cache`` (ignored by git), with no minimum
  compile time, so the many small GAME programs are kept too.

Either way the cache's keys carry the version of the program's stage names
(:func:`_key_by_stage_names`).

Entry points call :func:`configure_compile_cache` first in ``main()``,
before anything compiles.
"""

from __future__ import annotations

import os
import warnings

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def _key_by_stage_names() -> None:
    """Hash the version of the stage names (``obs/stages.py``) into every
    cache key, so that an executable compiled before a scope moved is not
    served, under its old names, to the profile of a program that differs
    from it in metadata alone. The hook is JAX's own, but private."""
    from jax._src import cache_key

    from photon_ml_tpu.obs.stages import VERSION

    if not callable(getattr(cache_key, "custom_hook", None)):
        warnings.warn(
            "this JAX has no cache_key.custom_hook: executables cached "
            "before obs/stages.py changed keep their old stage names"
        )
        return
    cache_key.custom_hook = lambda: f"photon_ml_tpu.obs.stages/{VERSION}"


def configure_compile_cache() -> str:
    """Point JAX at the repo's cache unless the environment already named
    one; returns the directory in use."""
    _key_by_stage_names()
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_CACHE_DIR
