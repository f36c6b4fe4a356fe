"""Seconds of set-up spent retrieving executables from the persistent
compile cache under the program's spans (``jax.cache_load_s``): a part of
``setup.compile_s``, which times compile or load (layer: compile)."""

from benchmark import host_spans


def read(obs):
    return host_spans.setup_compile_steps("CACHE_LOAD_TIMER")
