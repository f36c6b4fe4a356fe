"""Seconds of backend compilation (or of loading from the persistent
cache) in set-up, from ``jax.monitoring`` (layer: compile)."""

from benchmark.readers import counter


def read(obs):
    return counter(obs, "setup.compile_s")
