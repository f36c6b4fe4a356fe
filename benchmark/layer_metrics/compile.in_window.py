"""Programs compiled inside the window, from ``jax.monitoring``; must be 0
(layer: compile)."""

from benchmark.readers import counter


def read(obs):
    return counter(obs, "compile.in_window")
