"""Objective passes of the fixed-effect solve per outer iteration
(``OptimizationResult.objective_passes`` of its trackers; layer:
optimizers)."""

from benchmark.readers import ratio


def read(obs):
    return ratio(obs, "optim.objective_passes", "work")
