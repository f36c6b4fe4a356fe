"""Objective passes a fit took, as the optimizer counts them
(``OptimizationResult.objective_passes``; layer: optimizers)."""

from benchmark.readers import ratio


def read(obs):
    return ratio(obs, "optim.objective_passes", "work")
