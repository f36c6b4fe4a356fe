"""CG steps a TRON fit took, each one Hessian-vector pass
(``OptimizationResult.objective_passes`` less the outer iterations and the
pass at ``w = 0``, by ``optim/tron.py``'s own count; layer: optimizers)."""

from benchmark.readers import ratio


def read(obs):
    return ratio(obs, "optim.cg_steps", "work")
