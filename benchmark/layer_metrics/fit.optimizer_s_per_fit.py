"""Device seconds per fit of the optimiser's own arithmetic (two-loop
recursion, line search, ring-buffer update): self time of the operations
under a ``lbfgs.*`` stage and outside ``glm.objective``, on the first
device (profiler trace, ``tf_op``; layer: optimizers)."""

from benchmark import stages


def read(obs):
    return stages.part(obs, stages.FIT, "lbfgs.")
