"""Device seconds per fit in neither ``glm.objective`` nor a ``lbfgs.*``
stage, on the first device (profiler trace, ``tf_op``; layer: device).
With the two stage metrics it adds up to the first device's busy seconds
per fit."""

from benchmark import stages


def read(obs):
    return stages.part(obs, stages.FIT, stages.UNSTAGED)
