"""Device seconds per fit in neither ``glm.objective`` nor a ``tron.*``
stage, on the first device (profiler trace, ``tf_op``; layer: device). With
``fit.objective_s_per_fit`` and ``fit.cg_s_per_fit`` it adds up to the first
device's busy seconds per fit."""

from benchmark import stages, tron_parts


def read(obs):
    return tron_parts.seconds_per_fit(obs, stages.UNSTAGED)
