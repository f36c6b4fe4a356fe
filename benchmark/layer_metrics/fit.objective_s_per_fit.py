"""Device seconds per fit inside the GLM objective, whichever kernel or XLA
path serves a pass and whatever relayout XLA hangs on it: self time of the
operations under the program's ``glm.objective`` stage on the first device
(profiler trace, ``tf_op``; layer: glm_objective)."""

from benchmark import stages


def read(obs):
    return stages.part(obs, stages.FIT, "glm.objective")
