"""Device seconds per outer iteration of everything bucket-shaped in the
random effects (lane extraction, the vmapped solves, the scatter back):
self time of the operations under the program's ``re.solve`` stage
(profiler trace, ``tf_op``; layer: random_effects)."""

from benchmark import stages


def read(obs):
    return stages.part(obs, stages.DESCENT, "re.solve")
