"""Device seconds per fit in the tile-COO tail: self time of the operations
under the program's ``glm.tail`` stage (inside ``glm.objective``: the
kernels' custom calls and what XLA hangs on them) on the first device
(profiler trace, ``tf_op``; layer: glm_objective). None where the program
does not name the stage."""

from benchmark import glm_parts


def read(obs):
    return glm_parts.seconds_per_fit(obs, "glm.tail")
