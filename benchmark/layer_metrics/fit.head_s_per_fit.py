"""Device seconds per fit in the dense head's multiply-reduces: self time of
the operations under the program's ``glm.head`` stage (inside
``glm.objective``) on the first device (profiler trace, ``tf_op``; layer:
glm_objective). None where the program does not name the stage."""

from benchmark import glm_parts


def read(obs):
    return glm_parts.seconds_per_fit(obs, "glm.head")
