"""Device seconds per fit in Hessian-vector passes: self time of the
operations under the program's ``glm.hvp`` stage (inside ``glm.objective``)
on the first device (profiler trace, ``tf_op``; layer: glm_objective). None
where the program does not name the stage."""

from benchmark import tron_parts


def read(obs):
    return tron_parts.hvp_seconds_per_fit(obs)
