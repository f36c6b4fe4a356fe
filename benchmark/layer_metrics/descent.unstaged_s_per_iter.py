"""Device seconds per outer iteration in none of ``visit.fixed``,
``re.offsets``, ``re.solve``, ``re.score``: the unstacking program, the
tiny programs between launches, total updates, and what XLA left without
an ``op_name`` (profiler trace, ``tf_op``; layer: game_descent). With the
four stage metrics it adds up to ``descent.device_busy_s_per_iter``."""

from benchmark import stages


def read(obs):
    return stages.part(obs, stages.DESCENT, stages.UNSTAGED)
