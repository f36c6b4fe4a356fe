"""Device seconds per outer iteration of the fixed effect's whole visit
(offsets, solve, score, new total): self time of the operations under the
program's ``visit.fixed`` stage (profiler trace, ``tf_op``; layer:
game_descent). The same visit on the same rows in ``ml20m_descent`` and
``ml20m_fixed_only``: the two cells cross-check each other."""

from benchmark import stages


def read(obs):
    return stages.part(obs, stages.DESCENT, "visit.fixed")
