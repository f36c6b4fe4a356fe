"""Device seconds per outer iteration of the subspace lanes' objective
passes over their own rows (the scatter that densifies a lane over its
support and every multiply-reduce over it): self time of the operations
under the program's ``re.sparse_pass`` stage, which lies inside
``re.solve`` (profiler trace, ``tf_op``; layer: random_effects)."""

from benchmark import stages

FAMILY = ("re.sparse_pass", "re.subspace")


def read(obs):
    return stages.part(obs, FAMILY, "re.sparse_pass")
