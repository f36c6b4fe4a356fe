"""Device seconds per outer iteration of moving lanes through a sparse
shard's column maps: the extraction of each bucket's warm start from the
(entities, d) matrix and the zero-then-scatter of its solution back; self
time of the operations under the program's ``re.subspace`` stage, inside
``re.solve`` and beside ``re.sparse_pass`` (profiler trace, ``tf_op``;
layer: random_effects)."""

from benchmark import stages

FAMILY = ("re.sparse_pass", "re.subspace")


def read(obs):
    return stages.part(obs, FAMILY, "re.subspace")
