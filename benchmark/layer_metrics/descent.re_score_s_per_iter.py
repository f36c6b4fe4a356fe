"""Device seconds per outer iteration of scoring the random effects (the
``W[ids]`` gathers and the (n, d_e) work): self time of the operations
under the program's ``re.score`` stage (profiler trace, ``tf_op``; layer:
random_effects)."""

from benchmark import stages


def read(obs):
    return stages.part(obs, stages.DESCENT, "re.score")
