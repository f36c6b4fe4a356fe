"""Device seconds per outer iteration spent gathering residual offsets into
the random effects' bucket slots: self time of the operations under the
program's ``re.offsets`` stage (profiler trace, ``tf_op``; layer:
random_effects)."""

from benchmark import stages


def read(obs):
    return stages.part(obs, stages.DESCENT, "re.offsets")
