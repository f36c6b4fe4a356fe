"""Device seconds per fit of TRON's own vector algebra (CG's dots, axpys
and boundary step; acceptance, radius, histories): self time of the
operations under a ``tron.*`` stage and outside ``glm.objective``, on the
first device (profiler trace, ``tf_op``; layer: optimizers)."""

from benchmark import tron_parts


def read(obs):
    return tron_parts.seconds_per_fit(obs, "tron.")
