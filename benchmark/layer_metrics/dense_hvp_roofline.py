"""A dense Hessian-vector pass against the chip's roofline (layer:
glm_objective): the least time of the slice's CG steps, each one read of
rows x REAL columns x itemsize (``work.dense_pass``; bound by bytes, not
operations), over ALL device time under the program's ``glm.hvp`` stage,
custom call or not: whatever implements the pass reads at least that, so
the share cannot pass 100%."""

from benchmark import tron_parts


def read(obs):
    return tron_parts.hvp_roofline(obs)
