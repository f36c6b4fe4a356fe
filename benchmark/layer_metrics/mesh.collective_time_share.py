"""Percent of the slice the first device spent in collective operations
(profiler trace; layer: mesh)."""

from benchmark.readers import op_time_share
from benchmark.trace_reduce import COLLECTIVE


def read(obs):
    if obs.chips < 2:
        return None
    return op_time_share(obs, COLLECTIVE)
