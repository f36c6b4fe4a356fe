"""The tile-COO kernels (``ops/sparse_tiled``) against the chip's roofline:
least time for the slice's objective passes, from the sparse bytes function
of ``work.py``, over the summed device time of the kernels' custom calls
(profiler trace). Memory-bound at these shapes."""

from benchmark import work
from benchmark.readers import pass_roofline

# the kernels' custom calls carry the name of the jitted function around them
KERNEL = r"_tiled_apply_jit.*custom-call"


def read(obs):
    s = obs.shape
    if not s.get("nonzeros"):
        return None
    return pass_roofline(
        obs, KERNEL,
        lambda s: work.sparse_pass(s["rows"], s["columns"], s["nonzeros"]),
    )
