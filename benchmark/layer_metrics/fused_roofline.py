"""The fused dense kernel (``ops/fused``) against the chip's roofline:
least time for the slice's objective passes over one chip's rows, from the
dense bytes function of ``work.py``, over the summed time of the kernel's
custom calls on the first device (profiler trace)."""

from benchmark import work
from benchmark.readers import pass_roofline

# the only custom calls of a dense fit are the fused kernels
KERNEL = r"\bcustom-call\("


def read(obs):
    s = obs.shape
    if not s.get("itemsize"):
        return None
    return pass_roofline(
        obs, KERNEL,
        lambda s: work.dense_pass(
            s["rows"] // s["devices"], s["columns"], s["itemsize"]
        ),
    )
