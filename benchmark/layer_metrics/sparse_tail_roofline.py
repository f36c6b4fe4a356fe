"""The tile-COO kernels against the chip's roofline on the nonzeros they
hold (layer: glm_objective): least time for the slice's objective passes
over the TAIL's stored nonzeros (``benchmark/work.sparse_pass`` on the
program's ``tile_layout.tail_nonzeros``) over the device time of the Pallas
custom calls under the program's ``glm.tail`` stage. ``sparse_tiled_roofline``
divides ALL of the matrix's nonzeros by the same kernels' time, so it
overstates where a dense head holds half of them."""

from benchmark import glm_parts, work


def read(obs):
    tail = glm_parts.layout_counter("tile_layout.tail_nonzeros")
    if not tail:
        return None
    s = obs.shape
    return glm_parts.roofline(
        obs, work.sparse_pass(s["rows"], s["columns"], tail),
        glm_parts.device_seconds(obs, "glm.tail", custom_calls_only=True),
    )
