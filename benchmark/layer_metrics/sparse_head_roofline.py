"""The dense head's sweeps against the chip's roofline (layer:
glm_objective): least time for the slice's objective passes over a float32
matrix of rows x ``tile_layout.head_columns`` read once a direction
(``benchmark/glm_parts.head_pass``) over the device time under the
program's ``glm.head`` stage."""

from benchmark import glm_parts


def read(obs):
    width = glm_parts.layout_counter("tile_layout.head_columns")
    if not width:
        return None
    return glm_parts.roofline(
        obs, glm_parts.head_pass(obs.shape["rows"], width),
        glm_parts.device_seconds(obs, "glm.head"),
    )
