"""Stored nonzeros a non-empty cell of the tile-COO streams holds (layer:
layout): the program's build-time counters ``tile_layout.tail_nonzeros``
over ``tile_layout.tail_cells``. A cell is 1,024 rows x 1,024 columns; the
figure falls as 1 over the matrix's width and decides the form the build
gives the streams. None where the program has no such counters."""

from benchmark import glm_parts


def read(obs):
    tail = glm_parts.layout_counter("tile_layout.tail_nonzeros")
    cells = glm_parts.layout_counter("tile_layout.tail_cells")
    return tail / cells if tail and cells else None
