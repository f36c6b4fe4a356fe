"""Seconds of the tile-COO build in set-up spent merging a row's repeated draws
of a column (program span ``layout/merge``; layer: layout). The six
``layout.*_s`` phases are to be read against ``layout.build_s``."""

from benchmark import host_spans


def read(obs):
    return host_spans.setup_span("LAYOUT_MERGE")
