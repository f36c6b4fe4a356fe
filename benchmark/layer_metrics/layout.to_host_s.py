"""Seconds of the tile-COO build in set-up spent bringing the padded-sparse
rows to host arrays (program span ``layout/to-host``; layer: layout). The
five ``layout.*_s`` phases are to be read against ``layout.build_s``."""

from benchmark import host_spans


def read(obs):
    return host_spans.setup_span("LAYOUT_TO_HOST")
