"""Seconds of the tile-COO build in set-up spent counting the columns, choosing
the dense head and scattering its matrix on the device (program span
``layout/head``; layer: layout). The six ``layout.*_s`` phases are to be
read against ``layout.build_s``."""

from benchmark import host_spans


def read(obs):
    return host_spans.setup_span("LAYOUT_HEAD")
