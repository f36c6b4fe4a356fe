"""Seconds of the tile-COO build in set-up spent handing the packed streams to
the device (program span ``layout/stage``; layer: layout). The six
``layout.*_s`` phases are to be read against ``layout.build_s``."""

from benchmark import host_spans


def read(obs):
    return host_spans.setup_span("LAYOUT_STAGE")
