"""Seconds of the tile-COO build in set-up spent hashing the rows' indices and
values (SHA-256 over both arrays), which keys the process-wide layout cache
(program span ``layout/fingerprint``; layer: layout). ISSUE 36 listed five
phases; the first chip run found this sixth (2.6 s of ``rcv1_fit``'s 47.5
s). The six ``layout.*_s`` phases are to be read against ``layout.build_s``."""

from benchmark import host_spans


def read(obs):
    return host_spans.setup_span("LAYOUT_FINGERPRINT")
