"""Percent of the stored nonzeros that the layout build moved out of the
tile-COO streams into the dense head (layer: layout), from the program's
build-time counters ``tile_layout.head_nonzeros`` and
``tile_layout.tail_nonzeros``. The counters are set during set-up, and the
observation carries only the window's differences of the registry, so they
are read from the registry itself (readers run in the run's own process).
None where the program has no such counters."""


def read(obs):
    from photon_ml_tpu.obs.metrics import REGISTRY

    counters = REGISTRY.snapshot("tile_layout.")["counters"]
    if "tile_layout.head_nonzeros" not in counters:
        return None
    head = float(counters["tile_layout.head_nonzeros"]["value"])
    tail = float(counters["tile_layout.tail_nonzeros"]["value"])
    return 100.0 * head / (head + tail) if head + tail else None
