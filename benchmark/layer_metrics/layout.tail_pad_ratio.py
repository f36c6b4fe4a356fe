"""Packed slots of the built tile-COO layout over the stored nonzeros the
build left to it, both directions (layer: layout): 1.0 is no padding.
``layout.pad_ratio`` divides by ALL of the matrix's nonzeros; since the
build can move popular columns into a dense head, the streams hold only
the tail, which the program counts in ``tile_layout.tail_nonzeros`` (set
during set-up, so read from the registry itself, as
``layout.head_nonzero_share`` does). None where the program has no such
counter or built no tile-COO layout."""


def read(obs):
    from photon_ml_tpu.obs.metrics import REGISTRY

    slots = obs.counters.get("layout.slots")
    tail = REGISTRY.snapshot("tile_layout.")["counters"].get(
        "tile_layout.tail_nonzeros"
    )
    if not slots or not tail or not tail["value"]:
        return None
    return slots / (2.0 * float(tail["value"]))  # two directions
