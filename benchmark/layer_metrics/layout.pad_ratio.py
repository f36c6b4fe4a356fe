"""Packed slots of the built tile-COO layout over the nonzeros they hold,
both directions (layer: layout): 1.0 is no padding."""


def read(obs):
    c = obs.counters
    if not c.get("layout.slots") or not c.get("layout.nonzeros"):
        return None
    return c["layout.slots"] / (2.0 * c["layout.nonzeros"])  # two directions
