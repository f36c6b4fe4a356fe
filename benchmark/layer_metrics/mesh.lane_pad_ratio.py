"""Entity lanes after every bucket class is padded to a multiple of the
mesh, over the lanes that hold an entity (layer: random_effects): the
program's prepare-time counters ``re_mesh.padded_lanes`` and
``re_mesh.lanes``, read from the registry itself as
``re_offsets.run_slot_share`` reads its own. 1.0 is no padding. None where
the program has no such counters (no lane-sharded staging ran)."""


def read(obs):
    from photon_ml_tpu.obs.metrics import REGISTRY

    counters = REGISTRY.snapshot("re_mesh.")["counters"]
    if "re_mesh.lanes" not in counters:
        return None
    lanes = float(counters["re_mesh.lanes"]["value"])
    padded = float(counters["re_mesh.padded_lanes"]["value"])
    return padded / lanes if lanes else None
