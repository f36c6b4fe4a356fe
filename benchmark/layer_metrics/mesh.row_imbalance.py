"""Real bucket rows on the fullest chip over the mean of the chips (layer:
random_effects): what cutting every class's lanes by the mesh leaves
uneven, from the program's prepare-time counters ``re_mesh.rows_max_chip``
and ``re_mesh.rows_mean_chip`` (each effect's fullest chip, summed over the
effects: the chips meet at every exchange). 1.0 is even; lower is better.
None where the program has no such counters."""


def read(obs):
    from photon_ml_tpu.obs.metrics import REGISTRY

    counters = REGISTRY.snapshot("re_mesh.")["counters"]
    if "re_mesh.rows_mean_chip" not in counters:
        return None
    mean = float(counters["re_mesh.rows_mean_chip"]["value"])
    most = float(counters["re_mesh.rows_max_chip"]["value"])
    return most / mean if mean else None
