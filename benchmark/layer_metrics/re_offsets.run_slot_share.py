"""Percent of the random effects' bucket slots whose residual offsets are
read by run-start slices (one index a lane) and not by one index a slot
(layer: random_effects), from the program's prepare-time counters
``re_offsets.run_slots`` and ``re_offsets.slots``. The counters are set
during set-up, and the observation carries only the window's differences of
the registry, so they are read from the registry itself (readers run in the
run's own process). None where the program has no such counters."""


def read(obs):
    from photon_ml_tpu.obs.metrics import REGISTRY

    counters = REGISTRY.snapshot("re_offsets.")["counters"]
    if "re_offsets.slots" not in counters:
        return None
    slots = float(counters["re_offsets.slots"]["value"])
    runs = float(counters["re_offsets.run_slots"]["value"])
    return 100.0 * runs / slots if slots else None
