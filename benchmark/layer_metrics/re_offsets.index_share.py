"""Percent of the random effects' bucket slots' worth of indices that a visit
reads one by one (layer: random_effects): 100 x (``re_offsets.slots`` -
``re_offsets.run_slots`` + ``re_offsets.ordered_rows``) / ``re_offsets.slots``
from the program's prepare-time counters. A slot read by a run-start slice
costs no index of its own; an effect whose lanes are no runs of the file
gathers its real rows once a visit into its own order (``ordered_rows``
indices, a mesh's filler included) for those slices to read. A program
without ``ordered_rows`` reads as 0 there, so it reports 100 less
``re_offsets.run_slot_share``. Read from the registry itself, as that
reader does: the counters are set during set-up. None where the program has
no such counters."""


def read(obs):
    from photon_ml_tpu.obs.metrics import REGISTRY

    counters = REGISTRY.snapshot("re_offsets.")["counters"]
    if "re_offsets.slots" not in counters:
        return None
    value = lambda name: float(counters.get(name, {"value": 0.0})["value"])
    slots = value("re_offsets.slots")
    one_by_one = slots - value("re_offsets.run_slots") + value("re_offsets.ordered_rows")
    return 100.0 * one_by_one / slots if slots else None
