"""Percent of the sparse random effects' densified lanes, by float32 bytes
(lanes x capacity x width x 4 over every class), in classes whose
value-and-gradient reads a lane once, through ``ops/fused``'s row-major
kernel batched over the lanes of a chunk, and not twice, by two
multiply-reduce sweeps (layer: random_effects). From the program's
prepare-time counters ``re_subspace.one_read_bytes`` and
``re_subspace.dense_bytes``, set during set-up: read from the registry
itself, as ``re_offsets.run_slot_share`` is (readers run in the run's own
process). None where the program has no such counters."""


def read(obs):
    from photon_ml_tpu.obs.metrics import REGISTRY

    counters = REGISTRY.snapshot("re_subspace.")["counters"]
    if "re_subspace.dense_bytes" not in counters:
        return None
    dense = float(counters["re_subspace.dense_bytes"]["value"])
    once = float(counters["re_subspace.one_read_bytes"]["value"])
    return 100.0 * once / dense if dense else None
