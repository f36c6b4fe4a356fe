"""Percent of the L-BFGS history rings' bytes stored as whole (8, 128) tiles,
``(m, R, 128)`` where a solve is at least ``optim/lbfgs._TILED_MIN_WIDTH``
wide (layer: optimizers), from the program's trace-time counters
``lbfgs_history.tiled_bytes`` over ``lbfgs_history.ring_bytes`` (set when a
solve is traced, in set-up, so read from the registry itself, as
``layout.fine_nonzero_share`` does). None where the program has no such
counter (a commit before the tiled ring) or traced no L-BFGS solve."""


def read(obs):
    try:
        from photon_ml_tpu.obs.metrics import REGISTRY
    except ImportError:
        return None
    counters = REGISTRY.snapshot("lbfgs_history.")["counters"]
    if "lbfgs_history.ring_bytes" not in counters:
        return None
    ring = float(counters["lbfgs_history.ring_bytes"]["value"])
    tiled = float(counters["lbfgs_history.tiled_bytes"]["value"])
    return 100.0 * tiled / ring if ring else None
