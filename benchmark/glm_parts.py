"""The two parts of a sparse GLM pass since the dense head (PR 28): what
the readers of ``glm.head`` and ``glm.tail`` share.

The program names the parts inside ``glm.objective``
(``photon_ml_tpu/obs/stages.py``, ``GLM_HEAD`` / ``GLM_TAIL``): the head's
float32 multiply-reduces and the tile-COO kernels with what XLA hangs on
them. A program without those names (a parent commit under this benchmark)
gives every reader here None, and the harness leaves the metric out.
"""

from __future__ import annotations

from benchmark import stages, work

FAMILY = ("glm.head", "glm.tail")


def program_names_parts() -> bool:
    try:
        from photon_ml_tpu.obs import stages as program_stages
    except ImportError:
        return False
    return hasattr(program_stages, "GLM_HEAD") and hasattr(program_stages, "GLM_TAIL")


def seconds_per_fit(obs, name: str):
    """Device seconds per fit of one part (trace, ``tf_op``)."""
    if not program_names_parts():
        return None
    return stages.part(obs, FAMILY, name)


def layout_counter(name: str):
    """A build-time counter of the program's registry (set in set-up, so
    read from the registry itself, as ``layout.tail_pad_ratio`` does)."""
    try:
        from photon_ml_tpu.obs.metrics import REGISTRY
    except ImportError:
        return None
    entry = REGISTRY.snapshot("tile_layout.")["counters"].get(name)
    return float(entry["value"]) if entry and entry["value"] else None


def device_seconds(obs, stage: str, custom_calls_only: bool = False):
    """Self seconds on the first device of the slice's operations under
    ``stage``; with ``custom_calls_only`` the Pallas custom calls alone.
    None off a TPU (no paths in the CPU backend's trace) and where nothing
    ran under the stage."""
    path = stages.trace_path()
    sl = stages.read_slice(path) if path else None
    if sl is None or not program_names_parts():
        return None
    ops = [op for op in sl.ops if op.within((stage,))]
    if custom_calls_only:
        custom = {
            name for name, op in obs.trace.ops.items() if "custom-call" in op.text
        }
        ops = [op for op in ops if op.name in custom]
    return sum(op.self_s for op in ops) or None


def head_pass(rows: int, head_columns: int) -> tuple[float, float]:
    """(operations, bytes) of one objective pass over a dense float32 head
    of ``head_columns`` columns: each direction reads the matrix once
    (margins and contraction need different residuals, so the matrix is
    read twice a pass), two operations an element a direction."""
    return 2.0 * 2.0 * rows * head_columns, 2.0 * rows * head_columns * 4


def roofline(obs, least_of_pass, seconds):
    """Percent: the slice's objective passes at their least time over
    ``seconds``."""
    passes = obs.counters.get("optim.objective_passes")
    if not passes or not seconds:
        return None
    least, _ = work.least_seconds(*least_of_pass, obs.device_kind)
    return 100.0 * passes * least / seconds
