"""What the readers of a TRON fit's stages share (``epsilon_tron_fit``).

The program names a Hessian-vector pass ``glm.hvp`` inside
``glm.objective`` (``photon_ml_tpu/obs/stages.py``: around
``GLMObjective.hvp``, fused kernel or XLA alike) and the optimizer's own
vector algebra ``tron.cg`` / ``tron.update``. A fit's device seconds then
split into ``glm.objective``, ``tron.*`` outside it, and the unstaged rest.
A program without those names (a parent commit under this benchmark) gives
every reader here None, and the harness leaves the metric out.
"""

from __future__ import annotations

from benchmark import stages, work

FAMILY = ("glm.objective", "tron.")
HVP = "glm.hvp"


def program_names_stages() -> bool:
    try:
        from photon_ml_tpu.obs import stages as program_stages
    except ImportError:
        return False
    return hasattr(program_stages, "GLM_HVP") and hasattr(program_stages, "TRON_CG")


def seconds_per_fit(obs, name: str):
    """Device seconds per fit of one part of ``FAMILY`` (trace, ``tf_op``)."""
    if not program_names_stages():
        return None
    return stages.part(obs, FAMILY, name)


def hvp_seconds_per_fit(obs):
    """Device seconds per fit under ``glm.hvp``: every operation there,
    custom call or not."""
    if not program_names_stages():
        return None
    return stages.part(obs, (HVP,), HVP)


def hvp_roofline(obs):
    """Percent: the slice's CG steps, each at the least time of one read of
    rows x columns x itemsize (``work.dense_pass``), over the device seconds
    under ``glm.hvp``. Bound by bytes: ``least_seconds`` takes the larger of
    the two times, and 4 x rows x columns operations are far under either
    compute peak. None where nothing ran under the stage (the CPU backend's
    trace has no paths)."""
    steps, fits = obs.counters.get("optim.cg_steps"), obs.counters.get("work")
    per_fit = hvp_seconds_per_fit(obs)
    if not steps or not fits or not per_fit:
        return None
    s = obs.shape
    least, _ = work.least_seconds(
        *work.dense_pass(s["rows"], s["columns"], s["itemsize"]), obs.device_kind
    )
    return 100.0 * steps * least / (per_fit * fits)
