"""Shared arithmetic of the per-layer metric readers.

A reader (``benchmark/layer_metrics/<metric>.py``, function ``read``) gets
one observation of a traced run and returns the metric's value, or None
when what it reads is not there; the harness then leaves the metric out.
The observation holds:

- ``counters``: numbers by name. The runner's own counts summed over the
  slice's units (``work``, ``optim.objective_passes``, ...), its set-up
  facts (``layout.build_s``, ...), the harness's (``units``, ``window_s``,
  ``setup.compile_s``, ``compile.in_window``, ``device.peak_hbm_bytes``,
  ``device.hbm_bytes_limit``), and under ``registry.<name>`` the growth over the slice of every
  counter and timer of the program's ``obs/metrics.REGISTRY``;
- ``trace``: the ``trace_reduce.Reduced`` of the slice;
- ``shape``: the sizes the bytes/FLOP functions of ``work.py`` need;
- ``config``, ``traffic``, ``device_kind``, ``chips``.
"""

from __future__ import annotations

from benchmark import work


def counter(obs, name: str):
    return obs.counters.get(name)


def ratio(obs, num: str, den: str, scale: float = 1.0):
    n, d = obs.counters.get(num), obs.counters.get(den)
    if n is None or not d:
        return None
    return scale * n / d


def idle_share(obs):
    """Percent of the slice in which no operation ran, over the chips used."""
    t = obs.trace
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def busy_seconds_per_work(obs):
    work_units = obs.counters.get("work")
    return obs.trace.busy_s / work_units if work_units else None


def op_time_share(obs, pattern: str):
    """Percent of the slice the first device spent in matching operations."""
    return 100.0 * obs.trace.op_seconds(pattern) / obs.trace.window_s


def pass_roofline(obs, pattern: str, work_of_pass):
    """Percent of its roofline at which the kernel matching ``pattern`` ran:
    the least time for the slice's objective passes over the kernel's
    summed device time. ``work_of_pass(shape)`` gives one pass's
    (operations, bytes) on one chip."""
    passes = obs.counters.get("optim.objective_passes")
    seconds = obs.trace.op_seconds(pattern)
    if not passes or not seconds:
        return None
    least, _ = work.least_seconds(*work_of_pass(obs.shape), obs.device_kind)
    return 100.0 * passes * least / seconds
