"""Device profiling hooks.

Reference parity: SURVEY.md §5.1 — the reference leans on Spark's UI/event
timeline for stage-level tracing; the TPU-native equivalent is
``jax.profiler`` device traces (viewable in TensorBoard / Perfetto). The
drivers expose ``--profile-dir``; when set, the expensive phases run under
a trace so perf claims are backed by an inspectable timeline.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator

from photon_ml_tpu.obs.metrics import REGISTRY as _REGISTRY


@contextlib.contextmanager
def profile_trace(profile_dir: str | None, label: str = "trace") -> Iterator[None]:
    """Trace the enclosed block into ``profile_dir`` (no-op when None).

    One directory can hold several labeled traces; each ``label`` becomes a
    subdirectory so e.g. the ingest phase and a descent iteration land in
    separate, individually-loadable traces.
    """
    if profile_dir is None:
        yield
        return
    import os

    import jax

    target = os.path.join(profile_dir, label)
    os.makedirs(target, exist_ok=True)
    with jax.profiler.trace(target):
        yield


# -- stage counters --------------------------------------------------------
# COMPATIBILITY SHIM over the run-telemetry metrics registry
# (``photon_ml_tpu.obs.metrics.REGISTRY``): the process-wide wall-second
# stage counters (the prefetch pipeline's host-pack / device-put /
# consumer-wait split) now live in the registry's timer kind, so the same
# numbers appear in a run's JSONL ``run_end`` record, the bench telemetry
# block, and these legacy accessors. Every pre-telemetry call site and
# test keeps working unchanged: the snapshot shape
# (``{name: {"seconds", "calls"}}``) and reset semantics are identical.
# Thread-safe: prefetch workers accumulate concurrently.


@contextlib.contextmanager
def stage_timer(name: str) -> Iterator[None]:
    """Accumulate the enclosed block's wall seconds under ``name``."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        _REGISTRY.timer_add(name, time.perf_counter() - t0)


def add_seconds(name: str, seconds: float) -> None:
    _REGISTRY.timer_add(name, float(seconds))


def counter_snapshot(prefix: str | None = None) -> dict:
    """``{name: {"seconds", "calls"}}``, optionally filtered by prefix."""
    return _REGISTRY.timer_snapshot(prefix)


def reset_counters(prefix: str | None = None) -> None:
    _REGISTRY.reset_timers(prefix)
