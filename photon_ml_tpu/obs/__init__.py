"""Run-telemetry subsystem: spans, metrics registry, JSONL sink, exporters.

The TPU-native replacement for the observability the reference got from
Spark's UI/event timeline (SURVEY.md §5.1). Six pieces:

- **spans** (``span("descent/iter", iteration=it)``) — nested spans of
  host code, thread-correct across the prefetch worker pool, always on two
  clocks: a ``jax.profiler`` annotation of the name, and the registry
  timer ``span.<name>``; with a sink, a JSONL record too;
- **stages** (``stages.stage(stages.RE_SOLVE)``) — names inside the
  compiled programs (``jax`` name scopes): every device operation of a
  profiler trace says which stage of the program it belongs to;
- **metrics registry** (``metrics.REGISTRY``) — typed counters / gauges /
  histograms / timers, always on, subsuming the legacy stage counters
  (``utils/profiling`` is a compatibility shim over it);
- **JSONL sink** (``configure(telemetry_dir)`` … ``shutdown()``) — one
  run, one schema-versioned file, atomically rotated, single-writer
  under multihost;
- **exporters** — ``obs.export`` renders a run as a Chrome-trace/Perfetto
  JSON next to ``jax.profiler`` device traces; ``obs.report`` summarizes,
  diffs, validates and GATES runs (surfaced as ``photon-ml-tpu report``);
- **analytic device cost** (``obs.devcost``) — per-executable XLA
  ``cost_analysis``/``memory_analysis`` capture on fresh compiles plus
  HBM budget/watermark sampling, feeding the report's roofline table and
  the ``report gate`` regression tripwire.

Everything here is host-side and cheap: with no sink configured a span
costs about two microseconds (an annotation the profiler ignores unless it
is tracing, and a timer) and event emission is one attribute check, so the
instrumentation stays wired through production paths unconditionally.
"""

from photon_ml_tpu.obs import devcost  # noqa: F401
from photon_ml_tpu.obs import metrics  # noqa: F401
from photon_ml_tpu.obs.devcost import capture as capture_executable_cost  # noqa: F401
from photon_ml_tpu.obs.metrics import REGISTRY  # noqa: F401
from photon_ml_tpu.obs.sink import (  # noqa: F401
    SCHEMA_VERSION,
    TelemetrySink,
    active_sink,
    configure,
    shutdown,
)
from photon_ml_tpu.obs.spans import (  # noqa: F401
    current_span_id,
    emit_event,
    emit_log,
    span,
)

# Compile visibility is part of the ALWAYS-ON half: install the
# jax.monitoring listener at import (no backend init; the callback is a
# cheap no-op between runs) so ``jax.compile_s`` is in every registry
# snapshot even without a sink.
from photon_ml_tpu.obs.sink import _install_jax_monitoring

_install_jax_monitoring()


def enabled() -> bool:
    return active_sink() is not None
