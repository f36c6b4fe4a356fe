"""Buffered JSONL event sink — one run, one schema-versioned file.

Reference parity: the reference leans on Spark's event log / UI timeline
for run observability (SURVEY.md §5.1); this sink is the TPU-native
equivalent: every span, optimizer record, structured warning and metric
snapshot of a run lands as one JSON line in one file that a human or a
sweep script can diff across runs without grepping stderr.

Durability contract: the file on disk is ALWAYS a complete, parseable
run prefix. Buffered records are committed by **atomic rotation** — the
full accumulated content is written to a same-directory temp file,
fsync'd, and renamed over the run file (``utils/atomic_io``, the same
fsync-rename idiom the visit-checkpoint shards use) — so a reader never
observes a torn tail and a crash never shadows a complete file with a
partial one. The rotation threshold grows with the file (bounded at
``_MAX_ROTATE_EVERY``) so total write amplification stays O(n·log n)
rather than O(n²) on long runs. The tradeoff of full-rewrite atomicity
is that the sink holds the run's serialized records in memory and each
commit rewrites the whole file — sized for this framework's runs (span +
per-iteration record volume is a few hundred bytes each; even a
day-long sweep stays in the tens of MB). A workload emitting orders of
magnitude more should thin its per-iteration records, not the spans.

Multihost: process 0 writes the canonical ``run-<id>.jsonl`` — the file
every existing consumer reads unchanged. Under **fleet telemetry**
(``PHOTON_TELEMETRY_FLEET``; defaults to the ``PHOTON_RE_SHARD`` knob,
because the sharded random-effect schedule is exactly the workload whose
phase walls, exchange waits and per-link transfers live on processes
1..N-1) every non-zero process writes its own schema-versioned shard
``run-<id>.p<k>.jsonl`` under the same atomic-rotation durability
contract; ``photon-ml-tpu report fleet`` joins the canonical file and
its shards into one per-process view. With fleet telemetry off (the
default), ``configure`` on a non-zero process returns a disabled
subsystem exactly as before — the same single-writer discipline the
drivers use for models and metrics, byte for byte.

Disabled fast path: when no sink is configured, ``emit`` is a single
attribute check and a ``span()`` writes no record (it still names itself
to the profiler and adds its seconds to the registry: ``obs/spans.py``) —
telemetry can stay wired through production paths.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any

from photon_ml_tpu.obs import metrics as _metrics

SCHEMA_VERSION = 1

# rotation cadence: first commit after this many buffered records, then
# proportional to what's already written (amortized near-linear total IO)
_FIRST_ROTATE_EVERY = 128
_MAX_ROTATE_EVERY = 65536


def _json_default(o: Any) -> str:
    return str(o)


def _sanitize(v: Any) -> Any:
    """Strict-JSON-safe record values: Python's json module would happily
    write bare ``NaN``/``Infinity`` (a diverged solve's loss, say), which
    strict parsers — the Perfetto UI, any non-Python consumer — reject
    for the WHOLE file. Non-finite floats become strings, keeping the
    information without breaking the document."""
    if isinstance(v, float):
        if v != v:
            return "NaN"
        if v == float("inf"):
            return "Infinity"
        if v == float("-inf"):
            return "-Infinity"
        return v
    if isinstance(v, dict):
        return {k: _sanitize(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_sanitize(x) for x in v]
    return v


class TelemetrySink:
    """One run's JSONL file. Thread-safe; records are buffered and
    committed by atomic rotation (never an append a crash could tear)."""

    _seq = itertools.count()  # same-second same-process runs stay distinct

    def __init__(
        self,
        directory: str,
        run_id: str | None = None,
        shard_index: int | None = None,
    ):
        os.makedirs(directory, exist_ok=True)
        self.run_id = run_id or (
            time.strftime("%Y%m%dT%H%M%S")
            + f"-{os.getpid()}-{next(self._seq)}"
        )
        self.directory = directory
        # shard_index k > 0: one process's slice of a FLEET run —
        # ``run-<id>.p<k>.jsonl`` next to process 0's canonical
        # ``run-<id>.jsonl`` (which keeps its name so every
        # single-process consumer reads it unchanged)
        self.shard_index = shard_index
        suffix = f".p{shard_index}" if shard_index else ""
        self.path = os.path.join(
            directory, f"run-{self.run_id}{suffix}.jsonl"
        )
        self._lock = threading.Lock()
        self._lines: list[str] = []
        self._pending = 0
        self._rotate_every = _FIRST_ROTATE_EVERY
        self._closed = False

    def emit(self, record: dict) -> None:
        """Buffer one event record (a plain dict; non-JSON values are
        stringified rather than raised — telemetry must never take down
        the run it observes)."""
        line = json.dumps(_sanitize(record), default=_json_default)
        with self._lock:
            if self._closed:
                return
            self._lines.append(line)
            self._pending += 1
            if self._pending >= self._rotate_every:
                self._rotate_locked()

    def _rotate_locked(self) -> None:
        from photon_ml_tpu.utils.atomic_io import atomic_replace_bytes

        data = ("\n".join(self._lines) + "\n").encode()
        atomic_replace_bytes(self.directory, self.path, data)
        self._pending = 0
        self._rotate_every = min(
            max(_FIRST_ROTATE_EVERY, len(self._lines)), _MAX_ROTATE_EVERY
        )

    def flush(self) -> None:
        with self._lock:
            if not self._closed:
                self._rotate_locked()

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._rotate_locked()
            self._closed = True


# -- the process-wide active sink ------------------------------------------

_ACTIVE: TelemetrySink | None = None
_state_lock = threading.Lock()


def active_sink() -> TelemetrySink | None:
    return _ACTIVE


def is_active() -> bool:
    """Whether a telemetry sink is currently configured (cheap, lock-free
    — consumers use it to gate observability-only host syncs)."""
    return _ACTIVE is not None


def _process_index() -> int:
    # a rejoin-booted process (fresh interpreter, original identity
    # recorded by multihost.bootstrap_rejoin) must shard under its
    # ORIGINAL index — jax.process_index() is 0 there, and a 0-index
    # rejoiner would collide with the true canonical run file
    try:
        from photon_ml_tpu.parallel import multihost as mh

        if mh.rejoin_identity() is not None:
            return int(mh.original_process_index())
    except Exception:
        pass
    try:
        import jax

        return int(jax.process_index())
    except Exception:
        return 0


def _process_count() -> int:
    try:
        from photon_ml_tpu.parallel import multihost as mh

        if mh.rejoin_identity() is not None:
            return int(mh.original_process_count())
    except Exception:
        pass
    try:
        import jax

        return int(jax.process_count())
    except Exception:
        return 1


def fleet_telemetry_enabled() -> bool:
    """Fleet telemetry knob: ``PHOTON_TELEMETRY_FLEET`` (strict int parse
    like the sibling knobs — a typo fails loudly). Unset, it follows
    ``PHOTON_RE_SHARD``: the sharded random-effect schedule is exactly
    the workload whose telemetry lives on processes 1..N-1, and the
    default keeps every non-sharded multihost run's sink behavior (and
    file layout) bit-for-bit what it was."""
    env = os.environ.get("PHOTON_TELEMETRY_FLEET")
    if env is not None and env != "":
        return int(env) != 0
    try:
        from photon_ml_tpu.parallel.placement import re_shard_enabled

        return re_shard_enabled()
    except Exception:
        return False


def _fleet_run_id() -> str:
    """One run id for every process of a fleet run: process 0 generates
    its usual timestamp id and broadcasts it (the shards must carry the
    SAME ``<id>`` for ``report fleet`` to join them with the canonical
    file). Collective — every process reaches ``configure`` at the same
    program point, the same contract the drivers' multihost init already
    imposes. Callers that need to avoid the collective pass an explicit
    ``run_id`` (the bench harness does)."""
    import numpy as np

    from photon_ml_tpu.parallel.multihost import broadcast_from_host0

    rid = time.strftime("%Y%m%dT%H%M%S") + f"-{os.getpid()}"
    buf = np.zeros(64, np.uint8)
    raw = rid.encode()[:64]
    buf[: len(raw)] = np.frombuffer(raw, np.uint8)
    out = np.asarray(broadcast_from_host0(buf), np.uint8)
    return bytes(out[out != 0]).decode()


def configure(
    telemetry_dir: str | None,
    run_id: str | None = None,
    force_writer: bool | None = None,
) -> str | None:
    """Enable telemetry into ``telemetry_dir`` and return the run file's
    path. ``None`` leaves telemetry disabled (the CLI drivers call this
    unconditionally with their ``--telemetry-dir`` value). Multihost: the
    output process writes the canonical run file; under fleet telemetry
    (``fleet_telemetry_enabled``) every other process writes its own
    ``.p<k>`` shard, otherwise it gets a disabled subsystem unless
    ``force_writer=True``. Re-configuring closes any previous run's sink
    first."""
    global _ACTIVE
    with _state_lock:
        if _ACTIVE is not None:
            _shutdown_locked()
        if telemetry_dir is None:
            return None
        pidx = _process_index()
        fleet = _process_count() > 1 and fleet_telemetry_enabled()
        if fleet and run_id is None and force_writer is None:
            # collective: every process must agree on the shard-join id
            run_id = _fleet_run_id()
        writer = force_writer if force_writer is not None else pidx == 0
        shard_index = None
        if not writer:
            if not fleet:
                return None
            shard_index = pidx
        sink = TelemetrySink(
            telemetry_dir, run_id=run_id, shard_index=shard_index
        )
        record = {
            "event": "run_start",
            "t": time.time(),
            "schema_version": SCHEMA_VERSION,
            "run_id": sink.run_id,
            "pid": os.getpid(),
            "process_index": pidx,
            "knobs": _knob_snapshot(),
            # the registry is PROCESS-cumulative; the baseline lets a
            # reader (obs/report) delta run_end down to THIS run's
            # share when several runs live in one process
            "metrics_baseline": _metrics.REGISTRY.snapshot(),
        }
        if fleet:
            # only fleet runs carry the field: a single-process (or
            # fleet-off) run's file stays byte-for-byte what it was
            record["fleet"] = {"process_count": _process_count()}
        sink.emit(record)
        _ACTIVE = sink
        _install_jax_monitoring()
        return sink.path


def shutdown() -> None:
    """Emit the ``run_end`` record (with the full metrics snapshot), flush
    durably, and disable the sink. Safe to call when already disabled."""
    with _state_lock:
        _shutdown_locked()


def _shutdown_locked() -> None:
    global _ACTIVE
    sink = _ACTIVE
    _ACTIVE = None  # disable emission first: close must not race new spans
    if sink is None:
        return
    record = {
        "event": "run_end",
        "t": time.time(),
        "run_id": sink.run_id,
        "metrics": _metrics.REGISTRY.snapshot(),
    }
    try:
        from photon_ml_tpu.ops import prefetch

        record["chunk_cache"] = prefetch.cache_stats()
    except Exception:
        pass
    try:
        from photon_ml_tpu.ops import stream_executor

        # only when a stream actually rode the arbiter: an executor-off
        # run's run_end record stays key-for-key what it was
        if stream_executor.traffic_seen():
            record["stream_cache"] = stream_executor.cache_stats()
    except Exception:
        pass
    sink.emit(record)
    sink.close()


def _knob_snapshot() -> dict:
    """The retune surface a run executed under (same knobs the bench
    round-trips), so two JSONLs are diffable AS CONFIGURATIONS too."""
    knobs: dict = {}
    try:
        from photon_ml_tpu.ops import prefetch

        knobs["prefetch_depth"] = prefetch.prefetch_depth()
        knobs["chunk_cache_budget_bytes"] = int(
            prefetch.chunk_cache_budget_bytes()
        )
    except Exception:
        pass
    try:
        from photon_ml_tpu.ops import sparse_tiled as st

        knobs["groups_per_step"] = int(st.GROUPS_PER_STEP)
        knobs["segments_per_dma"] = int(st.SEGMENTS_PER_DMA)
        knobs["groups_per_run"] = int(st.GROUPS_PER_RUN)
        knobs["kernel_dtype"] = st.kernel_dtype()
    except Exception:
        pass
    try:
        from photon_ml_tpu.game import random_effect as re_mod

        knobs["re_compact_every"] = int(re_mod.compact_every())
        knobs["re_fuse_buckets"] = int(bool(re_mod.fuse_buckets()))
        knobs["re_combine"] = str(re_mod.re_combine_mode())
    except Exception:
        pass
    try:
        from photon_ml_tpu.game import projector

        knobs["re_project"] = str(projector.re_project_mode())
        knobs["re_project_dim"] = int(projector.re_project_dim())
    except Exception:
        pass
    try:
        from photon_ml_tpu.parallel import placement

        knobs["re_shard"] = int(bool(placement.re_shard_enabled()))
        knobs["re_split"] = int(placement.re_split_factor())
        knobs["re_replan_imbalance"] = float(
            placement.replan_imbalance_threshold()
        )
        knobs["re_device_split"] = int(
            bool(placement.re_device_split_enabled())
        )
        knobs["re_split_weight"] = str(placement.re_split_weight())
    except Exception:
        pass
    try:
        from photon_ml_tpu.data import index_map

        knobs["fe_shard"] = int(bool(index_map.fe_shard_enabled()))
        knobs["fe_split_weight"] = str(index_map.fe_split_weight())
    except Exception:
        pass
    try:
        from photon_ml_tpu.serve import refresh as serve_refresh
        from photon_ml_tpu.serve import router as serve_router
        from photon_ml_tpu.serve import store as serve_store

        knobs["serve_hot_bytes"] = int(serve_store.serve_hot_budget_bytes())
        knobs["serve_max_batch"] = int(serve_router.serve_max_batch())
        knobs["serve_max_wait_ms"] = float(serve_router.serve_max_wait_ms())
        knobs["serve_refresh_every"] = int(
            serve_refresh.serve_refresh_every()
        )
    except Exception:
        pass
    try:
        from photon_ml_tpu.ops import stream_executor

        knobs["stream_executor"] = int(
            bool(stream_executor.stream_executor_enabled())
        )
        knobs["stream_priority"] = str(
            stream_executor.stream_priority_spec()
        )
        knobs["stream_share"] = str(stream_executor.stream_share_spec())
    except Exception:
        pass
    return knobs


# -- XLA compile visibility via jax.monitoring ------------------------------
# Registered ONCE per process at obs import (and defensively re-checked in
# configure), never unregistered (jax offers no targeted removal); the
# callbacks consult the active sink so they are cheap no-ops between runs.
# Durations also land in the registry, so compile wall is in every
# snapshot — bench telemetry blocks included — even without a sink.

_jax_monitoring_installed = False

# the compile pipeline's other steps, each under its own always-on timer.
# They are booked only while a span of the program is open on the calling
# thread (obs/spans.py), so the seconds are the program's own: a caller's
# data generator or reference compiles under no span of ours.
TRACE_TIMER = "jax.trace_s"  # Python tracing to a jaxpr
LOWER_TIMER = "jax.lower_s"  # the jaxpr to an MLIR module, Pallas kernels included
# a hit of the persistent cache: a part of jax.compile_s, which times
# "compile or load"
CACHE_LOAD_TIMER = "jax.cache_load_s"
_JAX_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_JAX_STEP_TIMERS = {
    _JAX_TRACE: TRACE_TIMER,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER_TIMER,
    "/jax/compilation_cache/cache_retrieval_time_sec": CACHE_LOAD_TIMER,
}


def _on_jax_duration(name: str, secs: float, **kw) -> None:
    try:
        if "backend_compile" in name:
            # the leaf XLA compile phase only: jax nests it inside broader
            # "compile" events, and summing every match double-counts
            _metrics.REGISTRY.timer_add("jax.compile_s", secs)
        elif name in _JAX_STEP_TIMERS:
            from photon_ml_tpu.obs import spans

            if spans.open_spans() and (name != _JAX_TRACE or _outermost_trace()):
                _metrics.REGISTRY.timer_add(_JAX_STEP_TIMERS[name], secs)
        sink = _ACTIVE
        if sink is not None:
            sink.emit(
                {"event": "jax_event", "t": time.time(), "name": name,
                 "dur_s": secs}
            )
    except Exception:
        pass  # monitoring must never break compilation


def _outermost_trace() -> bool:
    """Whether the tracing event being reported is the outermost one. JAX
    reports a duration for every ``jit`` it traces, an inner one inside the
    outer one's trace and inside its seconds; the event fires when a trace
    has just ended, so an inner one still finds its caller's trace open.
    Counting the outermost alone counts every traced second once."""
    import jax

    return jax.core.trace_ctx.is_top_level()


def _install_jax_monitoring() -> None:
    global _jax_monitoring_installed
    if _jax_monitoring_installed:
        return
    try:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_jax_duration)
        _jax_monitoring_installed = True
    except Exception:
        pass  # older jax without monitoring: compile events just absent
