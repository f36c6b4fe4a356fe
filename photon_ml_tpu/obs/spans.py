"""Nested host-side spans on two clocks, + structured event emission.

``span("descent/iter", iteration=it)`` opens a named span of host code. It
is live whether or not a telemetry sink is configured, and keeps two clocks:

- the profiler's: every span opens a ``jax.profiler.TraceAnnotation`` of
  its name, so a run under ``jax.profiler`` (``--profile-dir``, the
  benchmark's traced slice) shows ``descent/launch`` or ``glm/train`` on
  the host plane beside the device operations. With no trace running the
  annotation is the profiler's own no-op;
- the wall clock (``time.perf_counter``): on exit the span adds its seconds
  to the always-on registry timer ``span.<name>`` (seconds and calls).

Spans nest through a THREAD-LOCAL stack, so concurrent prefetch worker
threads each build their own tree instead of inheriting whatever the
consumer thread happened to have open. The stack also tells a span where it
stood, which two more timers record: ``span_top.<name>`` for a call that
was the outermost open span of its thread (an entry point; their sum is the
wall time inside the program, each second once), and ``span_self.<name>``
for a call that had spans inside it: its seconds under none of them, which
is what is still to name. A span without children is a leaf; the leaves'
seconds are ``span_top`` less ``span_self``.

With a sink configured a span also makes an id and emits, on exit, one
complete record (name, ids, thread, start time, duration, attributes),
which maps 1:1 onto a Chrome-trace complete event for the Perfetto
exporter; a root span's exit samples the HBM watermarks. With no sink it
makes no id, keeps no attributes and writes nothing.

A span costs about two microseconds (PERF.md, PR 36), so it belongs around
a launch, a visit, a fit or a set-up step, never inside a per-entity or
per-row loop. Spans are HOST code: inside a traced function a ``with
span(...)`` would run once, at trace time, and name nothing; what runs on
the device is named by ``obs/stages.py``.

Names live here, as the stage names live in ``obs/stages.py``: a constant
for every span a metric of the benchmark reads and for every other span of
the modules that hold one, and ``TOP_LEVEL`` for the program's entry points. A name is ``<layer>/<step>``: the slash is what no
stage name and no span of the benchmark's harness has. The other sites
(drivers, streaming, serving; PERF.md section 3 has the inventory) keep
their names as literals: ``photon-ml-tpu report`` reads every span by the
part before the slash, as a phase.

The inventory (PR 36; 29 sites before it, 52 after). Every span is read by
``photon-ml-tpu report`` (its phase table and the Perfetto export take any
name) once a sink is on; what ELSE reads it:

- the constants below, by the benchmark (``benchmark/host_spans.py``; the
  table span -> site -> metric is in PERF.md section 3): ``game/batch``,
  ``game/place`` (PR 38: a batch's rows padded and put over a mesh, inside
  ``game/batch`` where ``make_game_batch`` is given the mesh), ``game/group``, ``game/bucket``, ``coordinate/fixed``,
  ``coordinate/random-effect``, ``descent/run`` / ``prepare`` / ``launch`` /
  ``collect``, ``layout/optimize`` / ``to-host`` / ``fingerprint`` /
  ``head`` / ``merge`` / ``pack`` / ``stage``, ``glm/train``,
  ``glm/lambda``, ``distributed/train``; ``descent/iter`` / ``visit`` /
  ``validation`` / ``checkpoint`` (``game/descent``'s unfused path and its
  checkpoints: no cell runs them) by the report alone;
- the literals, by the report alone: ``stream/<name>``
  (``ops/stream_executor``); ``ingest/cv-fold``, ``cv/fold``, ``cv/refit``
  (``supervised/cross_validation``); ``serve/refresh``, ``serve/window``
  twice (``serve/*``); ``replan/migrate``, ``game/fit``,
  ``ingest/re-shard``, ``descent/iter``, ``descent/visit``,
  ``descent/validation``, ``descent/checkpoint`` (``game/streaming``);
  ``score/pass`` (``cli/score``); ``ingest/train-data``,
  ``ingest/validation-data``, ``ingest/stats-pass``, ``ingest/fill-pass``,
  ``ingest/fill-validation``, ``train/grid-fit``, ``train/grid-entry``,
  ``train/streamed-descent`` (``cli/train``).

No site is read by nothing, so none was removed; ``descent/fused-outer``
became ``descent/launch`` around the launch alone.

When a profiler session is first seen by an entry point, the registry's
timers are copied as they stand (``session_baseline``): what the process
had spent before the traced part began. The benchmark reads its set-up
seconds there; totals read after the run would also hold what came after
the trace (the benchmark's own check runs the descent once more).
"""

from __future__ import annotations

import functools
import itertools
import threading
import time

from jax.profiler import TraceAnnotation

from photon_ml_tpu.obs import sink as _sink_mod
from photon_ml_tpu.obs.metrics import REGISTRY

# -- the names a metric reads ----------------------------------------------
GAME_BATCH = "game/batch"  # game/data.make_game_batch: host arrays to the device
GAME_PLACE = "game/place"  # game/data.place_game_batch: the rows over a mesh, once
GAME_GROUP = "game/group"  # game/data.group_by_entity: the ingest-time shuffle
GAME_BUCKET = "game/bucket"  # game/data.bucket_entities: padded row-index matrices
COORD_FIXED = "coordinate/fixed"  # the fixed effect's base batch, layout, visit fn
COORD_RE = "coordinate/random-effect"  # prepare_buckets, index maps, score features
DESCENT_RUN = "descent/run"  # CoordinateDescent.run, whole
DESCENT_PREPARE = "descent/prepare"  # the fused program's parts, owns, statics
DESCENT_LAUNCH = "descent/launch"  # the fused(...) call: trace, lower, load, dispatch
DESCENT_COLLECT = "descent/collect"  # slice_all, postprocess, model, trackers
DESCENT_CHECKPOINT = "descent/checkpoint"  # inside it: an iteration's checkpoint
DESCENT_ITER = "descent/iter"  # the unfused path: one outer iteration
DESCENT_VISIT = "descent/visit"  # inside it: one coordinate's visit
DESCENT_VALIDATION = "descent/validation"  # and its validation pass
LAYOUT_OPTIMIZE = "layout/optimize"  # ops/batch.optimize_batch_layout, whole
LAYOUT_TO_HOST = "layout/to-host"  # the padded-sparse rows to host arrays
LAYOUT_FINGERPRINT = "layout/fingerprint"  # the hash that keys tile_cache
LAYOUT_HEAD = "layout/head"  # column counts, the head's columns and matrix
LAYOUT_MERGE = "layout/merge"  # a row's repeated draws of a column merged
LAYOUT_PACK = "layout/pack"  # the chunk loop's sort and pack
LAYOUT_STAGE = "layout/stage"  # the packed streams onto the device
GLM_TRAIN = "glm/train"  # supervised/training.train_glm, whole
GLM_LAMBDA = "glm/lambda"  # one regularization weight's solve inside it
DISTRIBUTED_TRAIN = "distributed/train"  # DistributedTrainer.train

# The program's entry points: none of them opens inside another one on the
# paths the benchmark runs, and where a caller nests them anyway (a fixed
# effect's first visit builds its layout inside ``descent/run``) only the
# outermost open span of the thread counts as top-level (``span_top``).
TOP_LEVEL = (
    GAME_BATCH, GAME_GROUP, GAME_BUCKET, DESCENT_RUN, LAYOUT_OPTIMIZE,
    GLM_TRAIN, DISTRIBUTED_TRAIN, GAME_PLACE,
)

# registry timer prefixes (see the module docstring)
TIMER = "span."
TOP_TIMER = "span_top."
SELF_TIMER = "span_self."

# span ids are process-unique; itertools.count is atomic under the GIL
_ids = itertools.count(1)
_tls = threading.local()

# the registry's timers at the start of the profiler session last seen
_baseline: dict | None = None
_in_session = False


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _note_profiler_session() -> None:
    global _baseline, _in_session
    on = TraceAnnotation.is_enabled()
    if on and not _in_session:
        _baseline = REGISTRY.timer_snapshot()
    _in_session = on


def session_baseline() -> dict | None:
    """``{timer: {"seconds", "calls"}}`` as the registry stood when an
    entry point first ran under the current (or the last) profiler session;
    None where no session was seen."""
    return _baseline


class _Span:
    __slots__ = ("name", "attrs", "span_id", "parent_id", "t0", "start_unix",
                 "annotation", "child_s", "stack")

    def __init__(self, name: str, attrs: dict | None):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        st = self.stack = _stack()
        if _sink_mod.active_sink() is not None:
            self.parent_id = st[-1].span_id if st else None
            self.span_id = next(_ids)
            self.start_unix = time.time()
        else:
            self.span_id = None
        if not st:
            _note_profiler_session()
        self.child_s = None
        st.append(self)
        self.annotation = TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self.t0
        self.annotation.__exit__(exc_type, exc, tb)
        st = self.stack
        # tolerate exotic unwind orders; normal exits pop the top
        if st and st[-1] is self:
            st.pop()
        elif self in st:
            st.remove(self)
        REGISTRY.timer_add(TIMER + self.name, dur)
        if self.child_s is not None:
            REGISTRY.timer_add(SELF_TIMER + self.name, dur - self.child_s)
        if st:
            st[-1].child_s = (st[-1].child_s or 0.0) + dur
        else:
            REGISTRY.timer_add(TOP_TIMER + self.name, dur)
        s = _sink_mod.active_sink()
        if s is not None and self.span_id is not None:
            th = threading.current_thread()
            rec = {
                "event": "span",
                "t": self.start_unix,
                "name": self.name,
                "span_id": self.span_id,
                "parent_id": self.parent_id,
                "tid": th.ident,
                "thread": th.name,
                "dur_s": dur,
            }
            if self.attrs:
                rec["attrs"] = self.attrs
            if exc_type is not None:
                rec["error"] = exc_type.__name__
            s.emit(rec)
            if self.parent_id is None:
                # ROOT-span exit: sample device HBM watermarks (per-fit /
                # per-driver cadence — never per-iteration; the sampler is
                # itself sink-gated and never raises)
                try:
                    from photon_ml_tpu.obs import devcost

                    devcost.sample_hbm_watermarks(root_span=self.name)
                except Exception:
                    pass
        return False


def span(name: str, **attrs):
    """A nested span of host code: a profiler annotation and the registry
    timer ``span.<name>`` always, a JSONL record when a sink is on."""
    return _Span(name, attrs)


def spanned(name: str):
    """``span(name)`` around every call of the decorated function."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with _Span(name, None):
                return fn(*args, **kwargs)
        return inner
    return wrap


def open_spans() -> int:
    """How many spans this thread has open."""
    return len(getattr(_tls, "stack", ()))


def current_span_id() -> int | None:
    st = getattr(_tls, "stack", None)
    return st[-1].span_id if st else None


def emit_event(event: str, **payload) -> None:
    """Emit one structured record (attributed to the current thread's open
    span, if any). A no-op when telemetry is disabled."""
    s = _sink_mod.active_sink()
    if s is None:
        return
    rec = {"event": event, "t": time.time()}
    sid = current_span_id()
    if sid is not None:
        rec["span_id_ref"] = sid
    rec.update(payload)
    s.emit(rec)


def emit_log(level: str, message: str, fields: dict | None = None) -> None:
    """Structured twin of a PhotonLogger warn/error line (the logger's
    default event hook)."""
    s = _sink_mod.active_sink()
    if s is None:
        return
    rec = {"event": "log", "t": time.time(), "level": level,
           "message": message}
    sid = current_span_id()
    if sid is not None:
        rec["span_id_ref"] = sid
    if fields:
        rec["fields"] = fields
    s.emit(rec)
