"""Nested host-side spans + structured event emission.

``span("descent/iter", coordinate=cid)`` opens a named wall-clock span;
spans nest through a THREAD-LOCAL stack, so concurrent prefetch worker
threads each build their own span tree instead of inheriting whatever the
consumer thread happened to have open (cross-thread parent leakage would
corrupt every timeline the workers touch). A span record is emitted on
exit as one complete event — name, ids, thread, start time, duration,
attributes — which maps 1:1 onto a Chrome-trace complete event for the
Perfetto exporter.

A live span also opens a ``jax.profiler.TraceAnnotation`` of its name, so
a run with both a telemetry sink and a profiler trace (``--telemetry-dir``
and ``--profile-dir``) shows ``descent/iter``, ``glm/lambda`` or
``serve/window`` on the profiler's own clock, beside the device operations.

Disabled fast path: with no active sink, ``span()`` returns one shared
module-level no-op context manager — no object allocation, no stack
touch, no clock read — so spans stay wired through production hot paths
unconditionally.
"""

from __future__ import annotations

import itertools
import threading
import time

from jax.profiler import TraceAnnotation

from photon_ml_tpu.obs import sink as _sink_mod

# span ids are process-unique; itertools.count is atomic under the GIL
_ids = itertools.count(1)
_tls = threading.local()


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class _NoopSpan:
    """Shared do-nothing context manager (the disabled-sink fast path)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP_SPAN = _NoopSpan()


class _Span:
    __slots__ = ("name", "attrs", "span_id", "parent_id", "t0", "start_unix",
                 "annotation")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        st = _stack()
        self.parent_id = st[-1].span_id if st else None
        self.span_id = next(_ids)
        st.append(self)
        self.annotation = TraceAnnotation(self.name)
        self.annotation.__enter__()
        self.start_unix = time.time()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self.t0
        self.annotation.__exit__(exc_type, exc, tb)
        st = _stack()
        # tolerate exotic unwind orders; normal exits pop the top
        if st and st[-1] is self:
            st.pop()
        elif self in st:
            st.remove(self)
        s = _sink_mod.active_sink()
        if s is not None:
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
    """A nested wall-clock span; a no-op singleton when telemetry is off."""
    if _sink_mod.active_sink() is None:
        return NOOP_SPAN
    return _Span(name, attrs)


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
