"""Analytic device-cost capture: per-executable XLA cost/memory analysis.

Every perf knob so far (groups-per-run, prefetch depth, compaction,
kernel dtype) shipped bitwise-parity-tested but BLIND — no
session has had a TPU attached, so no on-device cost number exists for any
of them. XLA's AOT surface closes the gap on any backend: for a jitted
callable, ``fn.lower(*args).compile()`` yields ``cost_analysis()`` (flops,
bytes-accessed) and ``memory_analysis()`` (argument/output/temp/peak
bytes) — hardware-independent ANALYTIC estimates on CPU, real HBM numbers
the moment a chip appears. This module captures those numbers once per
fresh executable and publishes them as schema-versioned
``executable_cost`` telemetry records plus ``devcost.*`` registry gauges,
so the dtype-ladder and groups-per-run sweeps can be compared
analytically today and gated in CI (``photon-ml-tpu report gate``).

Capture discipline (the whole point is to never touch the hot path):

- **Cache-miss only.** A process-wide seen-set keyed by ``(label, knob
  tuple, argument signature)`` mirrors the jit caches it shadows: the
  knob tuple is the retune surface (dtype rung, groups-per-run, …) and the signature is tree structure + shape/dtype
  of every array leaf + repr of every static. A repeat call emits
  NOTHING and costs one tree flatten + the signature-tuple build + a
  set lookup (the knob snapshot is memoized on its raw env/global
  inputs — see ``_knob_items``).
- **Never under a trace.** Wired-through boundaries are called with
  tracers from outer jits/vmaps; any tracer leaf skips capture (the
  enclosing executable is captured at ITS boundary instead).
- **Gated.** Capture runs when a telemetry sink is active, or when
  ``PHOTON_DEVCOST=1`` forces it sink-less (registry gauges only — how
  ``bench.py --quick`` gets cost numbers into its JSON contract).
  ``PHOTON_DEVCOST=0`` forces it off. Cost: the AOT ``lower().compile()``
  is a SECOND compile of the executable (jax exposes no way to reach the
  jit cache's compiled object, and routing production calls through the
  AOT executable would sidestep the dispatch path the bitwise-parity
  tests pin down) — paid once per fresh executable, only on
  capture-enabled runs, recorded honestly as ``capture_s`` in the record
  and the ``devcost.capture_s`` timer. The tier-1 suite pins capture off
  (conftest) for exactly this reason.
- **Never fatal.** Every capture is wrapped; a failure increments
  ``devcost.capture_errors`` and the run proceeds.

The companion samplers here — ``sample_hbm_watermarks`` (called by the
span layer at every root-span exit) and ``record_hbm_budget`` (called by
``ops/streaming.device_hbm_budget_bytes``) — put the RUNTIME memory axis
next to the analytic one: ``bytes_in_use``/``peak_bytes_in_use`` from
``device.memory_stats()`` where the backend exposes them, and an explicit
``available: false`` record where it does not (CPU), so a report reader
can tell "no pressure" from "no instrument".
"""

from __future__ import annotations

import functools
import hashlib
import os
import threading
import time
from typing import Any

from photon_ml_tpu.obs import metrics as _metrics
from photon_ml_tpu.obs import sink as _sink_mod

COST_SCHEMA_VERSION = 1

_lock = threading.Lock()
_seen: set[tuple] = set()
_wrapped: dict = {}
# one-time-per-sink emission guards (a reconfigured sink is a new run and
# gets its own budget/unavailability records)
_budget_sink: Any = None
_wm_unavailable_sink: Any = None
# watermark sampling floor: root spans include per-chunk prefetch-worker
# spans, and memory watermarks at sub-second cadence are noise, not signal
_WM_MIN_INTERVAL_S = 0.5
_last_wm_sample = [float("-inf")]


def reset() -> None:
    """Forget captured executables and one-time emission state (tests)."""
    global _budget_sink, _wm_unavailable_sink
    with _lock:
        _seen.clear()
        _label_totals.clear()
        _budget_sink = None
        _wm_unavailable_sink = None
        _last_wm_sample[0] = float("-inf")


_warned_bad_env = [False]


def capture_enabled() -> bool:
    """Capture gate: ``PHOTON_DEVCOST`` wins (int parse — ``1`` forces
    on sink-less, ``0`` forces off), else capture exactly when a
    telemetry sink is active. Unlike the sibling RETUNE knobs (which
    change MATH and fail strict), a malformed value here degrades to
    capture-off with one warning: this check sits on every wired
    production boundary, and observability misconfiguration must never
    take down the run it observes."""
    env = os.environ.get("PHOTON_DEVCOST")
    if env is not None and env != "":
        try:
            return bool(int(env))
        except ValueError:
            if not _warned_bad_env[0]:
                # lint: waive(conc-unlocked-mutation) benign-race once-flag: worst case is a duplicate warning
                _warned_bad_env[0] = True
                import warnings

                warnings.warn(
                    f"PHOTON_DEVCOST={env!r} is not an int; device-cost "
                    f"capture disabled (use 1/0)",
                    stacklevel=2,
                )
            return False
    return _sink_mod.is_active()


# knob-snapshot memo: ``sink._knob_snapshot`` costs ~30 us (module
# imports, call-time knob readers, strict dtype validation) — too much
# for capture()'s REPEAT path, which runs per eager kernel/scoring call
# while a sink is active. The snapshot is a pure function of the raw env
# vars + module globals below, so it memoizes exactly on them (no TTL —
# a knob flip invalidates immediately). A knob added to _knob_snapshot
# must be added here too; the failure mode of forgetting is one missed
# re-capture on a mid-process flip of only that knob, never a wrong
# number. That wiring is no longer a memory exercise: the lint knob pass
# (photon_ml_tpu/analysis, code knob-devcost-missing) parses this
# function and fails when a snapshot-carried knob is not fingerprinted.
_knob_memo: list = []  # [raw_fingerprint, knobs_dict, sorted_items_tuple]


def _knob_raw_state() -> tuple:
    env = os.environ
    import photon_ml_tpu.ops.prefetch as pf
    import photon_ml_tpu.ops.sparse_tiled as st

    try:
        import sys

        re_mod = sys.modules.get("photon_ml_tpu.game.random_effect")
        re_state = (
            None if re_mod is None
            else (re_mod.COMPACT_EVERY, re_mod.FUSE_BUCKETS,
                  re_mod.RE_COMBINE)
        )
    except Exception:
        re_state = None
    try:
        import sys

        pl_mod = sys.modules.get("photon_ml_tpu.parallel.placement")
        shard_state = (
            None if pl_mod is None
            else (pl_mod.RE_SHARD, pl_mod.RE_SPLIT,
                  pl_mod.REPLAN_IMBALANCE, pl_mod.RE_DEVICE_SPLIT,
                  pl_mod.RE_SPLIT_WEIGHT)
        )
    except Exception:
        shard_state = None
    try:
        import sys

        pj_mod = sys.modules.get("photon_ml_tpu.game.projector")
        project_state = (
            None if pj_mod is None
            else (pj_mod.RE_PROJECT, pj_mod.RE_PROJECT_DIM)
        )
    except Exception:
        project_state = None
    try:
        import sys

        im_mod = sys.modules.get("photon_ml_tpu.data.index_map")
        fe_state = (
            None if im_mod is None
            else (im_mod.FE_SHARD, im_mod.FE_SPLIT_WEIGHT)
        )
    except Exception:
        fe_state = None
    try:
        import sys

        sv_store = sys.modules.get("photon_ml_tpu.serve.store")
        sv_router = sys.modules.get("photon_ml_tpu.serve.router")
        sv_refresh = sys.modules.get("photon_ml_tpu.serve.refresh")
        serve_state = (
            None if sv_store is None else sv_store.SERVE_HOT_BYTES,
            None if sv_router is None
            else (sv_router.SERVE_MAX_BATCH, sv_router.SERVE_MAX_WAIT_MS),
            None if sv_refresh is None else sv_refresh.SERVE_REFRESH_EVERY,
        )
    except Exception:
        serve_state = None
    try:
        import sys

        se_mod = sys.modules.get("photon_ml_tpu.ops.stream_executor")
        stream_state = (
            None if se_mod is None
            else (se_mod.STREAM_EXECUTOR, se_mod.STREAM_PRIORITY,
                  se_mod.STREAM_SHARE)
        )
    except Exception:
        stream_state = None
    return (
        env.get("PHOTON_STREAM_EXECUTOR"),
        env.get("PHOTON_STREAM_PRIORITY"),
        env.get("PHOTON_STREAM_SHARE"),
        env.get("PHOTON_SERVE_HOT_BYTES"),
        env.get("PHOTON_SERVE_MAX_BATCH"),
        env.get("PHOTON_SERVE_MAX_WAIT_MS"),
        env.get("PHOTON_SERVE_REFRESH_EVERY"),
        env.get("PHOTON_PREFETCH_DEPTH"),
        env.get("PHOTON_CHUNK_CACHE_BUDGET"),
        env.get("PHOTON_KERNEL_DTYPE"),
        env.get("PHOTON_RE_COMPACT_EVERY"),
        env.get("PHOTON_RE_FUSE_BUCKETS"),
        env.get("PHOTON_RE_COMBINE"),
        env.get("PHOTON_RE_PROJECT"),
        env.get("PHOTON_RE_PROJECT_DIM"),
        env.get("PHOTON_RE_SHARD"),
        env.get("PHOTON_RE_SPLIT"),
        env.get("PHOTON_RE_REPLAN_IMBALANCE"),
        env.get("PHOTON_RE_DEVICE_SPLIT"),
        env.get("PHOTON_RE_SPLIT_WEIGHT"),
        env.get("PHOTON_FE_SHARD"),
        env.get("PHOTON_FE_SPLIT_WEIGHT"),
        pf.PREFETCH_DEPTH, pf.CHUNK_CACHE_BUDGET,
        len(pf._device_budget_memo),
        st.GROUPS_PER_STEP, st.SEGMENTS_PER_DMA,
        st.GROUPS_PER_RUN, st.KERNEL_DTYPE,
        re_state,
        shard_state,
        project_state,
        fe_state,
        serve_state,
        stream_state,
    )


def _knob_items() -> tuple:
    """The knob snapshot as a sorted item tuple (the hashable half of
    every capture key), memoized on the raw knob inputs."""
    fp = _knob_raw_state()
    memo = _knob_memo
    if memo and memo[0] == fp:
        return memo[2]
    knobs = _sink_mod._knob_snapshot()
    items = tuple(sorted(knobs.items()))
    # lint: waive(conc-unlocked-mutation) deliberately lock-free memo: sits on capture()'s repeat path; a racing rewrite recomputes the same value
    _knob_memo[:] = [fp, knobs, items]
    return items


def knob_key() -> dict:
    """The retune surface an executable was compiled under — the same
    knob snapshot a run's ``run_start`` records (dtype rung,
    groups-per-run, prefetch depth, compaction knobs), so cost records
    key by CONFIGURATION, not by luck."""
    return dict(_knob_items())


def _leaf_descriptors(leaves) -> tuple:
    """Hashable per-leaf signature: shape/dtype for arrays, repr for
    statics — the same information the jit cache keys on. A plain tuple,
    not a digest: tuple hashing is what the repeat (cache-hit) path
    pays, and it must stay cheap (the treedef rides the key directly —
    PyTreeDef is hashable — so structure needs no stringification)."""
    parts = []
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            parts.append(f"{tuple(leaf.shape)}:{leaf.dtype}")
        else:
            parts.append(repr(leaf))
    return tuple(parts)


def _analyze(compiled) -> tuple[float, float, dict, int | None, bool]:
    """Normalize one ``Compiled``'s analyses across jax versions/backends.
    Returns (flops, bytes_accessed, memory dict, peak_bytes,
    peak_is_estimate). ``cost_analysis`` may be a per-device list; TPU
    exposes a true ``peak_memory_in_bytes`` while CPU only itemizes
    argument/output/temp — there the peak is estimated as their sum and
    flagged, so a reader never mistakes an estimate for a measurement."""
    cost: dict = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        cost = dict(ca or {})
    except Exception:
        pass
    flops = float(cost.get("flops", 0.0) or 0.0)
    bytes_accessed = float(cost.get("bytes accessed", 0.0) or 0.0)
    mem: dict = {}
    try:
        ma = compiled.memory_analysis()
        for k in (
            "generated_code_size_in_bytes",
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "alias_size_in_bytes",
            "temp_size_in_bytes",
            "peak_memory_in_bytes",
        ):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)
    except Exception:
        pass
    peak = mem.get("peak_memory_in_bytes")
    peak_is_estimate = False
    if peak is None and mem:
        peak = (
            mem.get("argument_size_in_bytes", 0)
            + mem.get("output_size_in_bytes", 0)
            + mem.get("temp_size_in_bytes", 0)
        )
        peak_is_estimate = True
    return flops, bytes_accessed, mem, peak, peak_is_estimate


def capture(
    label: str,
    fn: Any,
    args: tuple = (),
    kwargs: dict | None = None,
    **extra,
) -> dict | None:
    """Capture ``fn``'s executable cost for ``(args, kwargs)`` if this
    (label, knob tuple, signature) has not been captured before. ``fn``
    must be a jitted callable (``.lower``); call BEFORE (or after — the
    AOT path is independent) invoking it. Returns the record, or None
    when disabled / already seen / called under a trace / on any
    analysis failure."""
    if not capture_enabled():
        return None
    try:
        import jax
        from jax.core import Tracer

        kwargs = kwargs or {}
        leaves, treedef = jax.tree.flatten((args, kwargs))
        if any(isinstance(leaf, Tracer) for leaf in leaves):
            return None
        sig_tuple = _leaf_descriptors(leaves)
        key = (label, _knob_items(), treedef, sig_tuple)
        with _lock:
            if key in _seen:
                return None
            # mark BEFORE compiling: a failing capture must not re-pay
            # the AOT compile on every subsequent call
            _seen.add(key)
        # miss path only from here: materialize the knob dict and the
        # short record-only digest (a readable dedup tag in the JSONL)
        knobs = knob_key()
        sig = hashlib.sha256(
            "|".join(sig_tuple).encode()
        ).hexdigest()[:16]
        t0 = time.perf_counter()
        compiled = fn.lower(*args, **kwargs).compile()
        capture_s = time.perf_counter() - t0
        flops, bytes_accessed, mem, peak, peak_est = _analyze(compiled)
        record = {
            "event": "executable_cost",
            "cost_schema_version": COST_SCHEMA_VERSION,
            "label": label,
            "knobs": knobs,
            "arg_sig": sig,
            "flops": flops,
            "bytes_accessed": bytes_accessed,
            "arith_intensity": (
                flops / bytes_accessed if bytes_accessed else None
            ),
            "memory": mem,
            "peak_bytes": peak,
            "peak_is_estimate": peak_est,
            "capture_s": capture_s,
        }
        record.update(extra)
        _publish(record)
        return record
    except Exception:
        try:
            _metrics.REGISTRY.counter_inc("devcost.capture_errors")
        except Exception:
            pass
        return None


# per-label running totals behind the devcost.<label>.* gauges: one label
# can capture several executables (the compaction loop's shrinking fronts,
# several chunk geometries), and a last-write-wins gauge would show only
# the LAST one — blinding the bench-JSON gate path to every earlier
# executable. The gauges therefore carry the SUM of flops/bytes and the
# MAX peak across the label's captures, the same aggregation the
# telemetry-JSONL summarize path applies.
_label_totals: dict[str, list] = {}


def _publish(record: dict) -> None:
    reg = _metrics.REGISTRY
    label = record["label"]
    reg.counter_inc("devcost.captures")
    reg.timer_add("devcost.capture_s", record["capture_s"])
    with _lock:
        tot = _label_totals.setdefault(label, [0.0, 0.0, 0])
        tot[0] += record["flops"]
        tot[1] += record["bytes_accessed"]
        if record["peak_bytes"] is not None:
            tot[2] = max(tot[2], record["peak_bytes"])
        flops_t, bytes_t, peak_t = tot
    reg.gauge_set(f"devcost.{label}.flops", flops_t)
    reg.gauge_set(f"devcost.{label}.bytes_accessed", bytes_t)
    if peak_t:
        reg.gauge_set(f"devcost.{label}.peak_bytes", peak_t)
    from photon_ml_tpu.obs.spans import emit_event

    emit_event(
        "executable_cost",
        **{k: v for k, v in record.items() if k != "event"},
    )


def captured(label_prefix: str, fn: Any) -> Any:
    """A capture-instrumented twin of a jitted callable, MEMOIZED so the
    returned object is identity-stable: callers use these as jit STATIC
    keys (``minimize_fn``/``init_fn`` in ``game/random_effect``), and a
    fresh wrapper per selector call would poison every such cache into
    recompiling. Non-lowerable callables (the host-driven solver twins)
    are returned unchanged."""
    if not hasattr(fn, "lower"):
        return fn
    key = (label_prefix, fn)
    with _lock:
        wrapper = _wrapped.get(key)
    if wrapper is not None:
        return wrapper
    label = f"{label_prefix}.{getattr(fn, '__name__', 'fn')}"

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        capture(label, fn, args, kwargs)
        return fn(*args, **kwargs)

    with _lock:
        # a racing construction keeps the FIRST wrapper (identity must be
        # stable for the process lifetime)
        wrapper = _wrapped.setdefault(key, wrapper)
    return wrapper


# -- runtime memory axis: HBM budget + watermarks ---------------------------


def record_hbm_budget(budget_bytes: float, queried: bool) -> None:
    """Called by ``ops/streaming.device_hbm_budget_bytes`` on every query:
    gauges always (the bench telemetry block reads them), plus ONE
    ``hbm_budget`` event per sink naming which source won — a run on a
    memory-stats-less backend (CPU: ``fallback_default``) is
    distinguishable from a device-quoted one in ``report`` output."""
    global _budget_sink
    try:
        reg = _metrics.REGISTRY
        reg.gauge_set("hbm.budget_bytes", float(budget_bytes))
        reg.gauge_set("hbm.budget_queried", 1.0 if queried else 0.0)
        s = _sink_mod.active_sink()
        if s is not None and s is not _budget_sink:
            _budget_sink = s
            from photon_ml_tpu.obs.spans import emit_event

            emit_event(
                "hbm_budget",
                budget_bytes=float(budget_bytes),
                source="device_memory_stats" if queried else
                       "fallback_default",
            )
    except Exception:
        pass


def sample_hbm_watermarks(root_span: str | None = None) -> dict | None:
    """Sample ``device.memory_stats()`` watermarks across local devices —
    called at every root-span exit while a sink is active (root spans are
    per-fit/per-driver, so this is off the hot path by construction).
    Emits one ``hbm_watermark`` record (``available: false`` ONCE per
    sink on backends without memory stats) and keeps max-across-devices
    gauges; returns the record, or None when nothing was sampled.

    Rate-limited: prefetch WORKER spans are roots in their own threads
    (per-chunk cadence), so samples closer than ``_WM_MIN_INTERVAL_S``
    to the previous one are skipped — ``peak_bytes_in_use`` is a
    process-cumulative watermark, so a skipped sample loses only
    instantaneous ``bytes_in_use`` granularity, never the peak."""
    global _wm_unavailable_sink
    s = _sink_mod.active_sink()
    now = time.monotonic()
    with _lock:
        if now - _last_wm_sample[0] < _WM_MIN_INTERVAL_S:
            return None
        _last_wm_sample[0] = now
    try:
        import jax

        per_device = []
        for d in jax.local_devices():
            stats = None
            try:
                stats = d.memory_stats()
            except Exception:
                pass
            if stats:
                per_device.append(
                    {
                        "device": str(d.id),
                        "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                        "peak_bytes_in_use": int(
                            stats.get("peak_bytes_in_use", 0)
                        ),
                        "bytes_limit": int(stats.get("bytes_limit", 0)),
                    }
                )
        from photon_ml_tpu.obs.spans import emit_event

        if not per_device:
            if s is not None and s is not _wm_unavailable_sink:
                _wm_unavailable_sink = s
                rec = {"available": False, "root_span": root_span}
                emit_event("hbm_watermark", **rec)
                return rec
            return None
        reg = _metrics.REGISTRY
        in_use = max(d["bytes_in_use"] for d in per_device)
        peak = max(d["peak_bytes_in_use"] for d in per_device)
        reg.gauge_set("hbm.bytes_in_use", float(in_use))
        reg.gauge_set("hbm.peak_bytes_in_use", float(peak))
        rec = {
            "available": True,
            "root_span": root_span,
            "bytes_in_use": in_use,
            "peak_bytes_in_use": peak,
            "devices": per_device,
        }
        if s is not None:
            emit_event("hbm_watermark", **rec)
        return rec
    except Exception:
        return None


# -- host-side layout-pack accounting (tile_cache misses) -------------------


def record_layout_pack(nbytes: int, chunks: int) -> None:
    """Called by ``ops/tile_cache`` on a layout-cache MISS: the packed
    tile-COO streams are the kernel's HBM traffic, so the per-knob packed
    byte total is the analytic half of the dtype ladder's bytes-moved
    claim (f32 12 B/nnz → int8 4) — published next to the
    executable costs and rendered in the same roofline table."""
    try:
        reg = _metrics.REGISTRY
        reg.counter_inc("devcost.tile_layout.packs")
        reg.counter_inc("devcost.tile_layout.packed_bytes_total", nbytes)
        reg.gauge_set("devcost.tile_layout.packed_bytes", float(nbytes))
        if _sink_mod.is_active():
            from photon_ml_tpu.obs.spans import emit_event

            emit_event(
                "tile_layout_pack",
                nbytes=int(nbytes),
                chunks=int(chunks),
                knobs=knob_key(),
            )
    except Exception:
        pass
