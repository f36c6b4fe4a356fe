"""The benchmark's harness: one cell, one run, one result line.

Driven by data. A cell of ``BENCHMARK.json`` names a configuration and a
traffic mix; the configuration's ``file`` holds its sizes; the traffic file
``benchmark/traffic/<traffic>.json`` holds the mix's parameters and names a
``kind``, which is the runner ``benchmark/runners/<kind>.py``; a per-layer
metric ``<name>`` is read by ``benchmark/layer_metrics/<name>.py``. Nothing
here knows a cell, a configuration or a metric by name: a later PR adds
files and manifest entries and edits nothing.

A runner is a module with ``setup(cell) -> state``, ``unit(state) -> out``
(one measured unit of work, fenced), ``account(state, out) -> counters``
(outside the timing; must hold ``work``, the units of the end-to-end metric
this unit stands for), ``facts(state)``, ``shape(state)`` and
``check(state)`` (against the plain reference, after the window).

A run: set-up (data from the seed, the program's own preparation, one
warm-up unit, which compiles), then units until ``seconds`` have passed,
then the check. The end-to-end metric a traffic file names is the median
over the window's units of seconds per unit of work. With ``trace`` the
window is a short profiled slice instead and the per-layer metrics are
read from it; its timings are never reported as end-to-end metrics.
"""

from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import os
import shutil
import statistics
import sys
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
# where a traced run leaves its profile: inside the checkout, git-ignored
TRACE_DIR = os.path.join(ROOT, "chiprun_out", "benchmark_trace")
SLICE_SPAN = "bench.slice"


def load_manifest(path: str = MANIFEST) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve(manifest: dict, workload: str) -> SimpleNamespace:
    """The files one cell is made of, found by the names in the manifest."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    traffic = _load_json(os.path.join("benchmark", "traffic", cell["traffic"] + ".json"))
    metrics = {}
    for group in ("end_to_end", "per_layer"):
        metrics[group] = [
            m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]
        ]
    return SimpleNamespace(
        name=workload, chips=int(cell["chips"]),
        config=_load_json(config["file"]), traffic=traffic,
        runner_path=os.path.join(HERE, "runners", traffic["kind"] + ".py"),
        end_to_end=metrics["end_to_end"], per_layer=metrics["per_layer"],
    )


def load_runner(resolved):
    return _load_module(resolved.runner_path, "benchmark_runner_" + resolved.traffic["kind"])


def layer_reader(name: str):
    return _load_module(
        os.path.join(HERE, "layer_metrics", name + ".py"),
        "benchmark_layer_metric_" + name.replace(".", "_").replace("-", "_"),
    ).read


class JaxEvents:
    """Compile seconds, compile count and persistent-cache traffic from
    ``jax.monitoring`` (copied from ``chip_smoke.py``'s ``_JaxEvents``;
    listeners cannot be removed, so one instance lives per process)."""

    def __init__(self):
        from jax import monitoring

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, name: str, secs: float, **kw) -> None:
        if "backend_compile" in name:
            self.compile_s += secs
            self.compiles += 1


_EVENTS: JaxEvents | None = None


def jax_events() -> JaxEvents:
    global _EVENTS
    if _EVENTS is None:
        _EVENTS = JaxEvents()
    return _EVENTS


def device_memory(devices) -> tuple[int | None, int | None]:
    """(peak bytes on the fullest device, that device's limit); None where
    the backend reports no memory stats (the CPU backend).

    ``peak_bytes_in_use`` is the high-water mark of live BUFFERS. What a
    loaded program needs for its own scratch the backend reserves apart
    (``bytes_reserved``) and holds while the program stays loaded: on the
    v5e a descent that needs 13 GB to run reads 2.96 GB in use and 10.38 GB
    reserved (PR 22). So the peak is the larger of the buffers' mark and
    the buffers live now plus the most ever reserved."""
    peak = limit = None
    for d in devices:
        stats = d.memory_stats()
        if not stats:
            continue
        here = max(
            int(stats["peak_bytes_in_use"]),
            int(stats["bytes_in_use"]) + int(stats.get("peak_bytes_reserved", 0)),
        )
        if peak is None or here > peak:
            peak = here
            limit = int(stats.get("bytes_limit") or 0) or None
    return peak, limit


def _registry_counters() -> dict:
    """The program's always-on counters and timers, by name."""
    from photon_ml_tpu.obs.metrics import REGISTRY

    snap = REGISTRY.snapshot()
    flat = {k: float(v["value"]) for k, v in snap["counters"].items()}
    flat.update({k + ".seconds": float(v["seconds"]) for k, v in snap["timers"].items()})
    return flat


_SPANS: set[str] = set()


def _annotate(name: str):
    """A host span on the profiler's clock; its name is remembered so that
    the reduction can tell the harness's spans from the runtime's."""
    import jax

    _SPANS.add(name)
    return jax.profiler.TraceAnnotation(name)


def _start_trace() -> None:
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    os.makedirs(TRACE_DIR)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # device ops and the harness's spans only
    options.host_tracer_level = 2
    jax.profiler.start_trace(TRACE_DIR, profiler_options=options)


def _stop_trace() -> str:
    import jax

    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb"))
    if not found:
        raise RuntimeError(f"the profiler left no trace under {TRACE_DIR}")
    return found[0]


def run_cell(resolved, *, seed: int, seconds: float, trace: bool, devices,
             t_start: float, log=lambda msg: None) -> dict:
    """Run one cell on ``devices`` and return the result object (the last
    line of ``run.py``'s output). ``t_start`` is the process's start on
    ``time.perf_counter()``'s clock: set-up is counted from there."""
    import jax

    from benchmark import trace_reduce

    runner = load_runner(resolved)
    events = jax_events()
    cell = SimpleNamespace(
        config=resolved.config, traffic=resolved.traffic, seed=int(seed),
        devices=list(devices), annotate=_annotate,
    )
    state = runner.setup(cell)
    log(f"set-up facts: {json.dumps(runner.facts(state))}; peak device "
        f"bytes so far {device_memory(cell.devices)[0]}")
    warm = runner.account(state, runner.unit(state))  # compiles
    setup_s = time.perf_counter() - t_start
    setup_compile_s, compiles_before = events.compile_s, events.compiles
    log(f"warm-up unit: {json.dumps(warm)}; set-up {setup_s:.2f} s, "
        f"compile {setup_compile_s:.2f} s, cache hits {events.cache_hits} "
        f"misses {events.cache_misses}; peak device bytes so far "
        f"{device_memory(cell.devices)[0]}")

    window = float(seconds)
    if trace:
        # a slice of a few seconds, and whole units
        window = min(window, float(resolved.traffic.get("trace_slice_s", 4.0)))
        _start_trace()
    registry_before = _registry_counters()
    per_work, counters = [], {}
    with contextlib.ExitStack() as stack:
        if trace:
            stack.enter_context(_annotate(SLICE_SPAN))
        t_window = time.perf_counter()
        while not per_work or time.perf_counter() - t_window < window:
            t0 = time.perf_counter()
            out = runner.unit(state)
            dt = time.perf_counter() - t0
            counted = runner.account(state, out)
            per_work.append(dt / counted["work"])
            for k, v in counted.items():
                counters[k] = counters.get(k, 0.0) + float(v)
        window_s = time.perf_counter() - t_window
    trace_path = _stop_trace() if trace else None
    compiles_in_window = events.compiles - compiles_before
    registry_after = _registry_counters()

    peak, limit = device_memory(cell.devices)  # before the check's own arrays
    log(f"memory: peak {peak} of {limit}; backend stats of the first device "
        f"{json.dumps(cell.devices[0].memory_stats())}")
    verdict = runner.check(state)
    log(f"check: {json.dumps(verdict)}")

    counters.update(runner.facts(state))
    counters.update({
        "units": float(len(per_work)),
        "window_s": window_s,
        "setup_s": setup_s,
        "setup.compile_s": setup_compile_s,
        "compile.in_window": float(compiles_in_window),
    })
    if peak is not None:
        counters["device.peak_hbm_bytes"] = float(peak)
    if limit is not None:
        counters["device.hbm_bytes_limit"] = float(limit)
    for k, v in registry_after.items():
        counters["registry." + k] = v - registry_before.get(k, 0.0)

    first = cell.devices[0]
    device = {
        "platform": first.platform, "kind": first.device_kind,
        "count": len(jax.devices()), "memory_peak_bytes": peak,
    }
    result = {
        "correct": bool(verdict["correct"]) and compiles_in_window == 0,
        "attempted": len(per_work),
        "failed": int(counters.get("failed", 0.0)),
    }
    if not trace:
        values = {
            resolved.traffic["metric"]: statistics.median(per_work),
            "setup_s": setup_s,
        }
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in resolved.end_to_end if m["name"] in values
        }
        result["device"] = device
        return result

    reduced = trace_reduce.reduce_trace(
        trace_path, slice_span=SLICE_SPAN, devices=len(cell.devices),
        spans=tuple(_SPANS - {SLICE_SPAN}),
    )
    log(f"trace: {json.dumps(reduced.summary())}")
    log("trace, what patterns search in the longest operations: " + json.dumps(
        [op.text[:240] for op in sorted(
            reduced.ops.values(), key=lambda o: -o.self_s)[:6]]
    ))
    obs = SimpleNamespace(
        counters=counters, trace=reduced, config=resolved.config,
        traffic=resolved.traffic, shape=runner.shape(state),
        device_kind=first.device_kind, chips=len(cell.devices),
    )
    reported = {m["name"] for m in resolved.end_to_end}
    metrics = {}
    for m in resolved.per_layer:
        if m["moves"] not in reported:
            continue
        value = layer_reader(m["name"])(obs)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result["metrics"] = metrics
    device["busy_s"] = reduced.busy_s
    device["window_s"] = reduced.window_s
    result["device"] = device
    result["breakdown"] = reduced.breakdown()
    return result


def emit(result: dict) -> None:
    """The contract's last line of standard output, and nothing after it."""
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
