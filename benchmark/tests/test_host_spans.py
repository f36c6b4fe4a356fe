"""The program's host spans as the benchmark reads them
(``benchmark/host_spans.py``): the window's table on a small recorded trace
(``data/small_cpu_host_spans.xplane.pb``, see
``record_host_span_trace.py``), the set-up readers through a tiny cell, and
what a reader gives where the program has no names."""

import copy
import json
import os
import time
from types import SimpleNamespace

import jax
import pytest

import test_manifest
from benchmark import harness, host_spans, trace_reduce
from record_host_span_trace import PROGRAM_SPANS
from test_runners import _shrink_fit_sparse, _shrink_glmix

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small_cpu_host_spans.xplane.pb")
NEW_METRICS = (
    "setup.program_s", "setup.outside_program_s", "setup.unnamed_s",
    "setup.trace_lower_s", "setup.cache_load_s", "setup.game_batch_s",
    "setup.re_prepare_s", "layout.to_host_s", "layout.head_s", "layout.merge_s",
    "layout.pack_s", "layout.stage_s", "descent.idle_launch_s_per_iter",
    "descent.idle_collect_s_per_iter", "descent.idle_outside_s_per_iter",
    "layout.fingerprint_s",
)


@pytest.fixture(scope="module")
def table():
    return host_spans._window(TRACE, frozenset(PROGRAM_SPANS))


def test_the_slice_by_program_span(table):
    spans = table["spans"]
    assert set(spans) == set(PROGRAM_SPANS) | {host_spans.OUTSIDE}
    # three units, every span once a unit; the harness's own spans
    # (descent.run, fence) are not the program's
    assert all(spans[name][0] == 3 for name in PROGRAM_SPANS)
    assert spans["descent/prepare"][1] > 3 * 0.004  # the recorded pauses
    assert spans["descent/collect"][1] > 3 * 0.003
    inside = sum(spans[n][1] for n in PROGRAM_SPANS if n != "descent/run")
    assert inside <= spans["descent/run"][1] <= table["window"]


def test_idle_seconds_add_up_to_the_devices_idle(table):
    reduced = trace_reduce.reduce_trace(
        TRACE, slice_span=harness.SLICE_SPAN, devices=1, spans=("descent.run", "fence")
    )
    idle = reduced.window_s - reduced.busy_s
    assert table["window"] == pytest.approx(reduced.window_s)
    assert table["idle"] == pytest.approx(idle, rel=1e-9)
    for column in (2, 3):  # by midpoint, and cut at the spans' edges
        assert sum(row[column] for row in table["spans"].values()) == pytest.approx(
            idle, rel=1e-9
        )
    # the harness's labels and the program's split the same gaps
    assert table["spans"][host_spans.OUTSIDE][2] == pytest.approx(
        reduced.idle_by_span["(no span)"], rel=1e-9
    )
    # cut at the edges, the 4 ms under descent/prepare are the device's
    # idle time, all of them; by midpoint a gap that runs from one unit's
    # collection to the next one's launch is booked whole to one label
    rows = table["spans"]
    assert rows["descent/prepare"][3] == pytest.approx(rows["descent/prepare"][1])
    assert rows["descent/collect"][3] > 3 * 0.003
    assert rows[host_spans.OUTSIDE][3] > 2 * 0.005


def test_the_three_parts_of_a_descents_idle_time(table, monkeypatch):
    names = SimpleNamespace(DESCENT_COLLECT="descent/collect")
    monkeypatch.setattr(host_spans, "program_spans", lambda: names)
    monkeypatch.setattr(host_spans, "window", lambda: table)
    obs = SimpleNamespace(counters={"work": 6.0})
    parts = [
        host_spans.idle_per_work(obs, choose)
        for choose in (host_spans.is_launch, host_spans.is_collect, host_spans.is_outside)
    ]
    assert sum(parts) == pytest.approx(table["idle"] / 6.0, rel=1e-9)
    assert parts[1] == pytest.approx(table["spans"]["descent/collect"][2] / 6.0)
    assert parts[0] == pytest.approx(
        sum(table["spans"][n][2] for n in PROGRAM_SPANS if n != "descent/collect") / 6.0
    )
    assert host_spans.idle_per_work(SimpleNamespace(counters={}), host_spans.is_launch) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_reader_finds_nothing_where_the_program_has_no_names(metric, monkeypatch):
    """A parent commit under this benchmark: no ``obs/spans.py`` with names,
    so every reader returns None and the harness leaves the metric out."""
    monkeypatch.setattr(host_spans, "program_spans", lambda: None)
    obs = SimpleNamespace(counters={"work": 2.0, "setup_s": 10.0}, trace=None, chips=1)
    assert harness.layer_reader(metric)(obs) is None


def test_a_module_without_the_names_is_no_program_with_spans(monkeypatch):
    import importlib

    old = SimpleNamespace(span=lambda name, **kw: None, NOOP_SPAN=object())
    monkeypatch.setattr(importlib, "import_module", lambda name: old)
    assert host_spans.program_spans() is None


def test_the_manifest_holds_the_new_metrics_last():
    manifest = harness.load_manifest()
    names = [m["name"] for m in manifest["per_layer"]]
    assert tuple(names[-len(NEW_METRICS):]) == NEW_METRICS
    cells = [w["name"] for w in manifest["workloads"]]
    for m in manifest["per_layer"][-len(NEW_METRICS):]:
        assert m["source"] == "program_span" and m["unit"] == "s"
        assert m["better"] == "lower" and set(m["workloads"]) <= set(cells)
        descent = m["name"].startswith("descent.idle")
        assert m["moves"] == ("descent_iter_s" if descent else "setup_s")
    # and test_manifest.py's own checks pass with them
    test_manifest.test_top_level_keys_and_limits(manifest)
    test_manifest.test_names_are_plain_and_used_once(manifest)
    test_manifest.test_metrics_follow_the_contract(manifest)
    test_manifest.test_every_cell_reports_enough(manifest)


# -- through a tiny cell ---------------------------------------------------------

def _shrink_sparse_re(cfg):
    from test_sparse_re import _tiny

    cfg.clear()
    cfg.update(_tiny().config)


SHRINK = {
    "ml20m_descent": _shrink_glmix, "rcv1_fit": _shrink_fit_sparse,
    "sparse_re_descent": _shrink_sparse_re,
}


@pytest.fixture
def small_tiles(monkeypatch):
    import photon_ml_tpu.ops.sparse_tiled as st
    import photon_ml_tpu.ops.streaming as streaming
    from photon_ml_tpu.ops import tile_cache

    monkeypatch.setattr(st, "GROUPS_PER_STEP", 8)
    monkeypatch.setattr(st, "SEGMENTS_PER_DMA", 2)
    monkeypatch.setattr(streaming, "device_hbm_budget_bytes", lambda *a, **k: 1e6)
    tile_cache.clear()


def _traced(workload):
    """One traced run of the cell, tiny, in a registry that starts empty as
    a benchmark process's does; the result, and the registry's span timers
    after it."""
    from photon_ml_tpu.obs import spans
    from photon_ml_tpu.obs.metrics import REGISTRY

    for prefix in ("span", "jax."):
        REGISTRY.reset_timers(prefix)
    host_spans._log_setup.cache_clear()
    host_spans._window.cache_clear()
    resolved = copy.deepcopy(harness.resolve(harness.load_manifest(), workload))
    SHRINK[workload](resolved.config)
    resolved.traffic.update(trace_slice_s=0.3)
    logs = []
    out = harness.run_cell(
        resolved, seed=5, seconds=0.5, trace=True,
        devices=jax.devices()[: resolved.chips], t_start=time.perf_counter(),
        log=logs.append,
    )
    json.dumps(out)
    assert out["correct"] is True, logs
    return out, spans, REGISTRY.timer_snapshot("span")


def _value(out, name):
    return out["metrics"][name]["value"]


@pytest.mark.parametrize("workload", ["ml20m_descent", "rcv1_fit", "sparse_re_descent"])
def test_set_up_by_part_through_a_tiny_cell(workload, small_tiles):
    out, spans, after = _traced(workload)
    listed = {
        m["name"] for m in harness.resolve(harness.load_manifest(), workload).per_layer
    }
    assert set(NEW_METRICS) & listed <= set(out["metrics"])
    program, unnamed = _value(out, "setup.program_s"), _value(out, "setup.unnamed_s")
    # the harness's set-up wall is outside + inside, and nothing is negative
    # (t_start is this test's, so "outside" is the runner's data alone)
    assert 0 < program and 0 <= unnamed <= program
    assert 0 < _value(out, "setup.outside_program_s")
    # the compile pipeline's steps fell inside the program's spans
    assert 0 < _value(out, "setup.trace_lower_s") < program
    assert _value(out, "setup.cache_load_s") >= 0
    base = spans.session_baseline()
    if workload == "rcv1_fit":
        phases = sum(
            _value(out, f"layout.{p}_s")
            for p in ("to_host", "fingerprint", "head", "merge", "pack", "stage")
        )
        whole = base[spans.TIMER + spans.LAYOUT_OPTIMIZE]["seconds"]
        assert 0 < phases <= whole <= _value(out, "layout.build_s")
        assert base[spans.TOP_TIMER + spans.GLM_TRAIN]["calls"] == 1  # the warm-up
    else:
        assert 0 < _value(out, "setup.game_batch_s") < program
        assert 0 < _value(out, "setup.re_prepare_s") < program
        assert base[spans.TOP_TIMER + spans.DESCENT_RUN]["calls"] == 1
        parts = sum(
            _value(out, f"descent.idle_{p}_s_per_iter")
            for p in ("launch", "collect", "outside")
        )
        # they add up to descent.device_idle_share x window / work; a unit
        # is one run of 2 outer iterations, and the table was read once
        assert host_spans._window.cache_info().currsize == 1
        runs = host_spans.window()["spans"][spans.DESCENT_RUN][0]
        share = _value(out, "descent.device_idle_share") / 100.0
        assert parts == pytest.approx(
            share * out["device"]["window_s"] / (2 * runs), rel=0.02
        )


@pytest.mark.parametrize("workload,again", [
    ("ml20m_descent", 0), ("rcv1_fit", 0), ("sparse_re_descent", 1),
])
def test_what_the_check_adds_to_the_timers(workload, again, small_tiles):
    """Set-up is read from the registry as it stood when the slice began,
    so it holds neither the window nor the check. In most cells the check
    runs nothing of the program and total - window would do as well; the
    sparse descent's check runs ``CoordinateDescent.run`` once more (a run
    one iteration shorter), after the window."""
    out, spans, after = _traced(workload)
    base = spans.session_baseline()
    in_slice = host_spans.window()["spans"]
    entry = spans.GLM_TRAIN if workload == "rcv1_fit" else spans.DESCENT_RUN
    for timer, now in after.items():
        if not timer.startswith(spans.TIMER):
            continue
        name = timer[len(spans.TIMER):]
        before = base.get(timer, {"calls": 0})["calls"]
        # spans cut by the slice's edges do not exist here: units are whole
        window = in_slice.get(name, [0])[0]
        # the shorter run: one more descent/run, and one launch's three steps
        expected = again if name.startswith("descent/") else 0
        assert now["calls"] - before - window == expected, (timer, now, before, window)
    assert base[spans.TIMER + entry]["calls"] == 1
