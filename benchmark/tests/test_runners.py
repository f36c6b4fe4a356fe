"""The runners, tiny, through the harness on the CPU backend: every cell's
path end to end (set-up, warm-up, window, reference check, and the traced
run's per-layer readers) at sizes the configuration files do not have."""

import copy
import json
import os
import time

import jax
import pytest

from benchmark import harness


def _tiny(resolved, **traffic):
    r = copy.deepcopy(resolved)
    r.traffic.update(trace_slice_s=0.3, **traffic)
    return r


def _shrink_fit_sparse(cfg):
    cfg["features"].update(rows=2048, columns=4096, nonzeros_per_row=8, row_multiple=1)
    cfg["guarantees"]["grad_ratio_max"] = 0.9  # 20 iterations of a toy problem


def _shrink_fit_dense(cfg):
    cfg["features"].update(rows=4096, columns=128, generate_block_rows=256)


def _shrink_glmix(cfg):
    cfg.update(rows=6000, users=60, items=25)
    cfg["random_effects"]["userId"].update(entities=60, rows_floor=5)
    cfg["random_effects"]["itemId"].update(entities=25)
    cfg["guarantees"]["log_loss_ratio_max"] = 0.99


SHRINK = {
    "rcv1_fit": _shrink_fit_sparse, "dense_dp4_fit": _shrink_fit_dense,
    "ml20m_descent": _shrink_glmix, "ml20m_fixed_only": _shrink_glmix,
}


@pytest.fixture
def small_tiles(monkeypatch):
    """The tile-COO kernels in interpret mode at a small DMA-step carve, and
    a memory budget under which even the toy matrix is tiled, not
    densified."""
    import photon_ml_tpu.ops.sparse_tiled as st
    import photon_ml_tpu.ops.streaming as streaming

    monkeypatch.setattr(st, "GROUPS_PER_STEP", 8)
    monkeypatch.setattr(st, "SEGMENTS_PER_DMA", 2)
    monkeypatch.setattr(streaming, "device_hbm_budget_bytes", lambda *a, **k: 1e6)


def _run(workload, trace):
    manifest = harness.load_manifest()
    resolved = _tiny(harness.resolve(manifest, workload))
    SHRINK[workload](resolved.config)
    logs = []
    out = harness.run_cell(
        resolved, seed=5, seconds=0.5, trace=trace,
        devices=jax.devices()[: resolved.chips], t_start=time.perf_counter(),
        log=logs.append,
    )
    json.dumps(out)  # the last line must serialise
    return resolved, out, logs


@pytest.mark.parametrize(
    "workload", ["rcv1_fit", "dense_dp4_fit", "ml20m_descent", "ml20m_fixed_only"]
)
def test_untraced_run_reports_the_end_to_end_metrics(workload, small_tiles):
    resolved, out, logs = _run(workload, trace=False)
    assert out["correct"] is True, logs
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in resolved.end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert set(out["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert "breakdown" not in out


@pytest.mark.parametrize(
    "workload", ["rcv1_fit", "dense_dp4_fit", "ml20m_descent", "ml20m_fixed_only"]
)
def test_traced_run_reports_the_per_layer_metrics(workload, small_tiles):
    resolved, out, logs = _run(workload, trace=True)
    assert out["correct"] is True, logs
    listed = {m["name"] for m in resolved.per_layer}
    assert set(out["metrics"]) <= listed
    # the CPU backend has no memory stats and no Pallas custom calls: those
    # readers find nothing and are left out; every other one reports
    absent = {"device.peak_hbm_bytes", "device.hbm_fill",
              "sparse_tiled_roofline", "fused_roofline"}
    assert listed - set(out["metrics"]) <= absent
    assert out["metrics"]["compile.in_window"]["value"] == 0
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]
    if workload == "rcv1_fit":
        assert out["metrics"]["layout.pad_ratio"]["value"] >= 1.0
    if workload == "ml20m_descent":
        assert 0 < out["metrics"]["re_solve.useful_lane_share"]["value"] <= 100
    if workload == "dense_dp4_fit":
        assert out["metrics"]["mesh.collective_time_share"]["value"] > 0


def test_the_same_seed_gives_the_same_fit(small_tiles):
    _, a, _ = _run("ml20m_fixed_only", trace=False)
    _, b, _ = _run("ml20m_fixed_only", trace=False)
    assert a["correct"] and b["correct"]


def test_a_wrong_answer_is_not_correct(small_tiles, monkeypatch):
    """The check is held to the reference: a scorer that is off by a
    constant fails it."""
    from benchmark.reference import glmix

    monkeypatch.setattr(glmix, "score", lambda f, r, _s=glmix.score: _s(f, r) + 0.01)
    _, out, _ = _run("ml20m_fixed_only", trace=False)
    assert out["correct"] is False


def test_run_py_refuses_without_a_tpu():
    import subprocess
    import sys

    done = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         "rcv1_fit", "--seed", "0", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=harness.ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert done.returncode != 0
    assert done.stdout.strip() == ""  # no result line
    assert "no CPU fallback" in done.stderr


def test_run_py_refuses_without_the_program(tmp_path):
    """Alone in a directory with only ``BENCHMARK.json`` and ``benchmark/``
    there is nothing to measure: no result, non-zero exit."""
    import shutil
    import subprocess
    import sys

    shutil.copy(harness.MANIFEST, tmp_path / "BENCHMARK.json")
    shutil.copytree(harness.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "rcv1_fit",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=tmp_path,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "photon_ml_tpu" in done.stderr
