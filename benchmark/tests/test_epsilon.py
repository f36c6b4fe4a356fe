"""``glm_dense_epsilon`` / ``epsilon_tron_fit`` (PR 34): the generator, the
cell tiny through the harness on the CPU backend, the check against
``benchmark/reference/tron.py``, and the manifest's new entries."""

import copy
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmark import datagen_epsilon, harness
from benchmark.reference import tron as reference

NEW_METRICS = {
    "dense_hvp_roofline", "fit.hvp_s_per_fit", "fit.cg_s_per_fit",
    "fit.tron_unstaged_s_per_fit", "optim.cg_steps_per_fit",
}
JOINED = {
    "fit.device_idle_share", "optim.passes_per_fit", "fit.objective_s_per_fit",
}


def test_rows_are_unit_columns_standardised_classes_balanced():
    X, y = datagen_epsilon.epsilon_rows(8192, 200, 2048, data_seed=0)
    X, y = np.asarray(X), np.asarray(y)
    assert X.shape == (8192, 200) and X.dtype == np.float32
    np.testing.assert_allclose(np.linalg.norm(X, axis=1), 1.0, rtol=1e-5)
    # standardised columns, then unit rows: every column has the same scale
    np.testing.assert_allclose(X.std(axis=0) * np.sqrt(200), 1.0, atol=0.08)
    assert abs(X.mean()) < 1e-3
    assert set(np.unique(y)) == {0.0, 1.0} and 0.45 < y.mean() < 0.55
    # a common part: the correlation matrix has eigenvalues far above a
    # flat spectrum's, as many as there are factors
    eig = np.linalg.eigvalsh(np.corrcoef(X.T))[::-1]
    assert eig[0] > 5 * np.median(eig) and eig[15] > 1.5 * eig[20]
    _, _, w = datagen_epsilon._problem(200, 16, 0.6, 0.9, 2.0, 0)
    assert 1.7 < np.std(X @ w) < 2.3


def test_the_problem_is_the_data_seeds():
    a = datagen_epsilon.epsilon_rows(2048, 130, 512, data_seed=0)
    b = datagen_epsilon.epsilon_rows(2048, 130, 512, data_seed=0)
    c = datagen_epsilon.epsilon_rows(2048, 130, 512, data_seed=1)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))


def _run(trace, seed=2**31 + 7, **optimizer):
    resolved = copy.deepcopy(
        harness.resolve(harness.load_manifest(), "epsilon_tron_fit")
    )
    resolved.traffic.update(trace_slice_s=0.3)
    # a width that is no multiple of 128, as the source's is not; 1e-3 of
    # the first gradient: the toy's loss is a hundredth of the cell's, and
    # float32 resolves no step past that (tests/test_tron_reference.py)
    resolved.config["features"].update(
        rows=4096, columns=200, generate_block_rows=1024
    )
    resolved.config["optimizer"].update({"tolerance": 1e-3, **optimizer})
    resolved.config["guarantees"]["grad_ratio_max"] = 1e-3
    logs = []
    out = harness.run_cell(
        resolved, seed=seed, seconds=0.5, trace=trace,
        devices=jax.devices()[:1], t_start=time.perf_counter(), log=logs.append,
    )
    json.dumps(out)  # the last line must serialise
    return resolved, out, logs


def _check_notes(logs) -> dict:
    return json.loads(
        next(l for l in logs if l.startswith("check: ")).split(": ", 1)[1]
    )["notes"]


def test_untraced_run_is_correct_and_the_same_seed_gives_the_same_fit():
    resolved, out, logs = _run(trace=False)
    assert out["correct"] is True, logs
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"fit_s", "setup_s"}
    assert "breakdown" not in out
    notes = _check_notes(logs)
    g = resolved.config["guarantees"]
    assert notes["reason"] == 1  # the gradient test, not a cap or a stall
    assert notes["loss_rel_diff"] <= g["loss_rel_tol"]
    assert notes["grad_ratio"] <= g["grad_ratio_max"]
    assert notes["hvp_rel_diff"] <= g["hvp_rel_tol"]
    _, again, logs2 = _run(trace=False)
    assert again["correct"] is True
    twin = _check_notes(logs2)
    for key in ("loss_reported", "iterations", "objective_passes", "grad_ratio"):
        assert notes[key] == twin[key], key


def test_traced_run_reports_every_metric_the_cpu_can():
    resolved, out, logs = _run(trace=True)
    assert out["correct"] is True, logs
    listed = {m["name"] for m in resolved.per_layer}
    assert NEW_METRICS | JOINED <= listed
    assert {"fit.optimizer_s_per_fit", "fit.unstaged_s_per_fit",
            "fused_roofline"}.isdisjoint(listed)
    # the CPU backend has no memory stats and its trace no stage paths:
    # those readers find nothing; every other listed metric reports
    absent = {"device.peak_hbm_bytes", "device.hbm_fill", "dense_hvp_roofline"}
    assert listed - set(out["metrics"]) == absent
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["compile.in_window"] == 0
    notes = _check_notes(logs)
    assert m["optim.passes_per_fit"] == notes["objective_passes"]
    assert m["optim.cg_steps_per_fit"] == (
        notes["objective_passes"] - notes["iterations"] - 1
    )
    assert m["optim.cg_steps_per_fit"] > 4 * notes["iterations"]  # CG does the work
    # without paths everything is unstaged, and the family adds up
    assert m["fit.hvp_s_per_fit"] == 0 and m["fit.cg_s_per_fit"] == 0
    assert m["fit.tron_unstaged_s_per_fit"] > 0


def test_a_fit_cut_short_is_not_correct():
    _, out, logs = _run(trace=False, max_iterations=2)
    notes = _check_notes(logs)
    assert notes["iterations"] == 2 and out["correct"] is False
    assert notes["grad_ratio"] > 1e-3 and notes["hvp_rel_diff"] < 1e-5


def test_a_scaled_hessian_vector_product_is_not_correct(monkeypatch):
    """The one check that looks at the pass itself: a product that is off
    by a part in a thousand fails it, whatever the fit did."""
    from photon_ml_tpu.ops.glm import GLMObjective

    hvp = GLMObjective.hvp
    monkeypatch.setattr(
        GLMObjective, "hvp", lambda self, w, v: hvp(self, w, v) * 1.001
    )
    jax.clear_caches()
    try:
        _, out, logs = _run(trace=False)
    finally:
        jax.clear_caches()
    notes = _check_notes(logs)
    assert out["correct"] is False
    assert 5e-4 < notes["hvp_rel_diff"] < 2e-3
    assert notes["loss_rel_diff"] < 1e-5  # CG with a scaled H still descends


def test_the_reference_one_precision_down_is_told_apart():
    """What the check's limits must tell from float32: the reference's own
    passes with every matmul operand rounded to bfloat16 (one bf16 MXU
    pass)."""
    X, y = datagen_epsilon.epsilon_rows(4096, 200, 1024, data_seed=0)
    rng = np.random.default_rng(3)
    w, v = rng.standard_normal(200) * 2.0, rng.standard_normal(200)
    hv = reference.hvp(X, y, w, v, 1.0, 1024)
    f, _ = reference.value_grad(X, y, w, 1.0, 1024)
    hv_low = reference.hvp(X, y, w, v, 1.0, 1024, operands="bfloat16")
    f_low, _ = reference.value_grad(X, y, w, 1.0, 1024, operands="bfloat16")
    g = json.load(open(os.path.join(
        harness.ROOT, "benchmark", "configs", "glm_dense_epsilon.json"
    )))["guarantees"]
    assert np.linalg.norm(hv_low - hv) / np.linalg.norm(hv) > 10 * g["hvp_rel_tol"]
    assert abs(f_low - f) / f > g["loss_rel_tol"]


def test_readers_give_nothing_for_a_program_without_the_stages(monkeypatch):
    """Under a parent commit (no ``glm.hvp``, no ``tron.*``) every reader of
    a new stage returns None and does not raise; the count of CG steps
    comes from the runner and is there."""
    from types import SimpleNamespace

    from photon_ml_tpu.obs import stages as program_stages

    monkeypatch.delattr(program_stages, "GLM_HVP")
    obs = SimpleNamespace(
        counters={"work": 2.0, "optim.objective_passes": 100.0,
                  "optim.cg_steps": 88.0},
        trace=SimpleNamespace(ops={}), device_kind="TPU v5 lite",
        shape={"rows": 10, "columns": 10, "itemsize": 4, "devices": 1},
    )
    for name in sorted(NEW_METRICS - {"optim.cg_steps_per_fit"}):
        assert harness.layer_reader(name)(obs) is None, name
    assert harness.layer_reader("optim.cg_steps_per_fit")(obs) == 44.0


def test_the_roofline_counts_one_read_of_the_real_columns(monkeypatch):
    from types import SimpleNamespace

    from benchmark import tron_parts, work

    monkeypatch.setattr(tron_parts, "hvp_seconds_per_fit", lambda obs: 0.5)
    obs = SimpleNamespace(
        counters={"work": 2.0, "optim.cg_steps": 100.0},
        device_kind="TPU v5 lite",
        shape={"rows": 400000, "columns": 2000, "itemsize": 4, "devices": 1},
    )
    least, bound = work.least_seconds(
        *work.dense_pass(400000, 2000, 4), "TPU v5 lite"
    )
    assert bound == "memory" and 3.8e-3 < least < 4.0e-3
    assert tron_parts.hvp_roofline(obs) == pytest.approx(100 * 100 * least / 1.0)


def test_the_manifests_new_entries_follow_its_rules():
    manifest = harness.load_manifest()
    assert len(manifest["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    layers = {m["layer"] for m in manifest["per_layer"] if m["name"] not in NEW_METRICS}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["layer"] in layers and m["moves"] == "fit_s"
        assert m["workloads"] == ["epsilon_tron_fit"]
        assert os.path.exists(
            os.path.join(harness.HERE, "layer_metrics", name + ".py")
        )
    for name in JOINED:
        assert by_name[name]["workloads"][-1] == "epsilon_tron_fit"
    for name in ("fit.optimizer_s_per_fit", "fit.unstaged_s_per_fit", "fused_roofline"):
        assert "epsilon_tron_fit" not in by_name[name]["workloads"]
    cell = manifest["workloads"][-1]
    assert cell == {
        "name": "epsilon_tron_fit", "config": "glm_dense_epsilon",
        "traffic": "fit_tron_resident", "chips": 1, "why": cell["why"],
    }
    config = manifest["configs"][-1]
    assert config["name"] == "glm_dense_epsilon" and config["reduced"] == []
    assert len(config["source"]) <= 200 and len(config["why"]) <= 200
    body = json.load(open(os.path.join(harness.ROOT, config["file"])))
    feats = body["features"]
    assert (feats["rows"], feats["columns"], feats["dtype"]) == (400000, 2000, "float32")
    assert feats["rows"] % feats["generate_block_rows"] == 0
    assert body["architecture"] is None and body["intercept"] is False
    assert body["optimizer"]["type"] == "TRON" and body["l2"] == 1.0
    for key in ("loss_rel_tol", "grad_ratio_max", "hvp_rel_tol"):
        assert body["guarantees"][key] > 0 and body["guarantees"][key + "_why"]
    assert "epsilon_tron_fit" in next(
        m for m in manifest["end_to_end"] if m["name"] == "fit_s"
    )["workloads"]
