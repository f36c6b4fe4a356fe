"""``glm_sparse_criteo`` / ``criteo_fit`` (PR 31): the hashed-field
generator, the cell tiny through the harness on the CPU backend, and the
manifest's new entries against its rules."""

import copy
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmark import datagen_criteo, harness

CARDS = [64] * 3 + [3, 10, 1460, 93145, 10131227]
NEW_METRICS = {
    "fit.head_s_per_fit", "fit.tail_s_per_fit", "layout.tail_cell_fill",
    "sparse_tail_roofline", "sparse_head_roofline",
}


def _rows(seed, n=4096, d=1_000_000, data_seed=0):
    return tuple(np.asarray(a) for a in datagen_criteo.hashed_field_rows(
        seed, n, d, CARDS, data_seed, 4.0, -1.6
    ))


def test_one_nonzero_a_field_at_unit_norm_and_any_width():
    idx, val, y = _rows(1)
    fields = len(CARDS)
    assert idx.shape == val.shape == (4096, fields) and y.shape == (4096,)
    assert idx.dtype == np.int32 and idx.min() >= 0
    assert 65_536 < idx.max() < 1_000_000  # wider than datagen.py permutes
    np.testing.assert_allclose(val, 1.0 / np.sqrt(fields), rtol=1e-6)
    np.testing.assert_allclose(np.sum(val * val, axis=1), 1.0, rtol=1e-5)
    assert set(np.unique(y)) == {0.0, 1.0} and 0.1 < y.mean() < 0.4
    # a slot is a field (rotated by the seed): a field of 3 values names 3
    # columns, the 64-bin fields at most 64 each, the widest thousands
    distinct = sorted(len(np.unique(idx[:, j])) for j in range(fields))
    assert distinct[0] == 3 and distinct[1] == 10
    assert distinct[2:5] == sorted(distinct[2:5]) and max(distinct[2:5]) <= 64
    assert distinct[-1] > 1000
    # Zipf inside a field: rank 0 of the 3-value field takes half its rows
    narrow = next(j for j in range(fields) if len(np.unique(idx[:, j])) == 3)
    top = np.bincount(idx[:, narrow]).max() / idx.shape[0]
    assert abs(top - np.log(2.0) / np.log(4.0)) < 0.03


def test_a_seed_relabels_and_moves_no_shape_or_count():
    a, b, c = _rows(1), _rows(1), _rows(2)
    other = _rows(1, data_seed=1)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    # another seed: other arrays, the same rows (a rotation of each)
    assert not np.array_equal(a[0], c[0])
    np.testing.assert_array_equal(a[2], c[2])
    np.testing.assert_array_equal(np.sort(a[0], axis=1), np.sort(c[0], axis=1))
    # so no count the layout build reads moves: columns, cells
    np.testing.assert_array_equal(
        np.bincount(a[0].ravel(), minlength=1_000_000),
        np.bincount(c[0].ravel(), minlength=1_000_000),
    )
    assert not np.array_equal(a[2], other[2])
    assert not np.array_equal(np.sort(a[0], axis=1), np.sort(other[0], axis=1))


@pytest.fixture
def small_tiles(monkeypatch):
    """The tile-COO kernels in interpret mode at a small DMA-step carve, and
    a memory budget under which the toy matrix keeps a head and a tail."""
    import photon_ml_tpu.ops.sparse_tiled as st
    import photon_ml_tpu.ops.streaming as streaming

    monkeypatch.setattr(st, "GROUPS_PER_STEP", 8)
    monkeypatch.setattr(st, "SEGMENTS_PER_DMA", 2)
    monkeypatch.setattr(streaming, "device_hbm_budget_bytes", lambda *a, **k: 1e8)


def _run(trace):
    resolved = copy.deepcopy(
        harness.resolve(harness.load_manifest(), "criteo_fit")
    )
    resolved.traffic.update(trace_slice_s=0.3)
    # the source's width and fields; 3,000 rows: 3 row slabs x 977 column
    # slabs of near-empty cells
    resolved.config["features"].update(rows=3000)
    resolved.config["guarantees"]["grad_ratio_max"] = 0.9  # a toy problem
    logs = []
    out = harness.run_cell(
        resolved, seed=2**31 + 5, seconds=0.5, trace=trace,
        devices=jax.devices()[:1], t_start=time.perf_counter(), log=logs.append,
    )
    json.dumps(out)  # the last line must serialise
    return resolved, out, logs


def test_untraced_run_is_correct_at_the_full_width(small_tiles):
    resolved, out, logs = _run(trace=False)
    assert out["correct"] is True, logs
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"fit_s", "setup_s"}
    facts = json.loads(
        next(l for l in logs if l.startswith("set-up facts")).split(": ", 1)[1]
        .split("; peak")[0]
    )
    assert facts["layout.nonzeros"] == 3000 * 39
    assert "breakdown" not in out


def test_traced_run_reports_every_metric_the_cpu_can(small_tiles):
    from photon_ml_tpu.obs.metrics import REGISTRY
    from photon_ml_tpu.ops import tile_cache

    # one build a process, as in a run of the cell: its counters alone
    REGISTRY.reset(prefix="tile_layout.")
    tile_cache.clear()
    resolved, out, logs = _run(trace=True)
    assert out["correct"] is True, logs
    listed = {m["name"] for m in resolved.per_layer}
    assert NEW_METRICS <= listed
    assert {"sparse_tiled_roofline", "layout.pad_ratio"}.isdisjoint(listed)
    # the CPU backend has no memory stats and its trace no stage paths:
    # those readers find nothing; every other listed metric reports
    absent = {"device.peak_hbm_bytes", "device.hbm_fill",
              "sparse_tail_roofline", "sparse_head_roofline"}
    assert listed - set(out["metrics"]) == absent
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["compile.in_window"] == 0
    # a head of popular columns, and a tail of near-empty cells in the
    # sparse-cell form. At 3 row slabs a column slab's 60 nonzeros fill a
    # 1,024-slot segment of the gradient's stream whatever the form, so the
    # toy's ratio is 9.3 (runs: 15.2); the cell's own is 1.4 (PERF.md)
    assert m["layout.head_nonzero_share"] > 30
    assert 10 < m["layout.tail_cell_fill"] < 40
    assert 1.0 < m["layout.tail_pad_ratio"] < 10.0
    assert m["fit.head_s_per_fit"] == 0 and m["fit.tail_s_per_fit"] == 0


def test_readers_give_nothing_for_a_program_without_the_parts(monkeypatch):
    """Under a parent commit (no ``glm.head`` / ``glm.tail``, no
    ``tile_layout.tail_cells``) every new reader returns None and does not
    raise."""
    from types import SimpleNamespace

    from photon_ml_tpu.obs import stages as program_stages
    from photon_ml_tpu.obs.metrics import REGISTRY

    monkeypatch.delattr(program_stages, "GLM_HEAD")
    REGISTRY.reset(prefix="tile_layout.")
    obs = SimpleNamespace(
        counters={"work": 2.0, "optim.objective_passes": 44.0},
        trace=SimpleNamespace(ops={}), device_kind="TPU v5 lite",
        shape={"rows": 10, "columns": 10, "nonzeros": 20.0},
    )
    for name in sorted(NEW_METRICS):
        assert harness.layer_reader(name)(obs) is None, name


def test_the_manifests_new_entries_follow_its_rules():
    manifest = harness.load_manifest()
    layers = {m["layer"] for m in manifest["per_layer"] if m["name"] not in NEW_METRICS}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["layer"] in layers and m["moves"] == "fit_s"
        assert "criteo_fit" in m["workloads"]
        assert os.path.exists(
            os.path.join(harness.HERE, "layer_metrics", name + ".py")
        )
    cell = next(w for w in manifest["workloads"] if w["name"] == "criteo_fit")
    assert cell["chips"] == 1 and cell["config"] == "glm_sparse_criteo"
    config = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["rows"]
    body = json.load(open(os.path.join(harness.ROOT, config["file"])))
    feats = body["features"]
    assert feats["columns"] == 1_000_000 and feats["nonzeros_per_row"] == 39
    assert feats["integer_fields"] + len(feats["categorical_cardinalities"]) == 39
    assert abs(feats["rows"] * 20 - feats["source_rows"]) < 20  # 1/20
    assert body["architecture"] is None
    for name in ("fit_s",):
        assert "criteo_fit" in next(
            m for m in manifest["end_to_end"] if m["name"] == name
        )["workloads"]
