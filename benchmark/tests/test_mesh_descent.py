"""The four-chip descent cell, tiny, on the CPU's forced devices: the runner
through the harness with a shrunken configuration, the generator's shards,
and the three readers this cell brought, on hand-made counters. Nothing here
asks how many cells the manifest has or where an entry stands."""

import copy
import json
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from benchmark import datagen, datagen_glmix_mesh, harness

CELL = "ml20m_full_descent4"


def _shrunk():
    resolved = copy.deepcopy(harness.resolve(harness.load_manifest(), CELL))
    resolved.traffic.update(trace_slice_s=0.3)
    cfg = resolved.config
    # rows that do not divide by four, entity counts that do not either
    cfg.update(rows=6001, users=61, items=25)
    cfg["random_effects"]["userId"].update(entities=61, rows_floor=5)
    cfg["random_effects"]["itemId"].update(entities=25)
    cfg["guarantees"]["log_loss_ratio_max"] = 0.99
    return resolved


def _run(trace, seed=5):
    resolved = _shrunk()
    logs = []
    out = harness.run_cell(
        resolved, seed=seed, seconds=0.5, trace=trace,
        devices=jax.devices()[: resolved.chips], t_start=time.perf_counter(),
        log=logs.append,
    )
    json.dumps(out)
    return resolved, out, logs


def test_the_cell_asks_for_a_mesh_and_its_files_are_there():
    resolved = harness.resolve(harness.load_manifest(), CELL)
    assert resolved.chips == 4 and resolved.traffic["kind"] == "descent_mesh"
    cfg = resolved.config
    assert cfg["reduced"] == [] and cfg["rows"] == 20_000_263
    assert cfg["random_effects"]["userId"]["entities"] == cfg["users"] == 138_493
    assert cfg["random_effects"]["itemId"]["entities"] == cfg["items"] == 26_744
    assert cfg["layout"]["chips"] == 4
    for key in ("score_abs_tol", "log_loss_ratio_max", "entity_abs_tol",
                "entity_median_abs_tol"):
        assert cfg["guarantees"][key + "_why"], key


def test_untraced_run_on_four_devices_is_correct():
    resolved, out, logs = _run(trace=False)
    assert out["correct"] is True, logs
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"descent_iter_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())


def test_traced_run_reports_this_cells_readers():
    from photon_ml_tpu.obs.metrics import REGISTRY

    resolved, out, logs = _run(trace=True)
    assert out["correct"] is True, logs
    listed = {m["name"] for m in resolved.per_layer}
    assert set(out["metrics"]) <= listed
    absent = {"device.peak_hbm_bytes", "device.hbm_fill"}  # no memory stats here
    assert listed - set(out["metrics"]) <= absent
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert got["compile.in_window"] == 0
    assert got["mesh.collective_time_share.descent"] > 0
    # the CPU backend's trace names no paths: the exchange is unstaged there
    assert got["descent.exchange_s_per_iter"] >= 0
    assert got["mesh.lane_pad_ratio"] >= 1.0 and got["mesh.row_imbalance"] >= 1.0
    # one fused launch a run, no visit of the host loop
    timers = REGISTRY.snapshot("span.")["timers"]
    assert "span.descent/visit" not in timers
    assert timers["span.descent/launch"]["calls"] == timers["span.descent/run"]["calls"]
    assert REGISTRY.snapshot("mesh.")["gauges"]["mesh.batch_devices"] == 4.0


def test_a_wrong_answer_is_not_correct(monkeypatch):
    from benchmark.reference import glmix

    monkeypatch.setattr(glmix, "score", lambda f, r, _s=glmix.score: _s(f, r) + 0.01)
    _, out, _ = _run(trace=False)
    assert out["correct"] is False


def test_the_reference_one_precision_down_is_not_correct(monkeypatch):
    """The control in the program's place, through the runner's own
    comparison: with every operand of the reference rounded to bfloat16 the
    scores and the median entity leave their limits; the loss and the worst
    entity, which cannot tell a precision apart, still pass."""
    import jax.numpy as jnp

    seen = {}

    def load_runner(resolved, _load=harness.load_runner):
        runner = _load(resolved)  # a module of its own every time
        check = runner.check

        def control(st):
            seen["sound"] = check(st)
            seen["down"] = check(st, reference_operand=jnp.bfloat16)
            return seen["down"]

        runner.check = control
        return runner

    monkeypatch.setattr(harness, "load_runner", load_runner)
    resolved, out, logs = _run(trace=False)
    assert out["correct"] is False, logs
    sound, down = seen["sound"], seen["down"]
    assert sound["correct"] is True
    g = resolved.config["guarantees"]
    assert sound["notes"]["score_max_abs_diff"] <= g["score_abs_tol"]
    assert down["notes"]["score_max_abs_diff"] > g["score_abs_tol"]
    assert down["notes"]["log_loss"] <= g["log_loss_ratio_max"] * down["notes"]["null_log_loss"]
    # the median entity tells a precision apart where the worst cannot
    assert sound["notes"]["entity_median_abs_diff"] <= g["entity_median_abs_tol"] / 20
    assert sound["notes"]["entity_median_abs_diff_bf16_operands"] > 5 * g["entity_median_abs_tol"]
    assert down["notes"]["entity_median_abs_diff"] > 5 * g["entity_median_abs_tol"]
    assert down["notes"]["entity_max_abs_diff"] <= g["entity_abs_tol"]


def test_a_program_without_the_mesh_path_fails_at_once(monkeypatch):
    """What the parent commit does under this benchmark: the runner asks for
    ``place_game_batch`` before it makes any data."""
    import photon_ml_tpu.game as game

    monkeypatch.delattr(game, "place_game_batch")
    monkeypatch.setattr(
        datagen_glmix_mesh, "glmix_mesh_rows",
        lambda *a, **k: pytest.fail("data were made first"),
    )
    with pytest.raises(ImportError):
        _run(trace=False)


def _effects(users=61, items=25):
    cfg = _shrunk().config["random_effects"]
    cfg["userId"].update(entities=users)
    cfg["itemId"].update(entities=items)
    return cfg


def test_shards_add_up_to_the_counts_and_repeat_under_one_data_seed():
    from photon_ml_tpu.parallel.mesh import data_mesh

    mesh = data_mesh(devices=jax.devices()[:4])
    n, effects = 6001, _effects()
    a = datagen_glmix_mesh.glmix_mesh_rows(5, n, 6, effects, 0, mesh)
    b = datagen_glmix_mesh.glmix_mesh_rows(5, n, 6, effects, 0, mesh)
    y, w, Xf, Xe, ids = a
    assert y.shape == (6004,) and Xf.shape == (6004, 7)
    assert {len(s.data) for s in y.addressable_shards} == {1501}
    for x, z in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(z))
    # the padded rows: weight, label and features 0, after the real rows
    assert np.asarray(w).sum() == n and not np.asarray(w)[n:].any()
    assert not np.asarray(y)[n:].any() and not np.asarray(Xf)[n:].any()
    assert np.all(np.asarray(Xf)[:n, 6] == 1.0)
    for tag, spec in effects.items():
        counts = datagen.lognormal_counts(
            int(spec["entities"]), n, int(spec.get("rows_floor", 0)),
            float(spec["lognormal_mu"]), float(spec["lognormal_sigma"]),
        )
        got = np.bincount(ids[tag][:n], minlength=int(spec["entities"]))
        np.testing.assert_array_equal(np.sort(got), counts)
        assert not np.asarray(Xe[tag])[n:].any() and not ids[tag][n:].any()
    # chips draw their own rows: no two blocks alike
    blocks = [np.asarray(s.data) for s in Xf.addressable_shards]
    assert not np.array_equal(blocks[0][:100], blocks[1][:100])


def test_seed_relabels_and_moves_no_shape():
    n, effects = 6001, _effects()
    can_a, ids_a = datagen_glmix_mesh.id_columns(5, n, effects, 0)
    can_b, ids_b = datagen_glmix_mesh.id_columns(2_147_484_001, n, effects, 0)
    for tag in effects:
        np.testing.assert_array_equal(can_a[tag], can_b[tag])
        assert not np.array_equal(ids_a[tag], ids_b[tag])
        # a relabelling: the same partition of the rows under other names
        pairs = set(zip(ids_a[tag].tolist(), ids_b[tag].tolist()))
        assert len(pairs) == len(set(ids_a[tag].tolist()))
        np.testing.assert_array_equal(
            np.sort(np.bincount(ids_a[tag])), np.sort(np.bincount(ids_b[tag]))
        )
    # the id columns are benchmark/datagen.glmix_rows' own
    _, _, _, ids_one = datagen.glmix_rows(5, n, 6, effects, 0)
    for tag in effects:
        np.testing.assert_array_equal(ids_one[tag], ids_a[tag])


def test_the_block_scorer_is_the_plain_scorer():
    from benchmark.reference import glmix, glmix_blocks
    from photon_ml_tpu.parallel.mesh import data_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    rng = np.random.default_rng(0)
    n, e = 1000, 17
    X = rng.normal(size=(n, 5)).astype(np.float32)
    Xe = rng.normal(size=(n, 3)).astype(np.float32)
    ids = rng.integers(0, e, n).astype(np.int32)
    w = rng.normal(size=5).astype(np.float32)
    W = rng.normal(size=(e, 3)).astype(np.float32)
    rows = NamedSharding(data_mesh(devices=jax.devices()[:4]), P("data"))
    want = np.asarray(glmix.score((X, w), [(Xe, ids, W)]))
    got = glmix_blocks.score(
        (jax.device_put(X, rows), w), [(jax.device_put(Xe, rows), ids, W)],
        block_rows=96,
    )
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    down = glmix_blocks.score((X, w), [(Xe, ids, W)], operand=jax.numpy.bfloat16)
    assert 1e-3 < np.max(np.abs(down - want)) < 1e-1
    y = (rng.random(n) < 0.5).astype(np.float32)
    assert glmix_blocks.log_loss(want, y) == pytest.approx(
        glmix.log_loss(want, y), rel=1e-6
    )


@pytest.mark.parametrize(
    "name,counters,want",
    [
        ("mesh.lane_pad_ratio", {"re_mesh.lanes": 10.0, "re_mesh.padded_lanes": 12.0}, 1.2),
        ("mesh.row_imbalance",
         {"re_mesh.rows_max_chip": 330.0, "re_mesh.rows_mean_chip": 300.0}, 1.1),
        ("mesh.lane_pad_ratio", {}, None),
        ("mesh.row_imbalance", {}, None),
    ],
)
def test_counter_readers_on_hand_made_counters(name, counters, want):
    from photon_ml_tpu.obs.metrics import REGISTRY

    REGISTRY.reset(prefix="re_mesh.")
    for k, v in counters.items():
        REGISTRY.counter_inc(k, v)
    got = harness.layer_reader(name)(SimpleNamespace(counters={}))
    assert got == (pytest.approx(want) if want is not None else None)
    REGISTRY.reset(prefix="re_mesh.")


def test_exchange_reader_without_work_or_stage_reads_nothing(monkeypatch):
    read = harness.layer_reader("descent.exchange_s_per_iter")
    assert read(SimpleNamespace(counters={})) is None  # the slice did no work
    import photon_ml_tpu.obs.stages as program_stages

    monkeypatch.delattr(program_stages, "MESH_EXCHANGE")
    assert read(SimpleNamespace(counters={"work": 2.0})) is None
