"""The fused outer iteration under a mesh (PR 38): rows sharded for the fixed
effect, entity lanes for the random effects, coefficients whole on every
device, on four of the CPU's forced devices with seeded data.

(a) against the benchmark's plain reference; (b) against the same descent on
one device, with entity counts that do not divide by four and an entity
present in no row; (c) ``CoordinateDescent`` takes the fused path; (d) no
per-row matrix of the lowered program is replicated; (e) the stage name and
the prepare-time counters appear.
"""

from __future__ import annotations

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_ml_tpu.config import (
    OptimizationConfig,
    OptimizerConfig,
    RegularizationContext,
)
from photon_ml_tpu.game import (
    CoordinateDescent,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
    bucket_entities,
    group_by_entity,
    make_game_batch,
    place_game_batch,
)
from photon_ml_tpu.game.data import rows_placed_over
from photon_ml_tpu.obs import spans, stages
from photon_ml_tpu.obs.metrics import REGISTRY
from photon_ml_tpu.parallel.mesh import data_mesh
from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # the benchmark's references

SEQUENCE = ["fixed", "per_user", "per_item"]
# 1,003 rows over four devices: one padded row; 37 users of whom the last
# has no row; 11 items: no class's lanes divide by four
N, D, USERS, ITEMS, WIDTH = 1003, 5, 37, 11, 3
TASK = TaskType.LOGISTIC_REGRESSION


@pytest.fixture(scope="module")
def mesh():
    return data_mesh(devices=jax.devices()[:4])


def _data(seed=0, relabelled=False):
    rng = np.random.default_rng(seed)
    uid = np.sort(rng.integers(0, USERS - 1, N)).astype(np.int32)
    iid = rng.integers(0, ITEMS, N).astype(np.int32)
    if relabelled:  # the same rows an entity, under other names
        names = np.random.default_rng(seed + 100)
        uid = names.permutation(USERS - 1).astype(np.int32)[uid]
        iid = names.permutation(ITEMS).astype(np.int32)[iid]
    y = (rng.random(N) < 0.5).astype(np.float32)
    Xf = rng.normal(size=(N, D + 1)).astype(np.float32)
    Xf[:, D] = 1.0
    Xu = rng.normal(size=(N, WIDTH)).astype(np.float32)
    Xi = rng.normal(size=(N, WIDTH)).astype(np.float32)
    return y, {"global": Xf, "pu": Xu, "pi": Xi}, {"user": uid, "item": iid}


def _optimization(kind):
    return OptimizationConfig(
        optimizer=OptimizerConfig(
            optimizer_type=kind, max_iterations=20, tolerance=1e-7
        ),
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )


def _descent(mesh, seed=0, relabelled=False):
    y, feats, ids = _data(seed, relabelled)
    batch = make_game_batch(y, feats, id_tags=ids, mesh=mesh)
    coordinates = {
        "fixed": FixedEffectCoordinate(
            coordinate_id="fixed", batch=batch, feature_shard_id="global",
            config=_optimization(OptimizerType.LBFGS), task_type=TASK,
            intercept_index=D, mesh=mesh,
        )
    }
    for cid, tag, shard, count in (
        ("per_user", "user", "pu", USERS), ("per_item", "item", "pi", ITEMS),
    ):
        grouping = group_by_entity(ids[tag], num_entities=count)
        coordinates[cid] = RandomEffectCoordinate(
            coordinate_id=cid, batch=batch, feature_shard_id=shard,
            random_effect_type=tag,
            config=_optimization(OptimizerType.NEWTON_CHOLESKY),
            grouping=grouping, buckets=bucket_entities(grouping),
            task_type=TASK, num_entities=count, mesh=mesh,
        )
    return CoordinateDescent(coordinates, batch, TASK, mesh=mesh), batch


def _coefficients(result, sequence=SEQUENCE):
    return {c: np.asarray(result.model[c].coefficient_means) for c in sequence}


def _scores(result, sequence=SEQUENCE):
    return {c: np.asarray(result.training_scores[c])[:N] for c in sequence}


@pytest.fixture(scope="module")
def mesh_run(mesh):
    descent, batch = _descent(mesh)
    REGISTRY.reset(prefix="span")
    result = descent.run(SEQUENCE, 2)
    timers = REGISTRY.snapshot("span.")["timers"]
    return descent, batch, result, timers


# -- the placement ------------------------------------------------------------

def test_a_batch_is_placed_once_with_inert_rows_at_its_end(mesh):
    y, feats, ids = _data()
    REGISTRY.reset(prefix="span")
    batch = make_game_batch(y, feats, id_tags=ids, mesh=mesh)
    assert batch.num_rows == 1004 and rows_placed_over(batch, mesh)
    assert REGISTRY.snapshot("mesh.")["gauges"]["mesh.batch_devices"] == 4.0
    calls = {k: v["calls"] for k, v in REGISTRY.snapshot("span.")["timers"].items()}
    assert calls["span." + spans.GAME_PLACE] == calls["span." + spans.GAME_BATCH] == 1
    for leaf in jax.tree.leaves(batch):
        assert leaf.sharding.is_equivalent_to(
            NamedSharding(mesh, P("data")), leaf.ndim
        )
        assert not np.asarray(leaf)[N:].any()  # weight, label, features, ids: 0
    np.testing.assert_array_equal(np.asarray(batch.labels)[:N], y)
    np.testing.assert_array_equal(np.asarray(batch.weights)[:N], np.ones(N))
    # placed arrays are left where they are
    again = place_game_batch(batch, mesh)
    assert all(a is b for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(batch)))
    # and a batch on one device is not "placed"
    assert not rows_placed_over(make_game_batch(y, feats, id_tags=ids), mesh)


# -- (c) the fused path -----------------------------------------------------------

def test_the_descent_under_a_mesh_is_one_launch_a_run(mesh_run):
    _, _, result, timers = mesh_run
    calls = {k: v["calls"] for k, v in timers.items()}
    assert calls["span." + spans.DESCENT_LAUNCH] == 1
    assert calls["span." + spans.DESCENT_RUN] == 1
    assert "span." + spans.DESCENT_VISIT not in calls
    assert "span." + spans.DESCENT_ITER not in calls
    assert [len(result.trackers[c]) for c in SEQUENCE] == [2, 2, 2]
    # the total and every score stay over the mesh
    for c in SEQUENCE:
        assert rows_placed_over(result.training_scores[c], mesh_run[0].mesh)


def test_a_batch_on_one_device_keeps_the_host_loop_under_a_mesh(mesh):
    """The gate is the placement: coordinates handed a mesh but a batch that
    lies on one device (every caller before PR 38) visit as they did."""
    y, feats, ids = _data()
    batch = make_game_batch(y, feats, id_tags=ids)
    grouping = group_by_entity(ids["user"], num_entities=USERS)
    user = RandomEffectCoordinate(
        coordinate_id="per_user", batch=batch, feature_shard_id="pu",
        random_effect_type="user",
        config=_optimization(OptimizerType.NEWTON_CHOLESKY), grouping=grouping,
        buckets=bucket_entities(grouping), task_type=TASK, num_entities=USERS,
        mesh=mesh,
    )
    fixed = FixedEffectCoordinate(
        coordinate_id="fixed", batch=batch, feature_shard_id="global",
        config=_optimization(OptimizerType.LBFGS), task_type=TASK,
        intercept_index=D, mesh=mesh,
    )
    assert user._fused_visit_parts() is None and fixed._fused_visit_parts() is None


@pytest.mark.parametrize("knob", ["PHOTON_RE_SHARD", "PHOTON_RE_FUSE_BUCKETS",
                                  "PHOTON_RE_COMPACT_EVERY"])
def test_placement_and_schedule_knobs_keep_their_path(mesh, monkeypatch, knob):
    monkeypatch.setenv(knob, "1")
    descent, _ = _descent(mesh)
    assert descent.coordinates["per_user"]._fused_visit_parts() is None


# -- the program's entry points place: a mesh alone chooses the path ---------------

def _training_config(**kwargs):
    from photon_ml_tpu.config import (
        FixedEffectCoordinateConfig,
        GameTrainingConfig,
        RandomEffectCoordinateConfig,
    )

    fixed = kwargs.pop("fixed", _optimization(OptimizerType.LBFGS))
    return GameTrainingConfig(
        task_type=TASK,
        coordinate_update_sequence=tuple(SEQUENCE),
        coordinate_descent_iterations=2,
        fixed_effect_coordinates={"fixed": FixedEffectCoordinateConfig(
            feature_shard_id="global", optimization=fixed,
        )},
        random_effect_coordinates={
            cid: RandomEffectCoordinateConfig(
                random_effect_type=tag, feature_shard_id=shard,
                optimization=_optimization(OptimizerType.NEWTON_CHOLESKY),
            )
            for cid, tag, shard in (("per_user", "user", "pu"), ("per_item", "item", "pi"))
        },
        **kwargs,
    )


def _estimator_fit(mesh, config, validation=None):
    from photon_ml_tpu.estimators import GameEstimator

    y, feats, ids = _data()
    batch = make_game_batch(y, feats, id_tags=ids)  # on one device, as callers build it
    REGISTRY.reset(prefix="span")
    result = GameEstimator(config, mesh=mesh, intercept_indices={"global": D}).fit(
        batch, validation
    )[0]
    calls = {k: v["calls"] for k, v in REGISTRY.snapshot("span.")["timers"].items()}
    return result, calls


def _assert_same_fit(got, want):
    for c in SEQUENCE:
        np.testing.assert_allclose(
            np.asarray(got.model[c].coefficient_means),
            np.asarray(want.model[c].coefficient_means), rtol=0, atol=1e-3,
        )
        # the padded row is sliced off what the caller gets back
        assert got.descent.training_scores[c].shape == (N,)
        np.testing.assert_allclose(
            np.asarray(got.descent.training_scores[c]),
            np.asarray(want.descent.training_scores[c]), rtol=0, atol=1e-2,
        )


def test_the_estimator_places_its_batch_so_a_mesh_alone_fuses(mesh):
    got, calls = _estimator_fit(mesh, _training_config())
    assert calls["span." + spans.GAME_PLACE] == 1
    assert calls["span." + spans.DESCENT_LAUNCH] == 1
    assert "span." + spans.DESCENT_VISIT not in calls
    want, calls_one = _estimator_fit(None, _training_config())
    assert "span." + spans.GAME_PLACE not in calls_one
    _assert_same_fit(got, want)


@pytest.mark.parametrize("case", ["validation", "down_sampling"])
def test_what_needs_the_host_loop_runs_it_over_the_placed_batch(mesh, case):
    """Per-visit validation and a down-sampled coordinate keep the host loop
    on one device and under a mesh alike; under a mesh it visits the batch
    the estimator placed (padded row and all) and fits the same model."""
    import dataclasses

    kwargs, validation = {}, None
    if case == "validation":
        y, feats, ids = _data(seed=1)
        validation = make_game_batch(y, feats, id_tags=ids)
        kwargs["evaluators"] = ("AUC",)
    else:
        kwargs["fixed"] = dataclasses.replace(
            _optimization(OptimizerType.LBFGS), down_sampling_rate=0.5
        )
    got, calls = _estimator_fit(mesh, _training_config(**kwargs), validation)
    assert calls["span." + spans.GAME_PLACE] == 1
    assert calls["span." + spans.DESCENT_VISIT] == 6
    assert "span." + spans.DESCENT_LAUNCH not in calls
    want, _ = _estimator_fit(None, _training_config(**kwargs), validation)
    _assert_same_fit(got, want)
    if validation is not None:
        assert got.evaluation.primary == pytest.approx(want.evaluation.primary, abs=1e-3)


def test_the_training_driver_reads_and_fits_over_its_mesh(mesh, tmp_path, rng):
    """``cli/train.run`` with a mesh and nothing else: the reader places the
    rows it read (301 of them: three padded), the estimator leaves them
    where they lie, and every grid entry is one launch."""
    from test_drivers import _game_config, _quiet, _write_game_avro

    from photon_ml_tpu.cli import train as train_cli
    from photon_ml_tpu.game.data import placeable_over
    from photon_ml_tpu.io.data_reader import AvroDataReader

    path = str(tmp_path / "train.avro")
    _write_game_avro(path, rng, n=301)
    config = _game_config()
    dataset = AvroDataReader(config.feature_shards).read(
        path, id_tags=("userId",), mesh=mesh
    )
    assert dataset.batch.padded_rows == 3 and dataset.batch.num_real_rows == 301
    assert rows_placed_over(dataset.batch, mesh) and placeable_over(dataset.batch, mesh)

    fits = {}
    for name, m in (("mesh", mesh), ("one", None)):
        REGISTRY.reset(prefix="span")
        fits[name] = train_cli.run(
            config, [path], str(tmp_path / name), logger=_quiet(tmp_path), mesh=m
        )
        calls = {k: v["calls"] for k, v in REGISTRY.snapshot("span.")["timers"].items()}
        assert calls["span." + spans.DESCENT_LAUNCH] == 1
        assert "span." + spans.DESCENT_VISIT not in calls
        assert calls.get("span." + spans.GAME_PLACE, 0) == (1 if m is not None else 0)
    for c in ("fixed", "per_user"):
        np.testing.assert_allclose(
            np.asarray(fits["mesh"].model[c].coefficient_means),
            np.asarray(fits["one"].model[c].coefficient_means), rtol=0, atol=1e-3,
        )


# -- (a) against the plain reference -------------------------------------------

def test_mesh_descent_against_the_plain_reference(mesh_run):
    from benchmark.reference import glmix, newton

    _, batch, result, _ = mesh_run
    y, feats, ids = _data()
    w = _coefficients(result)
    parts = {
        "fixed": np.asarray(glmix.score((feats["global"], w["fixed"]), [])),
        "per_user": np.asarray(
            glmix.score(None, [(feats["pu"], ids["user"], w["per_user"])])
        ),
        "per_item": np.asarray(
            glmix.score(None, [(feats["pi"], ids["item"], w["per_item"])])
        ),
    }
    got = _scores(result)
    for c in SEQUENCE:
        # the program's scores of its own coefficients, float32 both
        np.testing.assert_allclose(got[c], parts[c], rtol=0, atol=2e-5)
    # the padded row scores 0 in every coordinate
    assert not any(np.asarray(result.training_scores[c])[N:].any() for c in SEQUENCE)
    # the last coordinate's entities sit at the reference Newton optimum of
    # what they were solved against; a float32 Newton stops where its summed
    # loss cannot see a step, 1e-4 of coefficients of size 0.1 to 1
    others = parts["fixed"] + parts["per_user"]
    for e in range(ITEMS):
        rows = np.flatnonzero(ids["item"] == e)
        ref = newton.entity_newton(feats["pi"][rows], y[rows], others[rows], 1.0)
        np.testing.assert_allclose(w["per_item"][e], ref, rtol=0, atol=1e-3)
    # the user nobody rated keeps the zero model
    assert not w["per_user"][USERS - 1].any()


def test_mesh_fixed_effect_reaches_the_reference_gradients_zero(mesh):
    from benchmark.reference import glm, glmix

    descent, _ = _descent(mesh)
    result = descent.run(["per_user", "fixed"], 1)
    y, feats, ids = _data()
    w = _coefficients(result, ["per_user", "fixed"])
    others = np.asarray(glmix.score(None, [(feats["pu"], ids["user"], w["per_user"])]))
    grad = lambda v: glm.dense_value_grad(
        feats["global"], y, v, 1.0, D, offsets=others
    )[1]
    ratio = np.linalg.norm(grad(w["fixed"])) / np.linalg.norm(
        grad(np.zeros_like(w["fixed"]))
    )
    assert ratio <= 1e-4


# -- (b) against one device -------------------------------------------------------

@pytest.mark.parametrize("sequence,atol", [
    # a coordinate alone from zero: the same lanes solve the same problems,
    # and a fixed effect's partial sums meet in another order
    (["per_user"], 1e-6), (["per_item"], 1e-6), (["fixed"], 1e-6),
    # the whole descent: a float32 Newton stops where its summed loss cannot
    # see a step, so offsets that differ in the last place move an entity's
    # coefficients by up to 3e-4 (see the reference test above)
    (SEQUENCE, 1e-3),
])
def test_mesh_descent_agrees_with_one_device(mesh, sequence, atol):
    iterations = 2 if len(sequence) > 1 else 1
    on_mesh = _descent(mesh)[0].run(sequence, iterations)
    on_one = _descent(None)[0].run(sequence, iterations)
    a, b = _coefficients(on_mesh, sequence), _coefficients(on_one, sequence)
    for c in sequence:
        assert a[c].shape == b[c].shape
        np.testing.assert_allclose(a[c], b[c], rtol=0, atol=atol)
    sa, sb = _scores(on_mesh, sequence), _scores(on_one, sequence)
    for c in sequence:
        np.testing.assert_allclose(sa[c], sb[c], rtol=0, atol=10 * atol)
    # the diagnostics come back a lane an entity, padded lanes dropped
    for c in sequence[1:] if sequence[0] == "fixed" else sequence:
        ta, tb = on_mesh.trackers[c][-1], on_one.trackers[c][-1]
        assert ta.iterations.shape == tb.iterations.shape == a[c].shape[:1]
        if len(sequence) == 1:
            np.testing.assert_array_equal(ta.iterations, tb.iterations)


# -- (d) nothing per-row is replicated ------------------------------------------

def _fused_program(descent):
    run_outer = descent._fused_outer_cache[tuple(SEQUENCE)]
    fused = next(
        cell.cell_contents for cell in run_outer.__closure__
        if getattr(cell.cell_contents, "__name__", "") == "fused"
    )
    batch = descent.batch
    total = batch.offsets
    owns = tuple(jnp.zeros_like(total) for _ in SEQUENCE)
    statics = tuple(
        descent.coordinates[c]._fused_visit_parts()[0](None) for c in SEQUENCE
    )
    return fused, (total, owns, statics)


def test_no_per_row_matrix_of_the_program_is_replicated(mesh_run, mesh):
    descent, batch, _, _ = mesh_run
    fused, args = _fused_program(descent)
    compiled = fused.lower(*args, r=2).compile()
    rows = batch.num_rows
    over = NamedSharding(mesh, P("data"))
    shardings, _ = compiled.input_shardings
    # an argument the program never reads (the base batch's zero offsets)
    # has no sharding to show: keep the two lists in step
    absent = lambda x: x is None
    leaves = jax.tree.leaves(args, is_leaf=absent)
    placed = jax.tree.leaves(shardings, is_leaf=absent)
    assert len(leaves) == len(placed)
    per_row = [
        (leaf, sharding) for leaf, sharding in zip(leaves, placed)
        if leaf is not None and sharding is not None and leaf.shape[:1] == (rows,)
    ]
    assert len(per_row) >= 4 + 3 + 2 * 2  # total, owns, the fixed batch, X and ids
    for leaf, sharding in per_row:
        assert sharding.is_equivalent_to(over, leaf.ndim), leaf.shape
    total, owns, _ = compiled.output_shardings
    for sharding in (total, *owns):
        assert sharding.is_equivalent_to(over, 1)
    # the partitioned module holds a device's shapes: the residual made whole
    # is the one array as long as the batch, and no matrix is
    text = compiled.as_text()
    assert re.search(rf"f32\[{rows}\]", text)
    assert not re.search(rf"\[{rows},\d+\]", text)
    assert re.search(rf"f32\[{rows // 4},{WIDTH}\]", text)


# -- (e) names and counters ------------------------------------------------------

def test_the_exchange_stage_reaches_the_lowered_program(mesh_run):
    fused, args = _fused_program(mesh_run[0])
    text = fused.lower(*args, r=1).as_text(debug_info=True)
    paths = re.findall(r'[/"]' + re.escape(stages.MESH_EXCHANGE) + r'[/"]', text)
    assert len(paths) >= 4  # two all-gathers a random effect, and their copies
    assert "all_gather" in text and "psum" in text


def test_prepare_counts_what_the_lane_cut_leaves_uneven(mesh):
    REGISTRY.reset(prefix="re_mesh.")
    descent, _ = _descent(mesh)
    prepared = {
        c: descent.coordinates[c]._prepared for c in ("per_user", "per_item")
    }
    counters = {
        k: v["value"] for k, v in REGISTRY.snapshot("re_mesh.")["counters"].items()
    }
    lanes = sum(pb.num_real for p in prepared.values() for pb in p)
    padded = sum(pb.mask.shape[0] for p in prepared.values() for pb in p)
    assert counters["re_mesh.lanes"] == lanes == (USERS - 1) + ITEMS
    assert counters["re_mesh.padded_lanes"] == padded > lanes
    assert all(pb.mask.shape[0] % 4 == 0 for p in prepared.values() for pb in p)
    # every real row is in one bucket of each effect
    assert counters["re_mesh.rows_mean_chip"] == pytest.approx(2 * N / 4)
    assert N / 4 * 2 <= counters["re_mesh.rows_max_chip"] <= 2 * N
    # staged a device's slice at a time: every leaf lies over the mesh
    for p in prepared.values():
        for pb in p:
            for leaf in jax.tree.leaves((pb.static, pb.row_idx, pb.mask)):
                assert len(leaf.sharding.device_set) == 4
                assert leaf.addressable_shards[0].data.shape[0] == leaf.shape[0] // 4
    REGISTRY.reset(prefix="re_mesh.")


@pytest.mark.parametrize("relabelled", [False, True])
def test_the_ordered_copy_under_the_mesh_is_the_slot_index_descent_bit_for_bit(
    mesh, relabelled
):
    """Users in blocks (no order: nothing gathered), items scattered: a device
    gathers its own segment of the item effect's order out of the whole
    residual, the segments unequal in rows so the shorter end in filler, and
    the same segments whatever the entities are called. Coefficients, scores
    and per-entity iterations of two outer iterations are those of the same
    descent reading one index a slot, bit for bit."""
    from stage_programs import slot_index_reading

    REGISTRY.reset(prefix="re_offsets.")
    descent, _ = _descent(mesh, relabelled=relabelled)
    user, item = (descent.coordinates[c]._prepared for c in ("per_user", "per_item"))
    assert all(pb.order is None and pb.row_idx.ndim == 1 for pb in user)
    order = item[0].order
    assert all(pb.order is order and pb.row_idx.ndim == 1 for pb in item)
    assert order.sharding.is_equivalent_to(NamedSharding(mesh, P("data")), 1)
    held = sum(
        (np.asarray(pb.mask) != 0).sum(axis=1).reshape(4, -1).sum(axis=1) for pb in item
    )
    assert held.sum() == N and len(set(held.tolist())) > 1
    segments = np.asarray(order).reshape(4, -1)
    assert segments.shape[1] == 1 + held.max()
    for segment, rows in zip(segments, held):
        assert segment[0] == 0 and not segment[1 + rows:].any()  # filler: row 0
    np.testing.assert_array_equal(np.unique(segments), np.arange(N))
    counters = {
        k: v["value"] for k, v in REGISTRY.snapshot("re_offsets.")["counters"].items()
    }
    assert counters["re_offsets.ordered_rows"] == segments.size
    assert counters["re_offsets.run_slots"] == counters["re_offsets.slots"]
    if relabelled:  # a device keeps its lanes, so its segment
        plain, _ = _descent(mesh)
        np.testing.assert_array_equal(
            segments.ravel(), np.asarray(plain.coordinates["per_item"]._prepared[0].order)
        )
    got = descent.run(SEQUENCE, 2)
    assert tuple(SEQUENCE) in descent._fused_outer_cache  # under shard_map, one launch
    reference, _ = _descent(mesh, relabelled=relabelled)
    for cid in ("per_user", "per_item"):
        coordinate = reference.coordinates[cid]
        object.__setattr__(
            coordinate, "_prepared_cache", slot_index_reading(coordinate._prepared)
        )
        assert all(pb.row_idx.ndim == 2 for pb in coordinate._prepared)
    want = reference.run(SEQUENCE, 2)
    bits = lambda a: np.asarray(a).view(np.uint32)
    for c in SEQUENCE:
        np.testing.assert_array_equal(
            bits(_coefficients(got)[c]), bits(_coefficients(want)[c]), err_msg=c)
        np.testing.assert_array_equal(
            bits(_scores(got)[c]), bits(_scores(want)[c]), err_msg=c)
    for c in ("per_user", "per_item"):
        iterations = np.asarray(got.trackers[c][-1].iterations)
        np.testing.assert_array_equal(
            iterations, np.asarray(want.trackers[c][-1].iterations), err_msg=c)
        assert iterations.max() > 1
    REGISTRY.reset(prefix="re_offsets.")


@pytest.mark.parametrize("relabelled", [False, True])
def test_a_class_sorted_by_size_is_dealt_evenly_whatever_the_labels(mesh, relabelled):
    """A file sorted by entity, the entities by size (what the benchmark's
    ``blocks`` assignment is): cut contiguously in file order the last chip
    would hold a class's fullest lanes, 1.2 times the mean. Dealt, the chips
    hold the same rows to a lane's difference, under any labelling."""
    counts = 33 + np.arange(64) // 2  # one capacity class, 33 to 64 rows
    names = np.random.default_rng(3).permutation(64) if relabelled else np.arange(64)
    ids = np.repeat(names, counts).astype(np.int32)
    n = len(ids)
    rng = np.random.default_rng(0)
    batch = make_game_batch(
        (rng.random(n) < 0.5).astype(np.float32),
        {"pu": rng.normal(size=(n, WIDTH)).astype(np.float32)},
        id_tags={"user": ids}, mesh=mesh,
    )
    grouping = group_by_entity(ids, num_entities=64)
    coordinate = RandomEffectCoordinate(
        coordinate_id="per_user", batch=batch, feature_shard_id="pu",
        random_effect_type="user",
        config=_optimization(OptimizerType.NEWTON_CHOLESKY), grouping=grouping,
        buckets=bucket_entities(grouping), task_type=TASK, num_entities=64,
        mesh=mesh,
    )
    REGISTRY.reset(prefix="re_mesh.")
    (prepared,) = coordinate._prepared
    per_chip = (np.asarray(prepared.mask) != 0).sum(axis=1).reshape(4, -1).sum(axis=1)
    assert per_chip.tolist() == [768, 768, 784, 784]  # contiguous: 592 to 976
    counters = {
        k: v["value"] for k, v in REGISTRY.snapshot("re_mesh.")["counters"].items()
    }
    assert counters["re_mesh.rows_max_chip"] / counters["re_mesh.rows_mean_chip"] < 1.011
    REGISTRY.reset(prefix="re_mesh.")


@pytest.mark.parametrize("prepared", [True, False])
def test_the_report_renders_the_lane_cut(tmp_path, prepared):
    from photon_ml_tpu.obs.report import format_summary, summarize_run
    from photon_ml_tpu.obs.sink import TelemetrySink

    counters = {
        "re_mesh.lanes": {"value": 100.0}, "re_mesh.padded_lanes": {"value": 104.0},
        "re_mesh.rows_max_chip": {"value": 330.0},
        "re_mesh.rows_mean_chip": {"value": 300.0},
    } if prepared else {}
    sink = TelemetrySink(str(tmp_path), run_id="HEAD", shard_index=None)
    sink.emit({"event": "run_start", "t": 1000.0, "schema_version": 1,
               "run_id": "HEAD", "pid": 0, "process_index": 0, "knobs": {},
               "fleet": {"process_count": 1}, "metrics_baseline": {}})
    sink.emit({"event": "run_end", "t": 1002.0, "run_id": "HEAD",
               "metrics": {"counters": counters, "gauges": {},
                           "histograms": {}, "timers": {}}})
    sink.close()
    summary = summarize_run(sink.path)
    if not prepared:
        assert "re_mesh" not in summary and "re-mesh" not in format_summary(summary)
        return
    assert summary["re_mesh"]["lane_pad_ratio"] == pytest.approx(1.04)
    assert summary["re_mesh"]["row_imbalance"] == pytest.approx(1.1)
    assert "padded 1.040x over the mesh" in format_summary(summary)
