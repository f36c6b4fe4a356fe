"""GAME layer tests: entity grouping/bucketing, batched random-effect
solves, coordinates, and coordinate descent.

Mirrors the reference's test strategy (SURVEY.md §4): the distributed/batched
implementation is checked against its single-problem twin (per-entity
individual solves), and the GAME pipeline is checked on synthetic GLMix data
with known generating effects.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.config import (
    OptimizationConfig,
    OptimizerConfig,
    RegularizationContext,
)
from photon_ml_tpu.data.synthetic import synthetic_game_data
from photon_ml_tpu.game import (
    CoordinateDescent,
    DenseFeatures,
    FixedEffectCoordinate,
    GameModel,
    RandomEffectCoordinate,
    bucket_entities,
    group_by_entity,
    make_game_batch,
    random_effect_scores,
    train_random_effects,
)
from photon_ml_tpu.game.data import gather_bucket
from photon_ml_tpu.ops.batch import DenseBatch
from photon_ml_tpu.ops.glm import make_objective
from photon_ml_tpu.ops.losses import logistic_loss, loss_for_task, squared_loss
from photon_ml_tpu.optim import lbfgs_minimize
from photon_ml_tpu.types import RegularizationType, TaskType

CFG = OptimizerConfig(max_iterations=50, tolerance=1e-9)


# ---------------------------------------------------------------------------
# grouping / bucketing
# ---------------------------------------------------------------------------
class TestGrouping:
    def test_group_by_entity_counts(self, rng):
        ids = np.array([2, 0, 2, 2, 1, 0], np.int32)
        g = group_by_entity(ids)
        assert g.num_entities == 3
        np.testing.assert_array_equal(g.counts, [2, 1, 3])
        for e in range(3):
            np.testing.assert_array_equal(np.sort(g.active_rows[e]), np.flatnonzero(ids == e))

    def test_active_upper_bound_reservoir(self, rng):
        ids = np.zeros(100, np.int32)
        g = group_by_entity(ids, active_upper_bound=10, seed=1)
        assert g.counts[0] == 100
        assert g.active_counts[0] == 10
        assert len(g.active_rows[0]) == 10
        assert len(np.unique(g.active_rows[0])) == 10

    def test_buckets_cover_all_active_entities(self, rng):
        ids = rng.integers(0, 50, size=400).astype(np.int32)
        g = group_by_entity(ids)
        b = bucket_entities(g)
        all_ents = np.concatenate(b.entity_ids)
        assert sorted(all_ents) == sorted(np.flatnonzero(g.counts > 0))
        for cap, ents, rows in zip(b.capacities, b.entity_ids, b.row_indices):
            assert rows.shape == (len(ents), cap)
            counts = (rows >= 0).sum(axis=1)
            np.testing.assert_array_equal(counts, g.active_counts[ents])
            # capacity is the smallest rung that fits every member
            assert counts.max() <= cap

    def test_gather_bucket_padding_inert(self, rng):
        n, d = 10, 3
        X = rng.normal(size=(n, d)).astype(np.float32)
        labels = rng.normal(size=n).astype(np.float32)
        ids = np.array([0] * 7 + [1] * 3, np.int32)
        g = group_by_entity(ids)
        b = bucket_entities(g, capacities=(8,))
        batch = gather_bucket(
            DenseFeatures(X=jnp.asarray(X)),
            labels,
            np.zeros(n, np.float32),
            np.ones(n, np.float32),
            b.row_indices[0],
        )
        assert batch.X.shape == (2, 8, d)
        # padded slots have weight exactly 0
        counts = (b.row_indices[0] >= 0).sum(axis=1)
        for i, c in enumerate(counts):
            assert float(jnp.sum(batch.weights[i] != 0)) == c


# ---------------------------------------------------------------------------
# batched random-effect solver vs per-entity twin
# ---------------------------------------------------------------------------
class TestRandomEffectSolver:
    @pytest.mark.parametrize("task", [TaskType.LINEAR_REGRESSION, TaskType.LOGISTIC_REGRESSION])
    def test_matches_individual_solves(self, rng, task):
        # E bounds the per-entity twin loop below — each entity is its own
        # distinct-shape jit solve, so E is the compile count, and the
        # batched-vs-individual equivalence is entity-count-independent
        n, d, E = 300, 4, 8
        ids = rng.integers(0, E, size=n).astype(np.int32)
        X = rng.normal(size=(n, d)).astype(np.float32)
        W_true = rng.normal(size=(E, d)).astype(np.float32)
        margin = np.sum(W_true[ids] * X, axis=1)
        if task is TaskType.LOGISTIC_REGRESSION:
            y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float32)
        else:
            y = (margin + rng.normal(scale=0.05, size=n)).astype(np.float32)

        loss = loss_for_task(task)
        g = group_by_entity(ids, num_entities=E)
        b = bucket_entities(g)
        res = train_random_effects(
            DenseFeatures(X=jnp.asarray(X)),
            y,
            np.zeros(n, np.float32),
            np.ones(n, np.float32),
            b,
            E,
            loss,
            CFG,
            l2_weight=1.0,
        )
        # twin: solve each entity's problem individually
        for e in range(E):
            rows = np.flatnonzero(ids == e)
            if len(rows) == 0:
                np.testing.assert_array_equal(np.asarray(res.coefficients[e]), 0.0)
                continue
            batch = DenseBatch(
                X=jnp.asarray(X[rows]),
                labels=jnp.asarray(y[rows]),
                offsets=jnp.zeros(len(rows)),
                weights=jnp.ones(len(rows)),
            )
            obj = make_objective(batch, loss, l2_weight=1.0)
            ref = lbfgs_minimize(obj, jnp.zeros((d,)), CFG)
            np.testing.assert_allclose(
                np.asarray(res.coefficients[e]), np.asarray(ref.w), atol=2e-3, rtol=1e-2
            )

    def test_entity_sharding_matches_unsharded(self, rng):
        from photon_ml_tpu.parallel import data_mesh

        n, d, E = 200, 3, 10
        ids = rng.integers(0, E, size=n).astype(np.int32)
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        g = group_by_entity(ids, num_entities=E)
        b = bucket_entities(g)
        args = (
            DenseFeatures(X=jnp.asarray(X)),
            y,
            np.zeros(n, np.float32),
            np.ones(n, np.float32),
            b,
            E,
            logistic_loss,
            CFG,
        )
        res0 = train_random_effects(*args, l2_weight=0.5)
        res8 = train_random_effects(*args, l2_weight=0.5, mesh=data_mesh(8))
        # not bit-exact: sharding changes XLA reduction shapes, and 50
        # L-BFGS iterations amplify f32 reassociation; both runs satisfy the
        # same 1e-9 gradient tolerance, so compare at optimization (not
        # bit) precision
        np.testing.assert_allclose(
            np.asarray(res0.coefficients), np.asarray(res8.coefficients), atol=3e-4
        )

    def test_scores_gather(self, rng):
        n, d, E = 20, 3, 4
        ids = rng.integers(0, E, size=n).astype(np.int32)
        X = rng.normal(size=(n, d)).astype(np.float32)
        W = rng.normal(size=(E, d)).astype(np.float32)
        s = random_effect_scores(DenseFeatures(X=jnp.asarray(X)), jnp.asarray(ids), jnp.asarray(W))
        np.testing.assert_allclose(np.asarray(s), np.sum(W[ids] * X, axis=1), rtol=1e-5)

    def test_warm_start_preserves_untrained_entities(self, rng):
        n, d, E = 50, 3, 8
        # only entities 0..3 appear in the data
        ids = rng.integers(0, 4, size=n).astype(np.int32)
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = rng.normal(size=n).astype(np.float32)
        g = group_by_entity(ids, num_entities=E)
        b = bucket_entities(g)
        W0 = rng.normal(size=(E, d)).astype(np.float32)
        res = train_random_effects(
            DenseFeatures(X=jnp.asarray(X)), y, np.zeros(n, np.float32),
            np.ones(n, np.float32), b, E, squared_loss, CFG,
            l2_weight=1.0, initial_coefficients=W0,
        )
        # entities 4..7 untouched
        np.testing.assert_array_equal(np.asarray(res.coefficients[4:]), W0[4:])
        assert np.isnan(res.loss_values[4:]).all()
        assert not np.isnan(res.loss_values[:4]).any()


# ---------------------------------------------------------------------------
# coordinate descent
# ---------------------------------------------------------------------------
def _game_setup(rng, task=TaskType.LOGISTIC_REGRESSION, n=600, d_fixed=5,
                effects=None, entity_scale=1.0):
    effects = effects or {"userId": (20, 3)}
    data = synthetic_game_data(rng, n, d_fixed, effects, task=task,
                              entity_scale=entity_scale)
    features = {"global": data.X}
    id_tags = {}
    for name in effects:
        features[f"shard_{name}"] = data.entity_X[name]
        id_tags[name] = data.entity_ids[name]
    batch = make_game_batch(data.y, features, id_tags=id_tags)
    return data, batch


class TestCoordinateDescent:
    def test_fixed_only_matches_single_glm(self, rng):
        """Config D: a single fixed-effect coordinate must equal plain GLM
        training on the same data."""
        data, batch = self._setup_fixed(rng)
        coord = FixedEffectCoordinate(
            coordinate_id="fixed",
            batch=batch,
            feature_shard_id="global",
            config=OptimizationConfig(
                optimizer=CFG,
                regularization=RegularizationContext(RegularizationType.L2),
                regularization_weight=1.0,
            ),
            task_type=TaskType.LOGISTIC_REGRESSION,
            intercept_index=data.intercept_index,
        )
        cd = CoordinateDescent({"fixed": coord}, batch, TaskType.LOGISTIC_REGRESSION)
        result = cd.run(["fixed"], num_iterations=1)

        obj = make_objective(
            batch.batch_for("global"),
            logistic_loss,
            l2_weight=1.0,
            intercept_index=data.intercept_index,
        )
        ref = lbfgs_minimize(
            obj, jnp.zeros((data.X.shape[1],)), CFG
        )
        w_cd = result.model["fixed"].model.coefficients.means
        np.testing.assert_allclose(np.asarray(w_cd), np.asarray(ref.w), atol=1e-4)

    def _setup_fixed(self, rng):
        return _game_setup(rng, effects={"userId": (10, 2)}, entity_scale=0.0)

    def test_glmm_improves_over_fixed_only(self, rng):
        """Config E shape: fixed + per-user random effect on data generated
        with real per-user effects. The mixed model must fit better than the
        fixed effect alone, and per-iteration training must reduce loss."""
        task = TaskType.LINEAR_REGRESSION
        data, batch = _game_setup(
            rng, task=task, n=800, effects={"userId": (15, 3)}, entity_scale=1.5
        )
        fixed = FixedEffectCoordinate(
            coordinate_id="fixed",
            batch=batch,
            feature_shard_id="global",
            config=OptimizationConfig(
                optimizer=CFG,
                regularization=RegularizationContext(RegularizationType.L2),
                regularization_weight=0.1,
            ),
            task_type=task,
            intercept_index=data.intercept_index,
        )
        ids = data.entity_ids["userId"]
        g = group_by_entity(ids, num_entities=15)
        b = bucket_entities(g)
        re = RandomEffectCoordinate(
            coordinate_id="per_user",
            batch=batch,
            feature_shard_id="shard_userId",
            random_effect_type="userId",
            config=OptimizationConfig(
                optimizer=CFG,
                regularization=RegularizationContext(RegularizationType.L2),
                regularization_weight=1.0,
            ),
            grouping=g,
            buckets=b,
            task_type=task,
            num_entities=15,
        )
        cd = CoordinateDescent(
            {"fixed": fixed, "per_user": re}, batch, task,
            validation_batch=batch, evaluators=["RMSE"],
        )
        result = cd.run(["fixed", "per_user"], num_iterations=3)

        rmse_first = result.validation_history[0]["fixed"].metrics["RMSE"]
        rmse_last = result.validation_history[-1]["per_user"].metrics["RMSE"]
        assert rmse_last < rmse_first * 0.8, (rmse_first, rmse_last)

        # recovered per-user coefficients correlate with the generating ones
        W = np.asarray(result.model["per_user"].coefficients)
        W_true = data.w_entity["userId"]
        trained = g.counts >= 10  # entities with enough data
        corr = np.corrcoef(W[trained].ravel(), W_true[trained].ravel())[0, 1]
        assert corr > 0.8, corr

    def test_warm_start_locked_coordinate(self, rng):
        """A coordinate present in the initial model but not in the update
        sequence keeps contributing scores (reference's locked coordinates)."""
        task = TaskType.LINEAR_REGRESSION
        data, batch = _game_setup(rng, task=task, n=300, effects={"userId": (8, 2)})
        fixed = FixedEffectCoordinate(
            coordinate_id="fixed",
            batch=batch,
            feature_shard_id="global",
            config=OptimizationConfig(optimizer=CFG),
            task_type=task,
            intercept_index=data.intercept_index,
        )
        # pretrain fixed alone, then lock it while training the RE
        cd1 = CoordinateDescent({"fixed": fixed}, batch, task)
        m1 = cd1.run(["fixed"], 1).model

        ids = data.entity_ids["userId"]
        g = group_by_entity(ids, num_entities=8)
        re = RandomEffectCoordinate(
            coordinate_id="per_user",
            batch=batch,
            feature_shard_id="shard_userId",
            random_effect_type="userId",
            config=OptimizationConfig(
                optimizer=CFG,
                regularization=RegularizationContext(RegularizationType.L2),
                regularization_weight=1.0,
            ),
            grouping=g,
            buckets=bucket_entities(g),
            task_type=task,
            num_entities=8,
        )
        cd2 = CoordinateDescent({"fixed": fixed, "per_user": re}, batch, task)
        result = cd2.run(["per_user"], 1, initial_model=m1)
        # fixed stayed locked: same coefficients object in the final model
        np.testing.assert_array_equal(
            np.asarray(result.model["fixed"].model.coefficients.means),
            np.asarray(m1["fixed"].model.coefficients.means),
        )
        # and the RE was trained against the fixed effect's residuals:
        # total score must beat the fixed-only score
        pred_mixed = result.model.score(batch)
        pred_fixed = m1.score(batch)
        err_mixed = float(jnp.mean((pred_mixed - batch.labels) ** 2))
        err_fixed = float(jnp.mean((pred_fixed - batch.labels) ** 2))
        assert err_mixed < err_fixed

    def test_out_of_range_entity_scores_zero(self, rng):
        from photon_ml_tpu.game.models import RandomEffectModel

        X = rng.normal(size=(4, 2)).astype(np.float32)
        W = rng.normal(size=(3, 2)).astype(np.float32)
        batch = make_game_batch(
            np.zeros(4, np.float32),
            {"s": X},
            id_tags={"userId": np.array([0, 2, 5, -1], np.int32)},
        )
        m = RandomEffectModel(
            coefficients=jnp.asarray(W), variances=None,
            random_effect_type="userId", feature_shard_id="s",
            task_type=TaskType.LINEAR_REGRESSION,
        )
        s = np.asarray(m.score(batch))
        np.testing.assert_allclose(s[0], X[0] @ W[0], rtol=1e-5)
        np.testing.assert_allclose(s[1], X[1] @ W[2], rtol=1e-5)
        assert s[2] == 0.0 and s[3] == 0.0


class TestBucketMerging:
    def test_merge_respects_target_and_budget(self, rng):
        ids = rng.integers(0, 200, size=3000).astype(np.int32)
        g = group_by_entity(ids)
        fine = bucket_entities(g, target_buckets=100)  # effectively no merge
        merged = bucket_entities(g)  # default target 8
        assert len(merged.capacities) <= max(len(fine.capacities), 8)
        # same entity coverage, counts intact
        np.testing.assert_array_equal(
            np.sort(np.concatenate(merged.entity_ids)),
            np.sort(np.concatenate(fine.entity_ids)),
        )
        total_active = int(g.active_counts.sum())
        padded = sum(
            rows.shape[0] * rows.shape[1] for rows in merged.row_indices
        ) - total_active
        assert padded <= 4.0 * total_active

    def test_degenerate_targets_do_not_crash(self, rng):
        ids = rng.integers(0, 30, size=500).astype(np.int32)
        g = group_by_entity(ids)
        b0 = bucket_entities(g, target_buckets=0)
        b1 = bucket_entities(g, target_buckets=1)
        for b in (b0, b1):
            np.testing.assert_array_equal(
                np.sort(np.concatenate(b.entity_ids)),
                np.sort(np.flatnonzero(g.counts > 0)),
            )

    def test_explicit_capacities_never_merge(self, rng):
        ids = np.repeat(np.arange(20, dtype=np.int32), 3)
        g = group_by_entity(ids)
        b = bucket_entities(g, capacities=(4, 8))
        assert b.capacities == (4,)  # all entities have 3 samples


# ---------------------------------------------------------------------------
# residual offsets: run starts into the file (rows sorted by the effect's id)
# beside run starts into the effect's own order (rows scattered: one gather a
# visit, an index a real row), one descent, bit for bit the slot-index one
# ---------------------------------------------------------------------------
def _blocks_and_shuffled_run(path, monkeypatch, refuse):
    """Fixed + per-user (the file sorted by user: blocks) + per-item
    (scattered) through ``CoordinateDescent.run``; ``refuse`` has both
    effects read their offsets by one index a slot, as before PR 30."""
    from stage_programs import slot_index_reading

    from photon_ml_tpu.game import random_effect as re_mod
    from photon_ml_tpu.obs.metrics import REGISTRY

    monkeypatch.setenv("PHOTON_RE_COMPACT_EVERY", "2" if path == "compacted" else "0")
    task = TaskType.LOGISTIC_REGRESSION
    effects = {"userId": (25, 3), "itemId": (14, 3)}
    data = synthetic_game_data(np.random.default_rng(21), 900, 4, effects, task=task)
    order = np.argsort(data.entity_ids["userId"], kind="stable")
    batch = make_game_batch(
        data.y[order],
        {"global": data.X[order],
         **{f"shard_{t}": data.entity_X[t][order] for t in effects}},
        id_tags={t: data.entity_ids[t][order] for t in effects},
    )
    config = OptimizationConfig(
        optimizer=CFG, regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    coords = {"fixed": FixedEffectCoordinate(
        coordinate_id="fixed", batch=batch, feature_shard_id="global",
        config=config, task_type=task, intercept_index=data.intercept_index,
    )}
    slots = {}
    for tag, (entities, _) in effects.items():
        g = group_by_entity(data.entity_ids[tag][order], num_entities=entities)
        buckets = bucket_entities(g)
        slots[tag] = sum(r.size for r in buckets.row_indices)
        coords[f"per_{tag}"] = RandomEffectCoordinate(
            coordinate_id=f"per_{tag}", batch=batch, feature_shard_id=f"shard_{tag}",
            random_effect_type=tag, config=config, grouping=g, buckets=buckets,
            task_type=task, num_entities=entities,
        )
    compacted_steps, step = [], re_mod._bucket_step_compacted

    def counted_step(*args, **kwargs):
        compacted_steps.append(1)
        return step(*args, **kwargs)

    monkeypatch.setattr(re_mod, "_bucket_step_compacted", counted_step)
    REGISTRY.reset(prefix="re_offsets")
    orders = {}
    for tag in effects:
        coord = coords[f"per_{tag}"]
        orders[tag] = coord._prepared[0].order
        assert all(pb.row_idx.ndim == 1 for pb in coord._prepared)
        if refuse:
            plain = slot_index_reading(coord._prepared)
            assert all(pb.row_idx.ndim == 2 and pb.order is None for pb in plain)
            object.__setattr__(coord, "_prepared_cache", plain)
    # the user effect gathers nothing; the item effect one index a real row
    assert orders["userId"] is None and orders["itemId"].shape == (len(order) + 1,)
    res = CoordinateDescent(coords, batch, task).run(list(coords), num_iterations=2)
    assert bool(compacted_steps) == (path == "compacted")  # ``_lane_prologue``'s path
    counters = {k: v["value"] for k, v in
                REGISTRY.snapshot("re_offsets.")["counters"].items()}
    out = {f"scores.{cid}": np.asarray(s) for cid, s in res.training_scores.items()}
    for cid in coords:
        out[f"w.{cid}"] = np.asarray(res.model[cid].coefficient_means)
        if cid != "fixed":
            out[f"iterations.{cid}"] = np.asarray(res.trackers[cid][-1].iterations)
    return out, counters, slots


@pytest.mark.parametrize("path", ["fused", "compacted"])
def test_run_start_offsets_leave_the_descent_bitwise(path, monkeypatch):
    got, counters, slots = _blocks_and_shuffled_run(path, monkeypatch, refuse=False)
    assert counters == {
        "re_offsets.slots": slots["userId"] + slots["itemId"],
        "re_offsets.run_slots": slots["userId"] + slots["itemId"],
        "re_offsets.ordered_rows": 901,
    }
    want, _, _ = _blocks_and_shuffled_run(path, monkeypatch, refuse=True)
    assert got.keys() == want.keys() and len(got) == 8
    for name in got:
        np.testing.assert_array_equal(
            got[name].view(np.uint32), want[name].view(np.uint32), err_msg=name
        )
    assert got["iterations.per_userId"].max() > 1


@pytest.mark.parametrize("path", ["visit", "descent"])
def test_fixed_visit_takes_the_kernels_at_65_columns(path, rng, monkeypatch):
    """The fixed effect of the GLMix cells (64 features and an intercept):
    its visit decides on the CONCRETE base batch whether the objective takes
    the one-pass kernels (inside the trace ``auto_fused`` sees a tracer and
    says no at any width) and hands the answer on as a static. With the
    kernels taken (interpret mode here) the visit gives the coefficients and
    scores of XLA's path, and L-BFGS evaluates value and gradient once a
    trial: one pass an iteration fewer."""
    from photon_ml_tpu.game import coordinate as coordinate_module

    data, batch = _game_setup(
        rng, n=2500, d_fixed=64, effects={"userId": (10, 2)}, entity_scale=0.0
    )
    assert data.X.shape == (2500, 65)
    task = TaskType.LOGISTIC_REGRESSION
    asked = []

    def run(fused):
        def decide(b):
            asked.append(isinstance(b.X, jax.core.Tracer))
            return fused

        monkeypatch.setattr(coordinate_module, "auto_fused", decide)
        coord = FixedEffectCoordinate(
            coordinate_id="fixed", batch=batch, feature_shard_id="global",
            config=OptimizationConfig(
                optimizer=CFG,
                regularization=RegularizationContext(RegularizationType.L2),
                regularization_weight=1.0,
            ),
            task_type=task, intercept_index=data.intercept_index,
        )
        if path == "visit":
            model, tracker, score, _ = coord.visit(batch.offsets, None)
            trackers = [tracker]
        else:
            res = CoordinateDescent({"fixed": coord}, batch, task).run(["fixed"], 2)
            model, trackers = res.model["fixed"], res.trackers["fixed"]
            score = res.training_scores["fixed"]
        base, fn = coord._visit_base, coord._visit_fn
        jaxpr = jax.make_jaxpr(fn)(
            base, batch.offsets, batch.offsets, jnp.zeros((65,), jnp.float32)
        )
        return model.model.coefficients.means, score, trackers, str(jaxpr)

    w_x, s_x, t_x, text_x = run(False)
    w_k, s_k, t_k, text_k = run(True)
    assert asked == [False, False]  # asked once a coordinate, of a concrete batch
    assert "pallas_call" in text_k and "pallas_call" not in text_x
    np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_x), atol=1e-3)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_x), atol=5e-3)
    assert len(t_k) == len(t_x)
    # the cold visit (a warm one spends its passes backtracking from a unit
    # step): XLA's path takes a value pass a trial and a gradient pass an
    # iteration, the kernels one pass a trial, and few iterations a second
    its_k, its_x = int(t_k[0].iterations), int(t_x[0].iterations)
    assert its_k > 1 and its_x > 1
    assert int(t_x[0].objective_passes) >= 1 + 2 * its_x
    assert 1 + its_k <= int(t_k[0].objective_passes) < 1 + 2 * its_k
