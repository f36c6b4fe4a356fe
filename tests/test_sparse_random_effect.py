"""A random effect over a SPARSE shard: the per-entity index map
(``game/projector.sparse_index_map``), buckets classed by capacity and
width, lanes solved in their own subspaces, and the descent over such a
coordinate against the benchmark's plain reference
(``benchmark/reference/glmix_sparse.py``, which imports nothing of the
program). The dense random effect must not have moved: its results on
``tests/test_game.py``'s problems are held, bit for bit, to what the commit
before the sparse subspaces gave (``tests/data/dense_re_before_subspaces.npz``,
written by ``_dense_re_results`` on that commit)."""

import os
import sys

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
    RandomEffectCoordinate,
    SparseFeatures,
    bucket_entities,
    group_by_entity,
    make_game_batch,
    train_random_effects,
)
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)  # the benchmark's reference
# the dense random effect's results as recorded before the sparse one came
# (PR 27), and as L-BFGS leaves them since a step that float32 f cannot
# judge is taken where it halves the gradient (PR 35)
BEFORE = os.path.join(ROOT, "tests", "data", "dense_re_before_subspaces.npz")
RECORDED = os.path.join(ROOT, "tests", "data", "dense_re_recorded.npz")

TASK = TaskType.LOGISTIC_REGRESSION
TIGHT = OptimizerConfig(max_iterations=200, tolerance=1e-9)


def _opt(optimizer=TIGHT, l2=1.0):
    return OptimizationConfig(
        optimizer=optimizer,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=l2,
    )


def _sparse_problem(seed=0, n=1200, d=64, nnz=4, entities=24, intercept=False):
    """Padded-sparse rows with distinct columns a row; with ``intercept``
    the last column is 1.0 in every row."""
    rng = np.random.default_rng(seed)
    free = d - 1 if intercept else d
    idx = np.stack([rng.choice(free, nnz, replace=False) for _ in range(n)])
    val = rng.uniform(0.2, 1.0, size=(n, nnz))
    if intercept:
        idx = np.concatenate([idx, np.full((n, 1), d - 1)], axis=1)
        val = np.concatenate([val, np.ones((n, 1))], axis=1)
    ids = rng.integers(0, entities, size=n).astype(np.int32)
    W = rng.normal(size=(entities, d)) * 0.8
    margin = np.sum(val * W[ids[:, None], idx], axis=1)
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float32)
    return idx.astype(np.int32), val.astype(np.float32), ids, y


def _dense_of(idx, val, d):
    X = np.zeros((len(idx), d), np.float32)
    np.add.at(X, (np.arange(len(idx))[:, None], idx), val)
    return X


# ---------------------------------------------------------------------------
# the index map
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("p, d, rung", [
    (1, 16384, 128), (128, 16384, 128), (129, 16384, 256), (5000, 16384, 8192),
    (9000, 16384, 16384), (40, 64, 64), (700, 1000, 1000),
])
def test_width_ladder(p, d, rung):
    from photon_ml_tpu.game.projector import width_rungs

    assert int(width_rungs(np.array([p]), d)[0]) == rung


@pytest.mark.parametrize("intercept", [False, True])
def test_index_map_supports_and_local_indices(intercept):
    from photon_ml_tpu.game.projector import sparse_index_map

    d, entities = 300, 9
    idx, val, ids, _ = _sparse_problem(1, n=400, d=d, entities=entities,
                                       intercept=intercept)
    val[::7, 0] = 0.0  # a padded nonzero is no part of any support
    ids[ids == 4] = 5  # entity 4 has no row
    icpt = d - 1 if intercept else None
    m = sparse_index_map(idx, val, ids.astype(np.int64), entities, d, icpt)
    for e in range(entities):
        rows = np.flatnonzero(ids == e)
        want = np.unique(idx[rows][val[rows] != 0])
        np.testing.assert_array_equal(m.support(e), want)
        assert m.widths[e] == len(want)
        assert m.rungs[e] == (0 if not len(rows) else min(d, 128) if len(want) <= 128 else 256)
        if not len(rows):
            continue
        cols = m.bucket_columns(np.array([e]), int(m.rungs[e]))[0]
        live = val[rows] != 0
        # a nonzero's slot holds its column; the intercept sits last
        np.testing.assert_array_equal(cols[m.local[rows]][live], idx[rows][live])
        assert np.all(m.local[rows][~live] == 0)
        n_free = len(want) - (1 if intercept else 0)
        assert np.all(cols[n_free:len(cols) - (1 if intercept else 0)] == d)
        if intercept:
            assert cols[-1] == d - 1


def test_lane_order_does_not_follow_the_entity_ids():
    """The same rows under other entity ids: the same lanes in the same
    order in every (capacity, width) class, so the same chunks."""
    rng = np.random.default_rng(4)
    ids = rng.integers(0, 30, size=700).astype(np.int32)
    perm = rng.permutation(30)
    widths = np.where(np.arange(30) % 2 == 0, 128, 256)
    a = bucket_entities(group_by_entity(ids, num_entities=30), widths=widths)
    relabelled = np.empty_like(widths)
    relabelled[perm] = widths
    b = bucket_entities(
        group_by_entity(perm[ids].astype(np.int32), num_entities=30),
        widths=relabelled,
    )
    assert a.capacities == b.capacities and a.widths == b.widths
    for ents_a, ents_b, rows_a, rows_b in zip(
        a.entity_ids, b.entity_ids, a.row_indices, b.row_indices
    ):
        np.testing.assert_array_equal(perm[ents_a], ents_b)
        np.testing.assert_array_equal(rows_a, rows_b)


def test_index_map_does_not_follow_the_entity_ids():
    from photon_ml_tpu.game.projector import sparse_index_map

    d, entities = 200, 12
    idx, val, ids, _ = _sparse_problem(2, n=500, d=d, entities=entities)
    perm = np.random.default_rng(9).permutation(entities)
    a = sparse_index_map(idx, val, ids.astype(np.int64), entities, d)
    b = sparse_index_map(idx, val, perm[ids].astype(np.int64), entities, d)
    np.testing.assert_array_equal(a.local, b.local)
    np.testing.assert_array_equal(a.widths, b.widths[perm])
    np.testing.assert_array_equal(a.rungs, b.rungs[perm])
    for e in range(entities):
        np.testing.assert_array_equal(a.support(e), b.support(perm[e]))
    again = sparse_index_map(idx, val, ids.astype(np.int64), entities, d)
    np.testing.assert_array_equal(a.columns, again.columns)


def test_buckets_are_classed_by_capacity_and_width():
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 40, size=900).astype(np.int32)
    g = group_by_entity(ids, num_entities=40)
    widths = np.where(np.arange(40) % 3 == 0, 128, 256)
    plain = bucket_entities(g)
    classed = bucket_entities(g, widths=widths)
    assert plain.widths is None
    assert sorted(np.concatenate(classed.entity_ids)) == sorted(
        np.concatenate(plain.entity_ids)
    )
    assert len(set(zip(classed.capacities, classed.widths))) == len(classed.widths)
    for cap, width, ents, rows in zip(
        classed.capacities, classed.widths, classed.entity_ids, classed.row_indices
    ):
        assert np.all(widths[ents] == width)
        assert rows.shape == (len(ents), cap)
        assert np.all(np.diff(rows[:, 0]) > 0)  # by first row, not by id
        np.testing.assert_array_equal((rows >= 0).sum(axis=1), g.active_counts[ents])


# ---------------------------------------------------------------------------
# the solve
# ---------------------------------------------------------------------------
def _train_sparse(idx, val, ids, y, d, entities, offsets=None, **kw):
    g = group_by_entity(ids, num_entities=entities)
    return train_random_effects(
        SparseFeatures(indices=jnp.asarray(idx), values=jnp.asarray(val),
                       num_features=d),
        y, np.zeros(len(y), np.float32) if offsets is None else offsets,
        np.ones(len(y), np.float32), bucket_entities(g), entities,
        loss_for_task(TASK), TIGHT, l2_weight=1.0, **kw,
    )


@pytest.mark.parametrize("intercept", [False, True])
def test_equal_to_rounding_with_the_full_width_solve(intercept):
    """At d = 64 the solve at full width is affordable: the sparse shard's
    subspace solve agrees with the same rows trained dense at all 64
    columns (what the commit before solved, lane for lane). To rounding:
    a float32 L-BFGS stops where its line search can no longer tell two
    values of the summed loss apart, which leaves coefficients of size 1
    within 1e-3 of each other on two paths."""
    d, entities = 64, 24
    idx, val, ids, y = _sparse_problem(4, d=d, entities=entities,
                                       intercept=intercept)
    icpt = d - 1 if intercept else None
    got = _train_sparse(idx, val, ids, y, d, entities, intercept_index=icpt)
    g = group_by_entity(ids, num_entities=entities)
    full = train_random_effects(
        DenseFeatures(X=jnp.asarray(_dense_of(idx, val, d))), y,
        np.zeros(len(y), np.float32), np.ones(len(y), np.float32),
        bucket_entities(g), entities, loss_for_task(TASK), TIGHT,
        l2_weight=1.0, intercept_index=icpt,
    )
    np.testing.assert_allclose(
        np.asarray(got.coefficients), np.asarray(full.coefficients),
        atol=1e-3, rtol=0,
    )
    assert got.converged.all()


def test_lanes_in_chunks_equal_lanes_at_once(monkeypatch):
    """A class wider than the chunk budget is densified and solved a chunk
    of lanes at a time; the lanes do not notice."""
    import photon_ml_tpu.game.random_effect as re_mod

    d, entities = 256, 40
    idx, val, ids, y = _sparse_problem(5, n=1500, d=d, entities=entities)
    whole = _train_sparse(idx, val, ids, y, d, entities)
    monkeypatch.setattr(re_mod, "_SUBSPACE_CHUNK_BYTES", 4 * 64 * 128 * 4)
    assert re_mod.subspace_chunk_lanes(64, 128, 40) == 4
    # the fewest chunks, of one size, and the same for the padded lanes
    assert re_mod.subspace_chunk_lanes(64, 128, 21) == 4  # 6 chunks: 24 lanes
    assert re_mod.subspace_chunk_lanes(64, 128, 24) == 4
    assert re_mod.subspace_chunk_lanes(64, 128, 9) == 3  # 3 chunks, not 4 + 4 + 1
    chunked = _train_sparse(idx, val, ids, y, d, entities)
    np.testing.assert_allclose(
        np.asarray(chunked.coefficients), np.asarray(whole.coefficients),
        atol=1e-6, rtol=0,
    )
    np.testing.assert_array_equal(chunked.iterations, whole.iterations)


def _as_on_a_tpu(monkeypatch):
    """``subspace_one_read`` as a TPU backend answers it: the kernels' own
    shape gate (they run in interpret mode here)."""
    import photon_ml_tpu.game.random_effect as re_mod
    from photon_ml_tpu.ops import fused

    monkeypatch.setattr(re_mod, "fused_for_shape", fused.supports_fused)


@pytest.mark.parametrize("capacity, width, on_a_tpu", [
    (64, 256, False), (64, 1024, False),  # a lane shorter than a 128-row tile
    (128, 1024, True), (128, 128, True), (256, 2048, True), (2048, 4096, True),
    (2048, 8192, True), (8192, 8192, True),  # the tile comes down to 128 rows
    (128, 14336, True), (128, 16384, False),  # two such tiles within the budget
    (256, 1000, False), (256, 64, False),  # a narrow shard's own width
])
def test_the_rule_reads_the_class_shape_and_the_backend(
    monkeypatch, capacity, width, on_a_tpu
):
    """``subspace_one_read``: off a TPU no class takes the kernel, so every
    result recorded here is what it was; on one, the classes whose lanes
    hold a whole float32 row-major tile."""
    from photon_ml_tpu.game.random_effect import subspace_one_read

    assert not subspace_one_read(capacity, width)
    _as_on_a_tpu(monkeypatch)
    assert subspace_one_read(capacity, width) == on_a_tpu


def _skewed_problem(seed, d, counts, nnz=6):
    """Entities of ``counts`` rows each, in blocks."""
    rng = np.random.default_rng(seed)
    ids = np.repeat(np.arange(len(counts)), counts).astype(np.int32)
    n = len(ids)
    idx = rng.integers(0, d, size=(n, nnz)).astype(np.int32)
    val = rng.uniform(0.2, 1.0, size=(n, nnz)).astype(np.float32)
    W = rng.normal(size=(len(counts), d)) * 0.8
    margin = np.sum(val * W[ids[:, None], idx], axis=1)
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margin))).astype(np.float32)
    return idx, val, ids, y


@pytest.mark.parametrize("schedule", ["single", "chunked", "compacted"])
def test_the_kernel_arm_solves_what_the_sweeps_solve(monkeypatch, schedule):
    """``_solve_bucket`` on subspace classes on both sides of the rule (40
    rows: the sweeps either way; 130 to 300 rows: the kernel where the rule
    says so), the kernel arm against XLA's: coefficients to 1e-5, iteration
    counts within one (the kernel adds a tile's partials in its own slot,
    so sums differ in order). ``chunked``: the lanes a ``lax.map`` of
    chunks. ``compacted``: the host-driven schedule, which builds its lanes
    with the same constructor, equals the single launch BITWISE on the
    kernel arm too."""
    import jax

    import photon_ml_tpu.game.random_effect as re_mod
    from photon_ml_tpu.obs.metrics import REGISTRY

    d = 2048
    counts = [40, 35, 130, 200, 250, 140, 300, 260]
    idx, val, ids, y = _skewed_problem(12, d, counts)
    offsets = (0.2 * np.random.default_rng(13).normal(size=len(y))).astype(np.float32)
    loose = OptimizerConfig(max_iterations=60, tolerance=1e-6)

    def train():
        jax.clear_caches()  # the rule is read at trace time
        REGISTRY.reset(prefix="re_subspace")
        g = group_by_entity(ids, num_entities=len(counts))
        out = train_random_effects(
            SparseFeatures(indices=jnp.asarray(idx), values=jnp.asarray(val),
                           num_features=d),
            y, offsets, np.ones(len(y), np.float32), bucket_entities(g),
            len(counts), loss_for_task(TASK), loose, l2_weight=1.0,
        )
        counters = REGISTRY.snapshot("re_subspace.")["counters"]
        return out, {k: v["value"] for k, v in counters.items()}

    if schedule == "chunked":
        monkeypatch.setattr(re_mod, "_SUBSPACE_CHUNK_BYTES", 2 * 256 * 2048 * 4)
    sweeps, off = train()
    assert off["re_subspace.one_read_bytes"] == 0
    _as_on_a_tpu(monkeypatch)
    try:
        kernel, on = train()
        if schedule == "compacted":
            monkeypatch.setenv("PHOTON_RE_COMPACT_EVERY", "3")
            compacted, _ = train()
    finally:
        jax.clear_caches()
    assert 0 < on["re_subspace.one_read_bytes"] < on["re_subspace.dense_bytes"]
    assert on["re_subspace.dense_bytes"] == off["re_subspace.dense_bytes"]
    np.testing.assert_allclose(
        np.asarray(kernel.coefficients), np.asarray(sweeps.coefficients),
        atol=1e-5, rtol=0,
    )
    assert np.abs(kernel.iterations - sweeps.iterations).max() <= 1
    assert kernel.converged.all() and kernel.iterations.max() > 3
    if schedule == "compacted":
        np.testing.assert_array_equal(
            np.asarray(compacted.coefficients).view(np.uint32),
            np.asarray(kernel.coefficients).view(np.uint32),
        )
        np.testing.assert_array_equal(compacted.iterations, kernel.iterations)


def _sparse_descent(idx, val, ids, y, d, entities, eager=False, seed=6):
    rng = np.random.default_rng(seed)
    Xf = rng.normal(size=(len(y), 4)).astype(np.float32)
    batch = make_game_batch(
        y,
        {"global": Xf,
         "per_user": SparseFeatures(indices=jnp.asarray(idx),
                                    values=jnp.asarray(val), num_features=d)},
        id_tags={"userId": ids},
    )
    g = group_by_entity(ids, num_entities=entities)
    coords = {
        "fixed": FixedEffectCoordinate(
            coordinate_id="fixed", batch=batch, feature_shard_id="global",
            config=_opt(), task_type=TASK,
        ),
        "per_user": RandomEffectCoordinate(
            coordinate_id="per_user", batch=batch, feature_shard_id="per_user",
            random_effect_type="userId", config=_opt(), grouping=g,
            buckets=bucket_entities(g), task_type=TASK, num_entities=entities,
        ),
    }
    if eager:
        # per-visit validation keeps the descent on the host loop, whose
        # visits go through ``train``
        cd = CoordinateDescent(coords, batch, TASK, validation_batch=batch,
                               evaluators=["AUC"])
    else:
        cd = CoordinateDescent(coords, batch, TASK)
    return Xf, batch, cd.run(["fixed", "per_user"], 2)


@pytest.fixture(scope="module")
def descent():
    d, entities = 512, 30
    idx, val, ids, y = _sparse_problem(6, n=1500, d=d, entities=entities)
    Xf, batch, res = _sparse_descent(idx, val, ids, y, d, entities)
    return dict(d=d, entities=entities, idx=idx, val=val, ids=ids, y=y, Xf=Xf,
                res=res)


def test_descent_scores_are_the_references(descent):
    from benchmark.reference import glmix, glmix_sparse

    res = descent["res"]
    want = np.asarray(glmix.score(
        (jnp.asarray(descent["Xf"]), res.model["fixed"].coefficient_means), []
    )) + glmix_sparse.sparse_score(
        descent["idx"], descent["val"], descent["ids"],
        res.model["per_user"].coefficient_means,
    )
    got = sum(np.asarray(s) for s in res.training_scores.values())
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


@pytest.mark.parametrize("entity", [0, 7, 13, 29])
def test_descent_entities_sit_at_the_references_optimum(descent, entity):
    """Zero outside the support, and on it the unique minimiser of the
    entity's problem against the fixed effect's final scores (the random
    effect is the sequence's last coordinate)."""
    from benchmark.reference import glmix, glmix_sparse

    res = descent["res"]
    rows = np.flatnonzero(descent["ids"] == entity)
    idx, val = descent["idx"][rows], descent["val"][rows]
    support = glmix_sparse.entity_support(idx, val)
    w = np.asarray(res.model["per_user"].coefficient_means)[entity]
    outside = np.ones(descent["d"], bool)
    outside[support] = False
    assert np.all(w[outside] == 0.0)
    fixed = np.asarray(glmix.score(
        (jnp.asarray(descent["Xf"]), res.model["fixed"].coefficient_means), []
    ))
    X = glmix_sparse.entity_dense(idx, val, support)
    w_ref = glmix_sparse.entity_solve(X, descent["y"][rows], fixed[rows], 1.0)
    np.testing.assert_allclose(w[support], w_ref, atol=2e-4, rtol=0)
    _, g_w = glmix_sparse.entity_value_grad(
        X, descent["y"][rows], fixed[rows], w[support], 1.0
    )
    _, g_0 = glmix_sparse.entity_value_grad(
        X, descent["y"][rows], fixed[rows], np.zeros(len(support)), 1.0
    )
    assert np.linalg.norm(g_w) <= 1e-3 * np.linalg.norm(g_0)


def test_the_eager_visits_agree_with_the_fused_ones(descent):
    _, _, eager = _sparse_descent(
        descent["idx"], descent["val"], descent["ids"], descent["y"],
        descent["d"], descent["entities"], eager=True,
    )
    np.testing.assert_allclose(
        np.asarray(eager.model["per_user"].coefficient_means),
        np.asarray(descent["res"].model["per_user"].coefficient_means),
        atol=1e-5, rtol=0,
    )
    tracker = eager.trackers["per_user"][-1]
    assert tracker.converged.all() and tracker.iterations.max() > 1


def test_subspace_counters_are_counted_at_prepare_time():
    from photon_ml_tpu.game.random_effect import prepare_buckets
    from photon_ml_tpu.obs.metrics import REGISTRY

    d, entities = 700, 10
    idx, val, ids, y = _sparse_problem(8, n=600, d=d, entities=entities, nnz=6)
    g = group_by_entity(ids, num_entities=entities)
    REGISTRY.reset(prefix="re_subspace")
    prepared = prepare_buckets(
        SparseFeatures(indices=jnp.asarray(idx), values=jnp.asarray(val),
                       num_features=d),
        y, np.ones(len(y), np.float32), bucket_entities(g),
    )
    snap = REGISTRY.snapshot("re_subspace.")
    counters = {k: v["value"] for k, v in snap["counters"].items()}
    supports = [len(np.unique(idx[ids == e])) for e in range(entities)]
    assert counters["re_subspace.entities"] == entities
    assert counters["re_subspace.support_columns"] == sum(supports)
    assert counters["re_subspace.padded_columns"] == sum(
        pb.num_real * pb.static.num_features for pb in prepared
    )
    assert counters["re_subspace.width_classes"] == len(
        {pb.static.num_features for pb in prepared}
    )
    assert snap["timers"]["re_subspace.build"]["calls"] == 1
    # the float32 bytes of the densified lanes, and (off a TPU) none of them
    # read once
    assert counters["re_subspace.dense_bytes"] == sum(
        4 * pb.num_real * pb.static.labels.shape[1] * pb.static.num_features
        for pb in prepared
    )
    assert counters["re_subspace.one_read_bytes"] == 0
    for pb in prepared:  # local indices, flat, inside the lane's width
        assert pb.static.indices.shape == (
            pb.static.labels.shape[0], pb.static.labels.shape[1] * 6
        )
        assert int(pb.static.indices.max()) < pb.static.num_features
        assert pb.columns.shape == (pb.static.labels.shape[0], pb.static.num_features)


# ---------------------------------------------------------------------------
# the dense random effect has not moved
# ---------------------------------------------------------------------------
def _solver_problem(task):
    rng = np.random.default_rng(12345)
    n, d, entities = 300, 4, 8
    ids = rng.integers(0, entities, size=n).astype(np.int32)
    X = rng.normal(size=(n, d)).astype(np.float32)
    margin = np.sum(rng.normal(size=(entities, d)).astype(np.float32)[ids] * X, axis=1)
    if task is TaskType.LOGISTIC_REGRESSION:
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    else:
        y = (margin + rng.normal(scale=0.05, size=n)).astype(np.float32)
    return ids, X, y, entities


def _descent_data(width):
    rng = np.random.default_rng(12345)
    return synthetic_game_data(rng, 800, 5, {"userId": (15, width)},
                               task=TASK, entity_scale=1.5)


def _dense_re_results() -> dict[str, np.ndarray]:
    """``tests/test_game.py``'s problems through the dense random effect:
    the batched solver alone, and a descent's fused and eager visits."""
    out = {}
    for task in (TaskType.LINEAR_REGRESSION, TaskType.LOGISTIC_REGRESSION):
        ids, X, y, entities = _solver_problem(task)
        n = len(y)
        g = group_by_entity(ids, num_entities=entities)
        res = train_random_effects(
            DenseFeatures(X=jnp.asarray(X)), y, np.zeros(n, np.float32),
            np.ones(n, np.float32), bucket_entities(g), entities,
            loss_for_task(task), OptimizerConfig(max_iterations=50, tolerance=1e-9),
            l2_weight=1.0,
        )
        out[f"solver.{task.name}"] = np.asarray(res.coefficients)
    for name, optimizer, width, validate in (
        ("lbfgs", OptimizerConfig(max_iterations=50, tolerance=1e-9), 3, False),
        ("newton8", OptimizerConfig(
            optimizer_type=OptimizerType.NEWTON_CHOLESKY, max_iterations=20,
            tolerance=1e-7), 8, False),
        ("eager", OptimizerConfig(max_iterations=50, tolerance=1e-9), 3, True),
    ):
        data = _descent_data(width)
        batch = make_game_batch(
            data.y, {"global": data.X, "shard": data.entity_X["userId"]},
            id_tags={"userId": data.entity_ids["userId"]},
        )
        g = group_by_entity(data.entity_ids["userId"], num_entities=15)
        coords = {
            "fixed": FixedEffectCoordinate(
                coordinate_id="fixed", batch=batch, feature_shard_id="global",
                config=_opt(l2=0.1), task_type=TASK,
                intercept_index=data.intercept_index,
            ),
            "per_user": RandomEffectCoordinate(
                coordinate_id="per_user", batch=batch, feature_shard_id="shard",
                random_effect_type="userId", config=_opt(optimizer), grouping=g,
                buckets=bucket_entities(g), task_type=TASK, num_entities=15,
            ),
        }
        extra = dict(validation_batch=batch, evaluators=["AUC"]) if validate else {}
        res = CoordinateDescent(coords, batch, TASK, **extra).run(
            ["fixed", "per_user"], 3
        )
        out[f"descent.{name}.per_user"] = np.asarray(
            res.model["per_user"].coefficient_means
        )
        out[f"descent.{name}.fixed"] = np.asarray(
            res.model["fixed"].coefficient_means
        )
        out[f"descent.{name}.scores"] = np.asarray(
            sum(res.training_scores.values())
        )
    return out


@pytest.fixture(scope="module")
def dense_now():
    return _dense_re_results()


@pytest.mark.parametrize("name", [
    "solver.LINEAR_REGRESSION", "solver.LOGISTIC_REGRESSION",
    "descent.lbfgs.per_user", "descent.lbfgs.fixed", "descent.lbfgs.scores",
    "descent.newton8.per_user", "descent.newton8.fixed", "descent.newton8.scores",
    "descent.eager.per_user", "descent.eager.fixed", "descent.eager.scores",
])
def test_the_dense_random_effect_is_bit_for_bit_what_it_was(dense_now, name):
    before = np.load(RECORDED)
    assert dense_now[name].dtype == before[name].dtype
    np.testing.assert_array_equal(dense_now[name], before[name])


def _entity_gradient_norms(X, ids, y, offsets, W, task, l2=1.0):
    """Per entity, the float64 gradient norm of its own L2-regularised
    problem at its row of ``W``."""
    X, y, W = (np.asarray(a, np.float64) for a in (X, y, W))
    m = offsets + np.sum(X * W[ids], axis=1)
    r = (1 / (1 + np.exp(-m)) if task is TaskType.LOGISTIC_REGRESSION else m) - y
    g = l2 * W
    np.add.at(g, ids, r[:, None] * X)
    return np.linalg.norm(g, axis=1)


@pytest.mark.parametrize("name", [
    "solver.LINEAR_REGRESSION", "solver.LOGISTIC_REGRESSION",
    "descent.lbfgs", "descent.newton8", "descent.eager",
])
def test_the_rerecorded_solves_are_the_tighter_ones(name):
    """What PR 35 re-recorded: the worst entity's gradient at the recorded
    coefficients is a tenth of what it was at the ones recorded before, or
    less (solver: 1.8e-3 -> 1.2e-4 and 2.8e-3 -> 2.1e-4, each the one lane
    whose last step did not halve its gradient; the descents' last visit:
    1.1e-2 -> 2.9e-4, 2.1e-3 -> 8.9e-5), and in the solver's problems,
    which both recordings solve from the same start, no entity's is
    larger."""
    norms = []
    for path in (BEFORE, RECORDED):
        rec = np.load(path)
        if name.startswith("solver."):
            task = TaskType[name.split(".")[1]]
            ids, X, y, _ = _solver_problem(task)
            norms.append(_entity_gradient_norms(X, ids, y, 0.0, rec[name], task))
        else:
            data = _descent_data(8 if name.endswith("newton8") else 3)
            fixed = np.asarray(data.X, np.float64) @ rec[f"{name}.fixed"]
            norms.append(_entity_gradient_norms(
                data.entity_X["userId"], np.asarray(data.entity_ids["userId"]),
                data.y, fixed, rec[f"{name}.per_user"], TASK,
            ))
    was, now = norms
    assert now.max() <= 0.1 * was.max()
    if name.startswith("solver."):
        assert np.all(now <= was)


@pytest.mark.parametrize("prepared", [True, False])
def test_the_run_report_renders_the_subspace_counters(tmp_path, prepared):
    """``obs/report`` shows ``re_subspace.*`` on runs that prepared a sparse
    random effect, and no new key on runs that did not."""
    from photon_ml_tpu.obs.report import format_summary, summarize_run
    from photon_ml_tpu.obs.sink import TelemetrySink

    counters = {
        "re_subspace.entities": {"value": 10.0},
        "re_subspace.support_columns": {"value": 2000.0},
        "re_subspace.padded_columns": {"value": 3000.0},
        "re_subspace.width_classes": {"value": 2.0},
        "re_subspace.dense_bytes": {"value": 4.0e9},
        "re_subspace.one_read_bytes": {"value": 3.0e9},
    } if prepared else {}
    timers = {"re_subspace.build": {"seconds": 0.25, "count": 1}} if prepared else {}
    sink = TelemetrySink(str(tmp_path), run_id="SUB", shard_index=None)
    sink.emit({"event": "run_start", "t": 1000.0, "schema_version": 1,
               "run_id": "SUB", "pid": 0, "process_index": 0, "knobs": {},
               "fleet": {"process_count": 1}, "metrics_baseline": {}})
    sink.emit({"event": "run_end", "t": 1002.0, "run_id": "SUB",
               "metrics": {"counters": counters, "gauges": {},
                           "histograms": {}, "timers": timers}})
    sink.close()
    summary = summarize_run(sink.path)
    if not prepared:
        assert "re_subspace" not in summary
        assert "re-subspace" not in format_summary(summary)
        return
    assert summary["re_subspace"]["width_pad_ratio"] == 1.5
    assert summary["re_subspace"]["build_s"] == 0.25
    assert "re-subspace: 10 entities" in format_summary(summary)
    assert "solved at 1.50x in 2 width classes" in format_summary(summary)
    assert summary["re_subspace"]["dense_bytes"] == 4.0e9
    assert summary["re_subspace"]["one_read_byte_share"] == 0.75
    assert "75.0% read once a value-and-gradient" in format_summary(summary)


# ---------------------------------------------------------------------------
# residual offsets by run starts: a sparse per-user effect over rows sorted
# by user (width classes, chunk padding; every lane still one run) beside a
# dense per-item effect over scattered rows
# ---------------------------------------------------------------------------
def _blocks_and_shuffled_sparse_run(path, monkeypatch, refuse):
    """``refuse``: both effects read their offsets one index a slot."""
    from stage_programs import slot_index_reading

    from photon_ml_tpu.obs.metrics import REGISTRY

    monkeypatch.setenv("PHOTON_RE_COMPACT_EVERY", "2" if path == "compacted" else "0")
    d, users, items = 400, 18, 9
    idx, val, ids, y = _sparse_problem(9, n=700, d=d, entities=users)
    order = np.argsort(ids, kind="stable")
    idx, val, ids, y = idx[order], val[order], ids[order], y[order]
    rng = np.random.default_rng(10)
    item_ids = rng.integers(0, items, size=len(y)).astype(np.int32)
    batch = make_game_batch(
        y,
        {"global": rng.normal(size=(len(y), 4)).astype(np.float32),
         "per_user": SparseFeatures(indices=jnp.asarray(idx), values=jnp.asarray(val),
                                    num_features=d),
         "per_item": rng.normal(size=(len(y), 3)).astype(np.float32)},
        id_tags={"userId": ids, "itemId": item_ids},
    )
    config = _opt(OptimizerConfig(max_iterations=40, tolerance=1e-8))
    coords = {"fixed": FixedEffectCoordinate(
        coordinate_id="fixed", batch=batch, feature_shard_id="global",
        config=config, task_type=TASK,
    )}
    for cid, tag, column, entities in (("per_user", "userId", ids, users),
                                       ("per_item", "itemId", item_ids, items)):
        g = group_by_entity(column, num_entities=entities)
        coords[cid] = RandomEffectCoordinate(
            coordinate_id=cid, batch=batch, feature_shard_id=cid,
            random_effect_type=tag, config=config, grouping=g,
            buckets=bucket_entities(g), task_type=TASK, num_entities=entities,
        )
    REGISTRY.reset(prefix="re_offsets")
    orders = {cid: coords[cid]._prepared[0].order for cid in ("per_user", "per_item")}
    # the users' lanes are runs of the file; the items' rows are gathered once
    assert orders["per_user"] is None and orders["per_item"].shape == (len(y) + 1,)
    if refuse:
        for cid in orders:
            object.__setattr__(
                coords[cid], "_prepared_cache", slot_index_reading(coords[cid]._prepared)
            )
    res = CoordinateDescent(coords, batch, TASK).run(list(coords), 2)
    counters = {k: v["value"] for k, v in
                REGISTRY.snapshot("re_offsets.")["counters"].items()}
    out = {f"scores.{cid}": np.asarray(s) for cid, s in res.training_scores.items()}
    for cid in coords:
        out[f"w.{cid}"] = np.asarray(res.model[cid].coefficient_means)
        if cid != "fixed":
            out[f"iterations.{cid}"] = np.asarray(res.trackers[cid][-1].iterations)
    # the classes that solve (by capacity and width), not the coordinate's buckets
    slots = {cid: sum(pb.num_real * pb.mask.shape[1] for pb in coords[cid]._prepared)
             for cid in ("per_user", "per_item")}
    forms = {cid: {pb.row_idx.ndim for pb in coords[cid]._prepared}
             for cid in ("per_user", "per_item")}
    return out, counters, slots, forms


@pytest.mark.parametrize("path", ["fused", "compacted"])
def test_run_start_offsets_leave_the_sparse_descent_bitwise(path, monkeypatch):
    got, counters, slots, forms = _blocks_and_shuffled_sparse_run(
        path, monkeypatch, refuse=False
    )
    assert forms == {"per_user": {1}, "per_item": {1}}
    assert counters == {
        "re_offsets.slots": slots["per_user"] + slots["per_item"],
        "re_offsets.run_slots": slots["per_user"] + slots["per_item"],
        "re_offsets.ordered_rows": 701,
    }
    want, _, _, forms = _blocks_and_shuffled_sparse_run(
        path, monkeypatch, refuse=True
    )
    assert forms == {"per_user": {2}, "per_item": {2}}
    assert got.keys() == want.keys() and len(got) == 8
    for name in got:
        np.testing.assert_array_equal(
            got[name].view(np.uint32), want[name].view(np.uint32), err_msg=name
        )
    assert got["iterations.per_user"].max() > 1
