"""K-fold cross-validation for the GLM sweep (SURVEY.md checklist item 7)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.config import OptimizerConfig
from photon_ml_tpu.ops.batch import DenseBatch
from photon_ml_tpu.supervised.cross_validation import cross_validate_glm
from photon_ml_tpu.types import TaskType


def _logistic_batch(rng, n, d, w_true):
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ w_true)))).astype(np.float32)
    return DenseBatch(
        X=jnp.asarray(X), labels=jnp.asarray(y),
        offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
    )


def test_cv_selects_moderate_lambda_and_refits(rng):
    d = 8
    w_true = (rng.normal(size=d) * 0.8).astype(np.float32)
    batch = _logistic_batch(rng, 400, d, w_true)
    res = cross_validate_glm(
        batch,
        TaskType.LOGISTIC_REGRESSION,
        k=4,
        regularization_weights=[0.1, 1.0, 1e4],
        optimizer_config=OptimizerConfig(max_iterations=100, tolerance=1e-8),
        seed=3,
    )
    assert res.metric_name == "AUC"
    # every λ gets one metric per fold
    assert all(len(v) == 4 for v in res.metric_values.values())
    # the absurd λ=1e4 (near-zero model) must not win
    assert res.best_weight != 1e4
    assert res.mean(res.best_weight) >= res.mean(1e4)
    # the refit trains exactly the winning weight on all rows
    assert list(res.final.models.keys()) == [res.best_weight]
    s = res.summary()
    assert s["best_weight"] == res.best_weight
    assert set(s["per_weight"]) == {"0.1", "1.0", "10000.0"}


def test_cv_linear_uses_rmse_lower_is_better(rng):
    d = 5
    w_true = (rng.normal(size=d)).astype(np.float32)
    X = rng.normal(size=(300, d)).astype(np.float32)
    y = X @ w_true + 0.05 * rng.normal(size=300).astype(np.float32)
    batch = DenseBatch(
        X=jnp.asarray(X), labels=jnp.asarray(y),
        offsets=jnp.zeros((300,), jnp.float32),
        weights=jnp.ones((300,), jnp.float32),
    )
    res = cross_validate_glm(
        batch, TaskType.LINEAR_REGRESSION, k=3,
        regularization_weights=[0.01, 1e5], seed=0,
    )
    assert res.metric_name == "RMSE"
    assert res.best_weight == 0.01  # the over-regularized model has huge RMSE
    assert res.mean(0.01) < res.mean(1e5)


def test_cv_rejects_bad_k(rng):
    batch = _logistic_batch(rng, 10, 3, np.ones(3, np.float32))
    with pytest.raises(ValueError):
        cross_validate_glm(batch, TaskType.LOGISTIC_REGRESSION, k=1)
    with pytest.raises(ValueError):
        cross_validate_glm(batch, TaskType.LOGISTIC_REGRESSION, k=11)


@pytest.mark.kernel
def test_cv_fold_ingest_on_tile_coo_matches_the_untiled_fold(rng, monkeypatch):
    """The CV fold-ingest consumer: a fold ingested onto the tile-COO path
    (through the process-wide layout cache) against the same fold left as
    padded-sparse rows on the XLA path, in all three directions (interpret
    mode, retuned-down constants)."""
    import photon_ml_tpu.ops.batch as ob
    import photon_ml_tpu.ops.sparse_tiled as st_mod
    from photon_ml_tpu.ops import tile_cache
    from photon_ml_tpu.ops.batch import SparseBatch
    from photon_ml_tpu.supervised.cross_validation import (
        _ingest_training_batch,
    )

    monkeypatch.setattr(st_mod, "GROUPS_PER_STEP", 8)
    monkeypatch.setattr(st_mod, "SEGMENTS_PER_DMA", 2)
    # simulate an over-budget dense form so ingest tiles (as in the
    # layout-cache CV test)
    monkeypatch.setattr(ob, "maybe_densify", lambda b, *a, **k: b)
    tile_cache.clear()
    n, d, k = 2048, 4096, 4
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    # a row names a column once, as real rows do: the tile-COO build merges
    # repeated draws and squares the merged entry, the XLA path each value
    val[np.tril(idx[:, :, None] == idx[:, None, :], k=-1).any(axis=2)] = 0.0
    batch = SparseBatch(
        indices=jnp.asarray(idx), values=jnp.asarray(val),
        labels=jnp.zeros(n, jnp.float32),
        offsets=jnp.zeros(n, jnp.float32),
        weights=jnp.ones(n, jnp.float32), num_features=d,
    )
    w = jnp.asarray(rng.normal(size=d).astype(np.float32))
    r = jnp.asarray(rng.normal(size=n).astype(np.float32))
    tb = _ingest_training_batch(batch)
    assert isinstance(tb, st_mod.TiledSparseBatch)
    for got, want in (
        (tb.matvec(w), batch.matvec(w)),
        (tb.rmatvec(r), batch.rmatvec(r)),
        (tb.rmatvec_sq(r), batch.rmatvec_sq(r)),
    ):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
        )
    tile_cache.clear()
