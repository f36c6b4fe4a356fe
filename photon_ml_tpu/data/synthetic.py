"""Synthetic dataset generators for tests and benchmarks.

Parity role: ``photon-test-utils::GameTestUtils`` / ``CommonTestUtils``
dataset builders (SURVEY.md §2.5) — plus the benchmark configs of
BASELINE.json need reproducible data at arbitrary scale.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from photon_ml_tpu.ops.batch import DenseBatch, dense_batch_from_numpy
from photon_ml_tpu.types import TaskType


def synthetic_glm_data(
    rng: np.random.Generator,
    n: int,
    d: int,
    task: TaskType = TaskType.LOGISTIC_REGRESSION,
    noise: float = 0.1,
    add_intercept: bool = True,
    dtype=np.float32,
) -> tuple[DenseBatch, int | None, np.ndarray]:
    """Dense GLM problem with known ground-truth weights.

    Returns (batch, intercept_index, w_true).
    """
    X = rng.normal(size=(n, d)).astype(dtype)
    intercept_index = None
    if add_intercept:
        X = np.concatenate([X, np.ones((n, 1), dtype)], axis=1)
        intercept_index = d
    w_true = (rng.normal(size=X.shape[1]) * 0.5).astype(dtype)
    margin = X @ w_true
    if task is TaskType.LOGISTIC_REGRESSION or task is TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM:
        y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margin))).astype(dtype)
    elif task is TaskType.LINEAR_REGRESSION:
        y = (margin + rng.normal(scale=noise, size=n)).astype(dtype)
    elif task is TaskType.POISSON_REGRESSION:
        y = rng.poisson(np.exp(np.clip(margin, -10, 3))).astype(dtype)
    else:  # pragma: no cover
        raise ValueError(task)
    return dense_batch_from_numpy(X, y, dtype=dtype), intercept_index, w_true


@dataclass(frozen=True)
class GameSyntheticData:
    """Columnar GAME dataset: global features + per-entity memberships.

    ``entity_ids[name]`` maps each sample to an int entity id in
    [0, num_entities[name]); ``entity_features[name]`` is the feature matrix
    for that random effect's shard (here shared with the fixed shard for
    simplicity); ``w_fixed`` / ``w_entity[name]`` are the generating
    coefficients.
    """

    X: np.ndarray  # (n, d_fixed) fixed-effect shard
    y: np.ndarray
    entity_ids: dict[str, np.ndarray]  # name → (n,) int32
    entity_X: dict[str, np.ndarray]  # name → (n, d_re) per-effect shard
    w_fixed: np.ndarray
    w_entity: dict[str, np.ndarray]  # name → (num_entities, d_re)
    intercept_index: int


def synthetic_game_data(
    rng: np.random.Generator,
    n: int,
    d_fixed: int,
    effects: dict[str, tuple[int, int]],
    task: TaskType = TaskType.LOGISTIC_REGRESSION,
    entity_scale: float = 1.0,
    skew: float = 1.5,
    dtype=np.float32,
) -> GameSyntheticData:
    """GLMix-style data: score = fixed(x) + Σ_e w_e[entity_e(i)]·x_e.

    ``effects`` maps effect name → (num_entities, d_re). Entity membership
    follows a Zipf-ish power law (``skew``) so entity sizes are realistically
    imbalanced — the hard case for the reference's per-entity grouping and
    for our bucketed batching.
    """
    X = rng.normal(size=(n, d_fixed)).astype(dtype)
    X = np.concatenate([X, np.ones((n, 1), dtype)], axis=1)
    intercept_index = d_fixed
    w_fixed = (rng.normal(size=d_fixed + 1) * 0.5).astype(dtype)
    margin = X @ w_fixed

    entity_ids: dict[str, np.ndarray] = {}
    entity_X: dict[str, np.ndarray] = {}
    w_entity: dict[str, np.ndarray] = {}
    for name, (num_entities, d_re) in effects.items():
        probs = (1.0 / np.arange(1, num_entities + 1) ** skew)
        probs /= probs.sum()
        ids = rng.choice(num_entities, size=n, p=probs).astype(np.int32)
        Xe = rng.normal(size=(n, d_re)).astype(dtype)
        We = (rng.normal(size=(num_entities, d_re)) * entity_scale).astype(dtype)
        margin = margin + np.sum(We[ids] * Xe, axis=1)
        entity_ids[name] = ids
        entity_X[name] = Xe
        w_entity[name] = We

    if task is TaskType.LOGISTIC_REGRESSION:
        y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-margin))).astype(dtype)
    elif task is TaskType.LINEAR_REGRESSION:
        y = (margin + rng.normal(scale=0.1, size=n)).astype(dtype)
    elif task is TaskType.POISSON_REGRESSION:
        y = rng.poisson(np.exp(np.clip(margin, -10, 3))).astype(dtype)
    else:  # pragma: no cover
        raise ValueError(task)
    return GameSyntheticData(
        X=X,
        y=y,
        entity_ids=entity_ids,
        entity_X=entity_X,
        w_fixed=w_fixed,
        w_entity=w_entity,
        intercept_index=intercept_index,
    )
