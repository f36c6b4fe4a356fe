"""Plain reference for a GLMix with a sparse per-entity random effect.

Independent of ``photon_ml_tpu``; numpy, float32 data and float64 sums. The
model scores a row as

    s_i = x_i . w  +  sum_j v_ij W_u[u_i, c_ij]  +  z_i . W_v[v_i]

with ``(c_ij, v_ij)`` the row's sparse nonzeros in the shard of random
effect ``u`` and ``z_i`` its dense features in the shard of random effect
``v`` (``reference/glmix.score`` has the dense parts). One entity ``e`` of
the sparse effect is an L2-regularised logistic regression on its own rows,
with the other coordinates' scores as fixed offsets:

    f_e(w) = sum_{i: u_i = e} softplus(-(2 y_i - 1) (x_i . w + o_i))
             + 0.5 * l2 * |w|^2

A column none of the entity's rows touches has gradient ``l2 * w_c``, so at
the optimum its coefficient is exactly 0: the problem lives on the entity's
SUPPORT (the columns its rows touch) and is solved there, densified.

Departures from upstream Photon-ML's random-effect data set, none of which
this reference (or the program's path under test) applies: no cap on
features per sample count (``numFeaturesToSamplesRatioUpperBound``), no
Pearson-correlation feature selection, no bounds on an entity's active data
(``numActiveDataPointsUpperBound`` / ``LowerBound``): every row trains.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.newton import entity_newton


def sparse_score(indices, values, ids, W) -> np.ndarray:
    """(n,) float32 contribution of a sparse random effect: row i's
    nonzeros against row ``ids[i]`` of the (entities, d) matrix ``W``."""
    indices, values = np.asarray(indices), np.asarray(values, np.float32)
    W = np.asarray(W, np.float32)
    rows = np.asarray(ids)[:, None]
    return np.sum(values.astype(np.float64) * W[rows, indices], axis=1).astype(
        np.float32
    )


def support_sizes(indices, values, ids, entities: int) -> np.ndarray:
    """(entities,) the number of distinct columns each entity's rows touch
    (nonzero values only)."""
    indices = np.asarray(indices)
    d = int(indices.max()) + 1
    keys = np.asarray(ids, np.int64)[:, None] * d + indices
    keys = np.unique(keys[np.asarray(values) != 0])
    return np.bincount(keys // d, minlength=entities)


class EntityRows:
    """The rows of each entity, found once (one sort of the id column)."""

    def __init__(self, ids):
        ids = np.asarray(ids)
        self.order = np.argsort(ids, kind="stable")
        self.starts = np.searchsorted(ids[self.order], np.arange(ids.max() + 2))

    def of(self, entity: int) -> np.ndarray:
        return self.order[self.starts[entity]:self.starts[entity + 1]]


def entity_support(indices, values) -> np.ndarray:
    """Sorted distinct columns of one entity's rows."""
    return np.unique(np.asarray(indices)[np.asarray(values) != 0])


def entity_dense(indices, values, support) -> np.ndarray:
    """One entity's rows densified over ``support``: (rows, p) float64.
    Entries of a row on the same column add, as in any padded-sparse row."""
    indices, values = np.asarray(indices), np.asarray(values, np.float64)
    X = np.zeros((indices.shape[0], len(support)))
    slot = np.searchsorted(support, indices)
    live = values != 0
    np.add.at(X, (np.nonzero(live)[0], slot[live]), values[live])
    return X


def entity_value_grad(X, y, offsets, w, l2: float):
    """(f_e(w), gradient) on the entity's densified rows, float64."""
    X, y = np.asarray(X, np.float64), np.asarray(y, np.float64)
    w = np.asarray(w, np.float64)
    m = X @ w + np.asarray(offsets, np.float64)
    value = np.sum(np.logaddexp(0.0, -(2.0 * y - 1.0) * m)) + 0.5 * l2 * w @ w
    p = 0.5 * (1.0 + np.tanh(0.5 * m))
    return float(value), X.T @ (p - y) + l2 * w


def entity_solve(X, y, offsets, l2: float) -> np.ndarray:
    """The unique minimiser of ``f_e`` on the support: damped Newton in
    float64 (``reference/newton.entity_newton``), for supports small enough
    for a dense Hessian."""
    return entity_newton(X, y, offsets, l2)
