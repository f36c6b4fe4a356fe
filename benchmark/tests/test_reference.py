"""Each plain reference against a closed form at a toy size."""

import numpy as np
import pytest

from benchmark.reference import glm, glmix, newton


def _toy(n=64, d=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d))
    y = (rng.uniform(size=n) < 0.5).astype(np.float64)
    return X, y, rng


def _as_sparse(X):
    n, d = X.shape
    return np.tile(np.arange(d, dtype=np.int32), (n, 1)), X.astype(np.float32)


def test_sparse_objective_at_zero_is_n_log_2():
    X, y, _ = _toy()
    idx, val = _as_sparse(X)
    f, g = glm.sparse_value_grad(idx, val, y, np.zeros(X.shape[1]), l2=3.0)
    assert f == pytest.approx(len(y) * np.log(2.0), rel=1e-12)
    np.testing.assert_allclose(g, val.astype(np.float64).T @ (0.5 - y), rtol=1e-10)


def test_sparse_gradient_is_the_derivative_of_the_value():
    X, y, rng = _toy()
    idx, val = _as_sparse(X)
    w = rng.normal(size=X.shape[1])
    f, g = glm.sparse_value_grad(idx, val, y, w, l2=0.7, intercept_index=5,
                                 block_rows=16)
    for j in range(len(w)):
        e = np.zeros_like(w)
        e[j] = 1e-6
        fp, _ = glm.sparse_value_grad(idx, val, y, w + e, 0.7, 5)
        fm, _ = glm.sparse_value_grad(idx, val, y, w - e, 0.7, 5)
        assert g[j] == pytest.approx((fp - fm) / 2e-6, rel=1e-5, abs=1e-6)


def test_duplicate_entries_add_and_padding_is_inert():
    idx = np.array([[1, 1, 0], [2, 0, 0]], np.int32)
    val = np.array([[0.5, 0.25, 0.0], [1.0, 0.0, 0.0]], np.float32)
    y = np.array([1.0, 0.0])
    w = np.array([9.0, 2.0, -1.0])
    f, _ = glm.sparse_value_grad(idx, val, y, w, l2=0.0)
    m = np.array([0.75 * 2.0, -1.0])
    assert f == pytest.approx(np.sum(np.logaddexp(0, -(2 * y - 1) * m)), rel=1e-12)


def test_dense_agrees_with_sparse_and_leaves_the_intercept_unpenalised():
    import jax.numpy as jnp

    X, y, rng = _toy(n=100)  # 100 rows: three blocks of 32 and a tail of 4
    idx, val = _as_sparse(X)
    w = rng.normal(size=X.shape[1])
    want = glm.sparse_value_grad(idx, val, y, w, 2.0, 5)
    got = glm.dense_value_grad(jnp.asarray(val), y, w, 2.0, 5, block_rows=32)
    assert got[0] == pytest.approx(want[0], rel=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-4)
    no_pen = glm.sparse_value_grad(idx, val, y, w, 0.0, 5)
    assert want[1][5] == pytest.approx(no_pen[1][5], rel=1e-12)


def test_dense_over_four_devices_is_the_same_number():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    X, y, rng = _toy(n=128)
    w = rng.normal(size=X.shape[1])
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    rows = NamedSharding(mesh, P("data"))
    Xs = jax.device_put(jnp.asarray(X, jnp.float32), rows)
    ys = jax.device_put(jnp.asarray(y, jnp.float32), rows)
    one = glm.dense_value_grad(jnp.asarray(X, jnp.float32), y, w, 1.0, block_rows=16)
    four = glm.dense_value_grad(Xs, ys, w, 1.0, block_rows=16)
    assert four[0] == pytest.approx(one[0], rel=1e-6)
    np.testing.assert_allclose(four[1], one[1], rtol=1e-5, atol=1e-5)


def test_newton_on_an_intercept_alone_is_the_log_odds():
    y = np.array([1.0] * 30 + [0.0] * 10)
    w = newton.entity_newton(np.ones((40, 1)), y, np.zeros(40), l2=0.0)
    assert w[0] == pytest.approx(np.log(3.0), abs=1e-9)


def test_newton_solution_has_zero_gradient_with_offsets_and_l2():
    X, y, rng = _toy(n=200, d=4, seed=3)
    off = rng.normal(size=200)
    w = newton.entity_newton(X, y, off, l2=1.5)
    p = 1.0 / (1.0 + np.exp(-(X @ w + off)))
    assert np.linalg.norm(X.T @ (p - y) + 1.5 * w) < 1e-9


def test_glmix_score_is_fixed_plus_gathered_dots():
    import jax.numpy as jnp

    Xf = jnp.asarray([[1.0, 2.0], [0.0, 1.0], [3.0, 0.0]])
    Xu = jnp.asarray([[1.0], [2.0], [3.0]])
    ids = jnp.asarray([1, 0, 1])
    s = glmix.score((Xf, np.array([0.5, -1.0])), [(Xu, ids, np.array([[10.0], [100.0]]))])
    np.testing.assert_allclose(np.asarray(s), [-1.5 + 100.0, -1.0 + 20.0, 1.5 + 300.0])
    assert glmix.log_loss(jnp.zeros(3), np.array([1.0, 0.0, 1.0])) == pytest.approx(
        np.log(2.0), rel=1e-6
    )
