"""L-BFGS and OWL-QN as device-resident ``lax.while_loop`` programs.

Reference parity: ``photon-lib::ml.optimization.LBFGS`` (wrapping
``breeze.optimize.LBFGS``, history m=10) and ``OWLQN`` (orthant-wise L1
variant, used whenever the L1 weight is positive) — SURVEY.md §2.1.

TPU-first design:
- The whole solve is one compiled program: two-loop recursion under
  ``lax.fori_loop`` over a fixed-size ring buffer, backtracking Armijo line
  search under ``lax.while_loop``, convergence checks on device. The
  reference pays a driver↔cluster round-trip per objective evaluation; here
  an "evaluation" is a fused matmul pass (+ one psum when sharded) and the
  iteration loop never leaves the device.
- History buffers are fixed (m, d) arrays with a ring index — no dynamic
  shapes, so XLA compiles one tile layout for the whole run.
- OWL-QN shares the implementation: the L1 machinery (pseudo-gradient,
  orthant projection of direction and iterates) switches on statically, so
  the plain L-BFGS path compiles with zero L1 overhead.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.config import OptimizerConfig
from photon_ml_tpu.obs.stages import (
    LBFGS_LINE_SEARCH,
    LBFGS_TWO_LOOP,
    LBFGS_UPDATE,
    stage,
)
from photon_ml_tpu.optim.common import (
    ConvergenceReason,
    OptimizationResult,
    grad_converged,
)

Array = jnp.ndarray

_ARMIJO_C1 = 1e-4
_CURVATURE_EPS = 1e-10
# the part of a float32 f under which a change of f is rounding, not signal
_F_RESOLUTION = 1e-7
# what a step that f cannot judge must leave of the gradient norm, at most
_BLIND_SHRINK = 0.5


class _LbfgsState(NamedTuple):
    w: Array
    f: Array  # objective value at w (incl. L1 term for OWL-QN)
    g: Array  # smooth gradient at w
    pg: Array  # pseudo-gradient (== g when no L1)
    S: Array  # (m, d) s-history ring
    Y: Array  # (m, d) y-history ring
    rho: Array  # (m,) 1/(sᵀy) ring
    count: Array  # int32: number of pairs ever stored (ring head = count-1 mod m)
    it: Array  # int32 iteration counter
    evals: Array  # int32: objective passes so far (incl. line-search trials)
    reason: Array  # int32 ConvergenceReason; loop runs while MAX_ITERATIONS
    done: Array  # bool
    g0_norm: Array
    loss_hist: Array
    gnorm_hist: Array


def _blind_progress(blind, f_new, f, g_new_norm, g_norm):
    """Is a step that Armijo refuses progress all the same? Yes where the
    step is ``blind`` (its first-order gain is under f's float32
    resolution, so f_new - f is rounding, whichever its sign), f did not
    rise by more than that resolution, and the gradient norm at least
    HALVED: a quasi-Newton step that close to the optimum cuts it by far
    more (205 -> 44 -> 0.12 -> 0.02 on the chip), while a norm at its own
    rounding floor drifts down by a few percent a step for as long as it is
    let (a warm per-entity lane ran 200 iterations so). Halving bounds the
    steps taken this way by log2 of the norm's range.

    A float32 sum over 5·10⁶ rows sits at 3·10⁶, one unit in its last
    place 0.25, and Armijo alone stopped the GLMix fixed effect at 2.4e-4
    of its first gradient, tolerance 1e-7 (PERF.md §6, PR 35). The rule
    reads only what the loop observes, so every objective gets it: one
    whose steps f can see is solved as before, bit for bit."""
    level = f_new <= f + _F_RESOLUTION * jnp.abs(f)
    shrank = g_new_norm <= _BLIND_SHRINK * g_norm
    return jnp.logical_and(jnp.logical_and(blind, level), shrank)


def _pseudo_gradient(w: Array, g: Array, l1w: Array) -> Array:
    """OWL-QN pseudo-gradient: the minimal-norm subgradient of
    f(w) + Σ l1wⱼ·|wⱼ|."""
    gp = g + l1w
    gm = g - l1w
    at_zero = jnp.where(gp < 0.0, gp, jnp.where(gm > 0.0, gm, 0.0))
    return jnp.where(w > 0.0, gp, jnp.where(w < 0.0, gm, at_zero))


def _two_loop(pg: Array, S: Array, Y: Array, rho: Array, count: Array, m: int) -> Array:
    """Two-loop recursion: returns r ≈ H⁻¹·pg using the ring-buffer history.
    Unfilled slots contribute exactly zero (their alpha/beta are masked)."""
    valid_n = jnp.minimum(count, m)

    def bwd(i, carry):
        q, alpha = carry
        slot = jnp.mod(count - 1 - i, m)
        valid = i < valid_n
        a = jnp.where(valid, rho[slot] * jnp.dot(S[slot], q), 0.0)
        q = q - a * Y[slot]
        return q, alpha.at[slot].set(a)

    q, alpha = lax.fori_loop(0, m, bwd, (pg, jnp.zeros((m,), pg.dtype)))

    newest = jnp.mod(count - 1, m)
    yy = jnp.dot(Y[newest], Y[newest])
    gamma = jnp.where(count > 0, jnp.dot(S[newest], Y[newest]) / jnp.maximum(yy, 1e-30), 1.0)
    r = gamma * q

    def fwd(i, r):
        slot = jnp.mod(count - valid_n + i, m)
        valid = i < valid_n
        beta = rho[slot] * jnp.dot(Y[slot], r)
        r = r + jnp.where(valid, alpha[slot] - beta, 0.0) * S[slot]
        return r

    return lax.fori_loop(0, m, fwd, r)


def _lbfgs_funcs(objective: Any, config: OptimizerConfig, l1w: Array | None):
    """The shared L-BFGS / OWL-QN loop, split into ``(init, cond, body)``
    closures. ``l1w`` is None (static) for plain L-BFGS, else the
    per-coordinate L1 weight vector (λ₁ · reg_mask).

    ``_lbfgs_impl`` composes them into the classic single
    ``lax.while_loop`` program; the chunked entry points below run the
    SAME cond/body bounded to ``it < it_bound`` so a caller can snapshot
    per-lane convergence between chunks (convergence-aware lane
    compaction, ``game/random_effect``). Because ``body`` is applied to a
    lane's state in the same order either way (a vmapped while_loop
    freezes done lanes via select), chunked and single-launch runs are
    bitwise identical per lane."""
    m = config.history_length
    T = config.max_iterations
    use_l1 = l1w is not None
    fused_eval = bool(
        getattr(objective, "one_pass_value_grad",
                getattr(objective, "fused", False))
    )

    def full_value(w: Array) -> Array:
        v = objective.value(w)
        if use_l1:
            v = v + jnp.sum(l1w * jnp.abs(w))
        return v

    def value_and_grads(w: Array):
        f, g = objective.value_and_grad(w)
        if use_l1:
            f = f + jnp.sum(l1w * jnp.abs(w))
            pg = _pseudo_gradient(w, g, l1w)
        else:
            pg = g
        return f, g, pg

    def init(w0: Array) -> _LbfgsState:
        d = w0.shape[0]
        dtype = w0.dtype
        f0, g0, pg0 = value_and_grads(w0)
        g0_norm = jnp.linalg.norm(pg0)

        loss_hist = jnp.full((T + 1,), jnp.nan, dtype)
        gnorm_hist = jnp.full((T + 1,), jnp.nan, dtype)
        loss_hist = loss_hist.at[0].set(f0)
        gnorm_hist = gnorm_hist.at[0].set(g0_norm)

        return _LbfgsState(
            w=w0,
            f=f0,
            g=g0,
            pg=pg0,
            S=jnp.zeros((m, d), dtype),
            Y=jnp.zeros((m, d), dtype),
            rho=jnp.zeros((m,), dtype),
            count=jnp.int32(0),
            it=jnp.int32(0),
            evals=jnp.int32(1),  # the initial value_and_grads
            reason=jnp.int32(ConvergenceReason.MAX_ITERATIONS),
            done=grad_converged(g0_norm, g0_norm, config.tolerance),
            g0_norm=g0_norm,
            loss_hist=loss_hist,
            gnorm_hist=gnorm_hist,
        )

    def cond(st: _LbfgsState):
        return jnp.logical_and(st.it < T, jnp.logical_not(st.done))

    def body(st: _LbfgsState) -> _LbfgsState:
        with stage(LBFGS_TWO_LOOP):
            p = -_two_loop(st.pg, st.S, st.Y, st.rho, st.count, m)
            if use_l1:
                # constrain the search direction to the descent orthant
                p = jnp.where(p * (-st.pg) > 0.0, p, 0.0)
            # fall back to steepest descent if the direction isn't a descent dir
            descent = jnp.dot(p, st.pg) < 0.0
            p = jnp.where(descent, p, -st.pg)

        with stage(LBFGS_LINE_SEARCH):
            if use_l1:
                xi = jnp.where(st.w != 0.0, jnp.sign(st.w), jnp.sign(-st.pg))

                def trial_point(t):
                    x = st.w + t * p
                    return jnp.where(jnp.sign(x) == xi, x, 0.0)

            else:

                def trial_point(t):
                    return st.w + t * p

            # First iteration: the Hessian guess is the identity, so scale the
            # initial step to unit length (Breeze does the same for iter 0).
            p_norm = jnp.linalg.norm(p)
            t0 = jnp.where(st.count == 0, 1.0 / jnp.maximum(1.0, p_norm), 1.0)

            def armijo_rhs(w_new):
                # Armijo on the (possibly projected) actual step
                return st.f + _ARMIJO_C1 * jnp.dot(st.pg, w_new - st.w)

            def hopeless(w_new):
                # Achievable decrease (~|pgᵀΔw|, the first-order model of the
                # step — NOT the c1-scaled Armijo threshold) below the f32
                # resolution of f: further halvings only shrink it, so no
                # representable improvement is possible; stop backtracking
                # instead of spinning max_line_search_steps objective passes
                # on the terminal iteration.
                return jnp.abs(jnp.dot(st.pg, w_new - st.w)) < _F_RESOLUTION * jnp.abs(st.f)

            def ls_should_continue(f_new, w_new, k):
                insufficient = jnp.logical_or(f_new > armijo_rhs(w_new), jnp.isnan(f_new))
                keep_going = jnp.logical_and(
                    insufficient, jnp.logical_not(hopeless(w_new))
                )
                return jnp.logical_and(keep_going, k < config.max_line_search_steps)

            slope0 = jnp.dot(st.pg, p)  # directional derivative at t = 0

            def next_t(t, f_t):
                # Safeguarded quadratic interpolation through f(0), f'(0), f(t):
                # the minimizer of the fitted parabola, clamped to [t/10, t/2].
                # An overshot step lands near the right t in one refit instead
                # of O(log) plain halvings (Breeze's line search interpolates
                # the same way) — this keeps the terminal iteration cheap.
                denom = 2.0 * (f_t - st.f - slope0 * t)
                t_q = -slope0 * t * t / jnp.where(denom != 0.0, denom, 1.0)
                t_q = jnp.where(
                    jnp.logical_and(jnp.isfinite(t_q), denom > 0.0), t_q, 0.5 * t
                )
                return jnp.clip(t_q, 0.1 * t, 0.5 * t)

            w_try = trial_point(t0)
            if fused_eval:
                # One-pass objective (ops/fused.py): value_and_grad costs the
                # same single X read as value alone, so each trial evaluates
                # both and an accepted step needs NO extra gradient pass —
                # the typical iteration touches X exactly once.
                def ls_cond(carry):
                    t, f_new, _, _, w_new, k = carry
                    return ls_should_continue(f_new, w_new, k)

                def ls_body(carry):
                    t, f_prev, _, _, _, k = carry
                    t_new = next_t(t, f_prev)
                    w_new = trial_point(t_new)
                    f, g, pg = value_and_grads(w_new)
                    return t_new, f, g, pg, w_new, k + 1

                f1, g1, pg1 = value_and_grads(w_try)
                t, f2, g2, pg2, w_new, ls_k = lax.while_loop(
                    ls_cond, ls_body, (t0, f1, g1, pg1, w_try, jnp.int32(0))
                )
                new_evals = st.evals + 1 + ls_k
            else:

                def ls_cond(carry):
                    t, f_new, w_new, k = carry
                    return ls_should_continue(f_new, w_new, k)

                def ls_body(carry):
                    t, f_prev, _, k = carry
                    t_new = next_t(t, f_prev)
                    w_new = trial_point(t_new)
                    return t_new, full_value(w_new), w_new, k + 1

                t, f_new, w_new, ls_k = lax.while_loop(
                    ls_cond, ls_body, (t0, full_value(w_try), w_try, jnp.int32(0))
                )
                f2, g2, pg2 = value_and_grads(w_new)
                new_evals = st.evals + 2 + ls_k

        with stage(LBFGS_UPDATE):
            rhs = armijo_rhs(w_new)
            # Armijo acceptance, EXCEPT the degenerate terminal case: a
            # fully-backtracked below-f32-resolution step (hopeless) that does
            # not decrease f satisfies "f_new <= rhs" with f_new == f, and
            # accepting it spins the solver at max_line_search_steps evals per
            # iteration with zero progress — that state means converged within
            # arithmetic precision: stop (reported as LINE_SEARCH_FAILED, the
            # same terminal state Breeze's FirstOrderMinimizer reaches).
            # Substantive steps with f_new == f are still accepted: near the
            # optimum of a large-n sum objective, f sits on an f32 plateau
            # while real steps keep improving w and the gradient norm.
            blind = hopeless(w_new)
            degenerate = jnp.logical_and(blind, f2 >= st.f)
            ls_ok = jnp.logical_and(f2 <= rhs, jnp.logical_not(degenerate))
            g2_norm = jnp.linalg.norm(pg2)
            # Where f cannot see the step, the gradient can: a step that
            # leaves f where it was, to the same resolution, and HALVES the
            # gradient is progress, and is taken. The halving bounds the
            # spinning that the degenerate case guards against. A solve in
            # which f sees every step never comes here.
            ls_ok = jnp.logical_or(ls_ok, _blind_progress(
                blind, f2, st.f, g2_norm, jnp.linalg.norm(st.pg)
            ))
            ls_ok = jnp.logical_and(ls_ok, jnp.logical_not(jnp.isnan(f2)))
            s = w_new - st.w
            y = g2 - st.g
            sy = jnp.dot(s, y)
            store = jnp.logical_and(ls_ok, sy > _CURVATURE_EPS)
            slot = jnp.mod(st.count, m)
            S = jnp.where(store, st.S.at[slot].set(s), st.S)
            Y = jnp.where(store, st.Y.at[slot].set(y), st.Y)
            rho = jnp.where(store, st.rho.at[slot].set(1.0 / jnp.maximum(sy, _CURVATURE_EPS)), st.rho)
            count = jnp.where(store, st.count + 1, st.count)

            converged = grad_converged(g2_norm, st.g0_norm, config.tolerance)

            # On line-search failure keep the old iterate and stop.
            w_out = jnp.where(ls_ok, w_new, st.w)
            f_out = jnp.where(ls_ok, f2, st.f)
            g_out = jnp.where(ls_ok, g2, st.g)
            pg_out = jnp.where(ls_ok, pg2, st.pg)
            reason = jnp.where(
                jnp.logical_not(ls_ok),
                jnp.int32(ConvergenceReason.LINE_SEARCH_FAILED),
                jnp.where(
                    converged,
                    jnp.int32(ConvergenceReason.GRADIENT_CONVERGED),
                    jnp.int32(ConvergenceReason.MAX_ITERATIONS),
                ),
            )
            done = jnp.logical_or(jnp.logical_not(ls_ok), converged)

            it = st.it + 1
            loss_hist = st.loss_hist.at[it].set(f_out)
            gnorm_hist = st.gnorm_hist.at[it].set(jnp.linalg.norm(pg_out))

        return _LbfgsState(
            w=w_out,
            f=f_out,
            g=g_out,
            pg=pg_out,
            S=S,
            Y=Y,
            rho=rho,
            count=count,
            it=it,
            evals=new_evals,
            reason=reason,
            done=done,
            g0_norm=st.g0_norm,
            loss_hist=loss_hist,
            gnorm_hist=gnorm_hist,
        )

    return init, cond, body


def _lbfgs_result(final: _LbfgsState) -> OptimizationResult:
    # If we stopped because the initial point already satisfied the test:
    reason = jnp.where(
        jnp.logical_and(final.it == 0, final.done),
        jnp.int32(ConvergenceReason.GRADIENT_CONVERGED),
        final.reason,
    )
    return OptimizationResult(
        w=final.w,
        value=final.f,
        grad_norm=jnp.linalg.norm(final.pg),
        iterations=final.it,
        reason=reason,
        loss_history=final.loss_hist,
        grad_norm_history=final.gnorm_hist,
        objective_passes=final.evals,
    )


def _lbfgs_impl(
    objective: Any,
    w0: Array,
    config: OptimizerConfig,
    l1w: Array | None,
) -> OptimizationResult:
    init, cond, body = _lbfgs_funcs(objective, config, l1w)
    final = lax.while_loop(cond, body, init(w0))
    return _lbfgs_result(final)


# -- chunked-run entry points (convergence-aware lane compaction) -----------
# The solver state is a pytree of fixed-shape arrays, so a batched caller
# can gather/scatter still-active lanes between chunks. Contract shared
# with tron.py: the state exposes ``.it`` (int32 iteration counter,
# incremented once per body application) and ``.done`` (bool); running
# ``chunk_run`` to increasing absolute bounds until every lane is done,
# then ``chunk_finalize``, reproduces ``*_minimize`` bitwise.
#
# Each entry point is @jit LIKE the one-shot minimize functions — the
# nested-jit call boundary is load-bearing for the bitwise claim: XLA
# compiles a while body differently when the loop is inlined into a
# larger computation than when it sits behind its own pjit boundary
# (measured on CPU: OWL-QN diverged by 1 ulp/iteration when the chunk
# pieces were inlined), and ``_solve_bucket`` calls the minimize twins
# through exactly this kind of boundary.


@partial(jax.jit, static_argnames=("config",))
def lbfgs_chunk_init(objective: Any, w0: Array, config: OptimizerConfig) -> _LbfgsState:
    """Solver state at ``w0`` (costs the initial value_and_grad pass)."""
    init, _, _ = _lbfgs_funcs(objective, config, None)
    return init(w0)


@partial(jax.jit, static_argnames=("config",))
def lbfgs_chunk_run(
    objective: Any, state: _LbfgsState, config: OptimizerConfig, it_bound: Array
) -> _LbfgsState:
    """Advance the loop until converged or ``state.it >= it_bound``
    (absolute iteration count — chunked callers pass c, 2c, 3c, …)."""
    _, cond, body = _lbfgs_funcs(objective, config, None)
    bound = jnp.asarray(it_bound, jnp.int32)
    return lax.while_loop(
        lambda st: jnp.logical_and(cond(st), st.it < bound), body, state
    )


@jax.jit
def lbfgs_chunk_finalize(state: _LbfgsState) -> OptimizationResult:
    return _lbfgs_result(state)


def _owlqn_l1w(objective: Any, state_dtype, l1_weight) -> Array:
    return jnp.asarray(l1_weight, state_dtype) * objective.reg_mask


@partial(jax.jit, static_argnames=("config",))
def owlqn_chunk_init(
    objective: Any, w0: Array, config: OptimizerConfig, l1_weight
) -> _LbfgsState:
    init, _, _ = _lbfgs_funcs(
        objective, config, _owlqn_l1w(objective, w0.dtype, l1_weight)
    )
    return init(w0)


@partial(jax.jit, static_argnames=("config",))
def owlqn_chunk_run(
    objective: Any,
    state: _LbfgsState,
    config: OptimizerConfig,
    it_bound: Array,
    l1_weight,
) -> _LbfgsState:
    _, cond, body = _lbfgs_funcs(
        objective, config, _owlqn_l1w(objective, state.w.dtype, l1_weight)
    )
    bound = jnp.asarray(it_bound, jnp.int32)
    return lax.while_loop(
        lambda st: jnp.logical_and(cond(st), st.it < bound), body, state
    )


@jax.jit
def owlqn_chunk_finalize(state: _LbfgsState) -> OptimizationResult:
    return _lbfgs_result(state)


@partial(jax.jit, static_argnames=("config",))
def lbfgs_minimize(objective: Any, w0: Array, config: OptimizerConfig) -> OptimizationResult:
    """Minimize a smooth objective with L-BFGS.

    ``objective`` is any pytree exposing ``value(w)`` and
    ``value_and_grad(w)`` (e.g. ``GLMObjective``).
    """
    return _lbfgs_impl(objective, w0, config, None)


@partial(jax.jit, static_argnames=("config",))
def owlqn_minimize(
    objective: Any,
    w0: Array,
    config: OptimizerConfig,
    l1_weight: Array | float,
) -> OptimizationResult:
    """Minimize objective(w) + λ₁·Σ|wⱼ| (over the objective's regularized
    coordinates) with OWL-QN. Requires ``objective.reg_mask``."""
    l1w = jnp.asarray(l1_weight, w0.dtype) * objective.reg_mask
    return _lbfgs_impl(objective, w0, config, l1w)
