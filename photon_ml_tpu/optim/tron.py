"""TRON: trust-region truncated-Newton, as one compiled device program.

Reference parity: ``photon-lib::ml.optimization.TRON`` — LinkedIn's port of
the LIBLINEAR trust-region Newton method (SURVEY.md §2.1): an outer
trust-radius loop around an inner conjugate-gradient solve of
``H·s = -g`` truncated at the trust boundary, with the classic
η/σ radius-update constants.

TPU-first: in the reference every CG step is a cluster round-trip
(``HessianVectorAggregator`` over treeAggregate); here a CG step is one
fused Hv kernel (two matmuls + one psum when sharded) inside a
``lax.while_loop`` — the entire solve compiles to a single XLA program.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.config import OptimizerConfig
from photon_ml_tpu.obs.stages import TRON_CG, TRON_UPDATE, stage
from photon_ml_tpu.optim.common import (
    ConvergenceReason,
    OptimizationResult,
    grad_converged,
)

Array = jnp.ndarray

# LIBLINEAR tron.cpp constants
_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0
_CG_XI = 0.1  # inner CG relative residual tolerance


class _CgState(NamedTuple):
    s: Array
    r: Array
    d: Array
    rtr: Array
    k: Array
    stop: Array  # bool: boundary hit or converged


def _trcg(hvp, g: Array, delta: Array, max_cg: int) -> tuple[Array, Array, Array]:
    """Truncated CG for H·s = -g within ‖s‖ ≤ delta.

    Returns (s, r, cg_iters) with r the final residual -g - H·s.
    """
    r0 = -g
    cg_tol = _CG_XI * jnp.linalg.norm(g)

    def cond(st: _CgState):
        return jnp.logical_and(
            st.k < max_cg,
            jnp.logical_and(jnp.logical_not(st.stop), jnp.sqrt(st.rtr) > cg_tol),
        )

    def body(st: _CgState) -> _CgState:
        hd = hvp(st.d)
        dhd = jnp.dot(st.d, hd)
        alpha = st.rtr / jnp.maximum(dhd, 1e-30)
        s1 = st.s + alpha * st.d
        outside = jnp.linalg.norm(s1) > delta

        # boundary intersection: τ ≥ 0 with ‖s + τ·d‖ = delta
        std = jnp.dot(st.s, st.d)
        dd = jnp.dot(st.d, st.d)
        ss = jnp.dot(st.s, st.s)
        rad = jnp.sqrt(jnp.maximum(std * std + dd * (delta * delta - ss), 0.0))
        tau = jnp.where(
            std >= 0.0,
            (delta * delta - ss) / jnp.maximum(std + rad, 1e-30),
            (rad - std) / jnp.maximum(dd, 1e-30),
        )

        step = jnp.where(outside, tau, alpha)
        s_new = st.s + step * st.d
        r_new = st.r - step * hd
        rtr_new = jnp.dot(r_new, r_new)
        beta = rtr_new / jnp.maximum(st.rtr, 1e-30)
        d_new = r_new + beta * st.d
        return _CgState(
            s=s_new,
            r=r_new,
            d=jnp.where(outside, st.d, d_new),
            rtr=rtr_new,
            k=st.k + 1,
            stop=outside,
        )

    init = _CgState(
        s=jnp.zeros_like(g), r=r0, d=r0, rtr=jnp.dot(r0, r0), k=jnp.int32(0),
        stop=jnp.array(False),
    )
    fin = lax.while_loop(cond, body, init)
    return fin.s, fin.r, fin.k


class _TronState(NamedTuple):
    w: Array
    f: Array
    g: Array
    delta: Array
    it: Array
    passes: Array  # cumulative full-data passes: value_and_grad + CG Hv
    reason: Array
    done: Array
    g0_norm: Array
    loss_hist: Array
    gnorm_hist: Array


def _tron_funcs(objective: Any, config: OptimizerConfig):
    """The TRON loop split into ``(init, cond, body)`` closures — same
    structure as ``lbfgs._lbfgs_funcs`` and the same chunked-run contract
    (state exposes ``.it``/``.done``; body order per lane is unchanged by
    chunking, so chunked and single-launch runs are bitwise identical)."""
    T = config.max_iterations

    def init(w0: Array) -> _TronState:
        with stage(TRON_UPDATE):
            return _init(w0)

    def _init(w0: Array) -> _TronState:
        dtype = w0.dtype
        f0, g0 = objective.value_and_grad(w0)
        g0_norm = jnp.linalg.norm(g0)

        loss_hist = jnp.full((T + 1,), jnp.nan, dtype).at[0].set(f0)
        gnorm_hist = jnp.full((T + 1,), jnp.nan, dtype).at[0].set(g0_norm)

        return _TronState(
            w=w0,
            f=f0,
            g=g0,
            delta=g0_norm,
            it=jnp.int32(0),
            passes=jnp.int32(1),  # the initial value_and_grad
            reason=jnp.int32(ConvergenceReason.MAX_ITERATIONS),
            done=grad_converged(g0_norm, g0_norm, config.tolerance),
            g0_norm=g0_norm,
            loss_hist=loss_hist,
            gnorm_hist=gnorm_hist,
        )

    def cond(st: _TronState):
        return jnp.logical_and(st.it < T, jnp.logical_not(st.done))

    def body(st: _TronState) -> _TronState:
        # the objective names its own passes (``glm.objective``, and
        # ``glm.hvp`` inside it), so a reader of ``tron.*`` outside
        # ``glm.objective`` sees the optimizer's vector algebra alone
        with stage(TRON_CG):
            s, r, cg_k = _trcg(
                lambda v: objective.hvp(st.w, v), st.g, st.delta,
                config.max_cg_iterations,
            )
        with stage(TRON_UPDATE):
            return _update(st, s, r, cg_k)

    def _update(st: _TronState, s: Array, r: Array, cg_k: Array) -> _TronState:
        gs = jnp.dot(st.g, s)
        # r = -g - H·s ⇒ sᵀHs = -gs - s·r ⇒ predicted reduction:
        prered = -0.5 * (gs - jnp.dot(s, r))
        w_new = st.w + s
        # one fused pass: the value feeds the acceptance ratio, the gradient
        # is used iff the step is accepted (branch-free; a rejected step
        # wastes only the gradient half of the pass, and rejections are rare)
        f_new, g_new = objective.value_and_grad(w_new)
        actred = st.f - f_new
        snorm = jnp.linalg.norm(s)

        # first-iteration radius calibration (LIBLINEAR)
        delta = jnp.where(st.it == 0, jnp.minimum(st.delta, snorm), st.delta)

        # interpolated step scale
        denom = f_new - st.f - gs
        alpha = jnp.where(denom <= 0.0, _SIGMA3, jnp.maximum(_SIGMA1, -0.5 * gs / denom))

        delta = jnp.where(
            actred < _ETA0 * prered,
            jnp.minimum(jnp.maximum(alpha, _SIGMA1) * snorm, _SIGMA2 * delta),
            jnp.where(
                actred < _ETA1 * prered,
                jnp.maximum(_SIGMA1 * delta, jnp.minimum(alpha * snorm, _SIGMA2 * delta)),
                jnp.where(
                    actred < _ETA2 * prered,
                    jnp.maximum(_SIGMA1 * delta, jnp.minimum(alpha * snorm, _SIGMA3 * delta)),
                    jnp.maximum(delta, jnp.minimum(alpha * snorm, _SIGMA3 * delta)),
                ),
            ),
        )

        accept = actred > _ETA0 * prered
        w_out = jnp.where(accept, w_new, st.w)
        f_out = jnp.where(accept, f_new, st.f)
        g_out = jnp.where(accept, g_new, st.g)

        g_norm = jnp.linalg.norm(g_out)
        converged = jnp.logical_and(accept, grad_converged(g_norm, st.g0_norm, config.tolerance))

        # stagnation guards (LIBLINEAR): no progress possible
        tiny = 1e-12 * jnp.abs(st.f)
        stalled = jnp.logical_or(
            jnp.logical_and(jnp.abs(actred) <= 0.0, prered <= 0.0),
            jnp.logical_and(jnp.abs(actred) <= tiny, jnp.abs(prered) <= tiny),
        )
        unbounded = f_out < -1e32

        reason = jnp.where(
            converged,
            jnp.int32(ConvergenceReason.GRADIENT_CONVERGED),
            jnp.where(
                jnp.logical_or(stalled, unbounded),
                jnp.int32(ConvergenceReason.OBJECTIVE_CONVERGED),
                jnp.int32(ConvergenceReason.MAX_ITERATIONS),
            ),
        )
        done = jnp.logical_or(converged, jnp.logical_or(stalled, unbounded))

        it = st.it + 1
        return _TronState(
            w=w_out,
            f=f_out,
            g=g_out,
            delta=delta,
            it=it,
            # full-data passes: each CG step is one Hv pass and the
            # acceptance value_and_grad one more, so a fit of ``it``
            # outer iterations took ``passes - it - 1`` CG steps. It is
            # ``OptimizationResult.objective_passes``, which the benchmark
            # reads as ``optim.passes_per_fit`` and, by that identity,
            # ``optim.cg_steps_per_fit`` (benchmark/runners/fit_tron.py)
            passes=st.passes + cg_k + jnp.int32(1),
            reason=reason,
            done=done,
            g0_norm=st.g0_norm,
            loss_hist=st.loss_hist.at[it].set(f_out),
            gnorm_hist=st.gnorm_hist.at[it].set(g_norm),
        )

    return init, cond, body


def _tron_result(final: _TronState) -> OptimizationResult:
    reason = jnp.where(
        jnp.logical_and(final.it == 0, final.done),
        jnp.int32(ConvergenceReason.GRADIENT_CONVERGED),
        final.reason,
    )
    return OptimizationResult(
        w=final.w,
        value=final.f,
        grad_norm=jnp.linalg.norm(final.g),
        iterations=final.it,
        reason=reason,
        loss_history=final.loss_hist,
        grad_norm_history=final.gnorm_hist,
        objective_passes=final.passes,
    )


@partial(jax.jit, static_argnames=("config",))
def tron_minimize(objective: Any, w0: Array, config: OptimizerConfig) -> OptimizationResult:
    """Minimize a twice-differentiable objective with TRON.

    ``objective`` must expose ``value(w)``, ``value_and_grad(w)`` and
    ``hvp(w, v)`` (e.g. ``GLMObjective``).
    """
    init, cond, body = _tron_funcs(objective, config)
    final = lax.while_loop(cond, body, init(w0))
    return _tron_result(final)


# -- chunked-run entry points (see lbfgs.py for the shared contract; the
# @jit boundary on each piece is load-bearing for the bitwise claim) --------


@partial(jax.jit, static_argnames=("config",))
def tron_chunk_init(objective: Any, w0: Array, config: OptimizerConfig) -> _TronState:
    init, _, _ = _tron_funcs(objective, config)
    return init(w0)


@partial(jax.jit, static_argnames=("config",))
def tron_chunk_run(
    objective: Any, state: _TronState, config: OptimizerConfig, it_bound: Array
) -> _TronState:
    _, cond, body = _tron_funcs(objective, config)
    bound = jnp.asarray(it_bound, jnp.int32)
    return lax.while_loop(
        lambda st: jnp.logical_and(cond(st), st.it < bound), body, state
    )


@jax.jit
def tron_chunk_finalize(state: _TronState) -> OptimizationResult:
    return _tron_result(state)
