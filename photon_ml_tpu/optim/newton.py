"""Damped Newton with exact Cholesky solves — the small-d batched solver.

Why this exists (TPU-first design, not reference parity): the reference
solves every per-entity random-effect GLM with L-BFGS — fine on a CPU
executor, but on an accelerator a vmapped L-BFGS ``while_loop`` runs
~20 iterations of many SMALL sequential kernels per bucket, and kernel
issue latency (not FLOPs) dominates wall-clock for d≈8 problems (bench
config E: the per-coordinate marginal was ~50 ms of almost no math).
For small d the exact Newton step is nearly free on the MXU: the (d, d)
Hessian is one batched contraction, the solve one batched Cholesky, and
convergence takes ~3-6 iterations instead of ~20 — a fraction of the
sequential kernels. Under ``vmap`` every lane shares the fixed-length
backtracking scan, so one bucket solve is a handful of large fused
kernels per iteration.

Semantics: minimizes the same smooth objective to the same optimum
(convex GLM + L2 ridge ⇒ the Hessian is PD; a Levenberg-style jitter
covers the unregularized corner), with the same convergence tests and
``OptimizationResult`` contract as L-BFGS. Requires ``objective.hessian``
(dense batches); L1 is not supported (use OWL-QN).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.config import OptimizerConfig
from photon_ml_tpu.obs.stages import NEWTON_SOLVE, stage
from photon_ml_tpu.optim.common import (
    ConvergenceReason,
    OptimizationResult,
    grad_converged,
)

Array = jnp.ndarray

_JITTER = 1e-8  # Levenberg floor: keeps the Cholesky PD without L2

# Below this width the unrolled pure-jnp solve replaces the XLA linalg
# custom-calls. Profiled on v5e (bench config E, (20000, 8, 8) lanes under
# vmap): cholesky + cho_solve lower to custom-calls costing 2.1-3.5 ms per
# Newton iteration — 71% of the whole fused GAME outer program — while the
# unrolled form is 3·d static steps of batched matvecs that fuse into the
# surrounding program.
_UNROLL_MAX_D = 32


def _solve_spd_small(H: Array, g: Array) -> Array:
    """Solve ``H p = g`` (H symmetric PD, small static d) without linalg
    custom-calls: unrolled Cholesky + forward/back substitution.

    Each of the 3·d steps is a (d,)-vector op; under the caller's ``vmap``
    they become (k, d) elementwise/matvec kernels over the entity lanes.
    A non-PD ``H`` produces NaNs (sqrt of a negative pivot) — callers keep
    their existing NaN fallback.

    No matrix is materialized: ``L`` lives as a Python list of column
    vectors and the substitutions as per-lane scalars, so there are NO
    dynamic-update-slices (an ``.at[:, j].set`` under vmap copies the whole
    (k, d, d) buffer — profiled at ~0.11 ms per slice, 24 slices per Newton
    iteration, which re-dominated the loop after the custom-calls left).
    Entries of column j above the diagonal carry garbage, but by induction
    they are only ever multiplied into other above-diagonal positions and
    never into an entry the substitutions read.
    """
    d = H.shape[-1]
    cols: list[Array] = []  # cols[j] ≡ L[:, j]; entries i < j are unused
    for j in range(d):
        s = H[:, j]
        for k in range(j):
            s = s - cols[k] * cols[k][j]
        cols.append(s * lax.rsqrt(s[j]))
    # forward substitution L y = g (per-lane scalars)
    y: list[Array] = []
    for i in range(d):
        yi = g[i]
        for k in range(i):
            yi = yi - cols[k][i] * y[k]
        y.append(yi / cols[i][i])
    # back substitution Lᵀ p = y: (Lᵀ p)_i = Σ_{k≥i} L[k, i]·p_k
    p: list[Array] = [None] * d
    for i in reversed(range(d)):
        pi = y[i]
        for k in range(i + 1, d):
            pi = pi - cols[i][k] * p[k]
        p[i] = pi / cols[i][i]
    return jnp.stack(p)


@partial(jax.jit, static_argnames=("config",))
def newton_minimize(
    objective: Any, w0: Array, config: OptimizerConfig
) -> OptimizationResult:
    """Minimize a smooth objective with damped (backtracking) Newton.

    ``objective`` must expose ``value_and_grad(w)`` and ``hessian(w)``
    (the GLM objective's dense-batch Hessian). Intended for small d —
    the Hessian is materialized (d, d) every iteration.
    """
    T = int(config.max_iterations)
    d = w0.shape[0]
    eye = jnp.eye(d, dtype=w0.dtype)
    # fixed-length backtracking: t in {1, 1/2, ..., 2^-(K-1)}; the first
    # Armijo-acceptable trial wins (select, not data-dependent loop — the
    # whole ladder evaluates as ONE batched objective sweep under vmap)
    K = max(int(config.max_line_search_steps), 1)
    ts = 0.5 ** jnp.arange(K, dtype=w0.dtype)

    # margin-state fast path (GLMObjective): margins are affine in w, so
    # the loop carries m = margins(w) and updates it as m + t·dm after the
    # line search — ONE matvec per iteration (the direction's) where the
    # generic path re-derives margins inside hessian, the ladder, and
    # value_and_grad. The carried margins drift by one fused multiply-add
    # of rounding per iteration (bounded by the iteration cap), the same
    # trade CG makes with its carried residual.
    margin_api = all(
        hasattr(objective, a)
        for a in (
            "margins", "direction_margins", "value_and_grad_from_margins",
            "hessian_from_margins", "ray_values_from_margins",
        )
    )

    if margin_api:
        m0 = objective.margins(w0)
        f0, g0 = objective.value_and_grad_from_margins(m0, w0)
    else:
        m0 = jnp.zeros((0,), w0.dtype)  # placeholder, untouched
        f0, g0 = objective.value_and_grad(w0)
    g0_norm = jnp.linalg.norm(g0)

    loss_hist = jnp.full((T + 1,), jnp.nan, w0.dtype).at[0].set(f0)
    gnorm_hist = jnp.full((T + 1,), jnp.nan, w0.dtype).at[0].set(g0_norm)

    init = dict(
        w=w0, f=f0, g=g0, m=m0, it=jnp.int32(0), evals=jnp.int32(1),
        reason=jnp.int32(ConvergenceReason.MAX_ITERATIONS),
        done=grad_converged(g0_norm, g0_norm, config.tolerance),
        loss_hist=loss_hist, gnorm_hist=gnorm_hist,
    )

    def cond(st):
        return jnp.logical_and(st["it"] < T, jnp.logical_not(st["done"]))

    def body(st):
        if margin_api:
            H = objective.hessian_from_margins(st["m"], st["w"])
        else:
            H = objective.hessian(st["w"])
        with stage(NEWTON_SOLVE):
            if d <= _UNROLL_MAX_D:
                p = -_solve_spd_small(H + _JITTER * eye, st["g"])
            else:
                L = jnp.linalg.cholesky(H + _JITTER * eye)
                p = -jax.scipy.linalg.cho_solve((L, True), st["g"])
            # a failed factorization (NaN) falls back to steepest descent
            bad = jnp.any(jnp.isnan(p))
            p = jnp.where(bad, -st["g"], p)
        gTp = jnp.dot(st["g"], p)
        # Newton decrement test: the quadratic model promises ~(-gTp)/2 of
        # decrease; below f32 resolution of f, further steps only walk the
        # rounding plateau (the L-BFGS degenerate-step stop's analog)
        plateau = -gTp <= 1e-7 * jnp.maximum(1.0, jnp.abs(st["f"]))

        if margin_api:
            dm = objective.direction_margins(p)
            fs = objective.ray_values_from_margins(st["m"], dm, st["w"], p, ts)
        else:
            # generic objectives really do evaluate K trial points (the
            # K+1 pass accounting below matches this branch exactly)
            fs = jax.vmap(lambda t: objective.value(st["w"] + t * p))(ts)
        armijo = fs <= st["f"] + 1e-4 * ts * gTp
        ok_any = jnp.any(armijo)
        k = jnp.argmax(armijo)  # first acceptable step
        t = ts[k]
        w_new = st["w"] + t * p
        if margin_api:
            m_new = st["m"] + t * dm
            f_new, g_new = objective.value_and_grad_from_margins(m_new, w_new)
            m_out = jnp.where(ok_any, m_new, st["m"])
        else:
            f_new, g_new = objective.value_and_grad(w_new)
            m_out = st["m"]

        w_out = jnp.where(ok_any, w_new, st["w"])
        f_out = jnp.where(ok_any, f_new, st["f"])
        g_out = jnp.where(ok_any, g_new, st["g"])
        g_norm = jnp.linalg.norm(g_out)
        converged = grad_converged(g_norm, g0_norm, config.tolerance)
        reason = jnp.where(
            jnp.logical_not(ok_any),
            jnp.int32(ConvergenceReason.LINE_SEARCH_FAILED),
            jnp.where(
                converged,
                jnp.int32(ConvergenceReason.GRADIENT_CONVERGED),
                jnp.where(
                    plateau,
                    jnp.int32(ConvergenceReason.OBJECTIVE_CONVERGED),
                    jnp.int32(ConvergenceReason.MAX_ITERATIONS),
                ),
            ),
        )
        it = st["it"] + 1
        # objective_passes counts FULL-DATA passes (the physical work
        # unit): on the margin path an iteration reads the data ~3× —
        # Hessian contraction, direction matvec, gradient contraction —
        # and the whole K-trial ladder is free (elementwise over stored
        # margins). The generic path really does evaluate K trials.
        passes_per_iter = jnp.int32(3 if margin_api else K + 1)
        return dict(
            w=w_out, f=f_out, g=g_out, m=m_out, it=it,
            evals=st["evals"] + passes_per_iter,
            reason=reason,
            done=jnp.logical_or(
                jnp.logical_or(jnp.logical_not(ok_any), converged), plateau
            ),
            loss_hist=st["loss_hist"].at[it].set(f_out),
            gnorm_hist=st["gnorm_hist"].at[it].set(g_norm),
        )

    final = lax.while_loop(cond, body, init)
    reason = jnp.where(
        jnp.logical_and(final["it"] == 0, final["done"]),
        jnp.int32(ConvergenceReason.GRADIENT_CONVERGED),
        final["reason"],
    )
    return OptimizationResult(
        w=final["w"],
        value=final["f"],
        grad_norm=jnp.linalg.norm(final["g"]),
        iterations=final["it"],
        reason=reason,
        loss_history=final["loss_hist"],
        grad_norm_history=final["gnorm_hist"],
        objective_passes=final["evals"],
    )
