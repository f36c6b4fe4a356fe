"""Plain reference for an L2-regularised logistic regression fit by TRON.

Independent of ``photon_ml_tpu``: what the benchmark holds
``optim/tron.tron_minimize`` and ``GLMObjective.hvp`` to. No bias term
(LIBLINEAR's default ``-B -1``), labels y in {0, 1}:

    f(w)    = sum_i softplus(-(2 y_i - 1) x_i.w) + 0.5 * l2 * |w|^2
    g(w)    = X^T (sigmoid(X w) - y) + l2 * w
    H(w) v  = X^T (D (X v)) + l2 * v,   D = diag(sigmoid(m) (1 - sigmoid(m)))

With ``l2 = 1`` that is LIBLINEAR's ``-s 0`` objective at C = 1. The three
passes read a dense device-resident ``X`` in row blocks, float32
``jax.numpy`` traced under ``jax.default_matmul_precision("highest")`` (a
TPU's default float32 matmul is one bf16 pass), and add the blocks' partial sums
in float64 on the host, so a block of ``block_rows`` rows is all that ever
exists beside the resident matrix.

``tron`` is the trust-region Newton method of Lin, Weng and Keerthi (JMLR
2008) as LIBLINEAR's ``tron.cpp`` runs it, a plain Python loop over those
passes with its vector algebra in float64 numpy: the published constants
(eta0-2 = 1e-4, 0.25, 0.75; sigma1-3 = 0.25, 0.5, 4; CG stops at a residual
of 0.1 |g|), the boundary step of ``trcg``, the radius update, the two
stagnation guards. Departures from ``tron.cpp``, each because the program
under test makes it and the counts are compared:

- the trust radius is cut to the first step's length at the FIRST outer
  iteration only; ``tron.cpp`` does so while its ``iter`` is 1, that is
  until the first accepted step;
- outer iterations count rejected steps too, and are capped
  (``max_iterations``); CG steps are capped (``max_cg_iterations``; the
  ``tron.cpp`` of the paper has no cap);
- value and gradient come from one pass at every trial point
  (``tron.cpp`` evaluates the gradient after acceptance only): the same
  numbers, one pass instead of two;
- the stopping test is |g| <= tolerance * max(1, |g(0)|) (the program's
  Breeze-style floor; |g(0)| is far above 1 on any data set worth a fit);
- no bias term, labels 0/1 in place of -1/+1, float32 passes where
  LIBLINEAR computes in double.
"""

from __future__ import annotations

import functools

import numpy as np

ETA0, ETA1, ETA2 = 1e-4, 0.25, 0.75
SIGMA1, SIGMA2, SIGMA3 = 0.25, 0.5, 4.0
CG_XI = 0.1


def _blocks(n: int, block_rows: int):
    return [(lo, min(block_rows, n - lo)) for lo in range(0, n, block_rows)]


@functools.lru_cache(maxsize=None)
def _passes(rows: int, operands: str):
    """The per-block programs for a block of ``rows`` rows. ``operands``
    ``"bfloat16"`` rounds every matmul operand to bfloat16 first: what one
    bf16 MXU pass computes (float32 products and sums of rounded operands),
    the precision next below the float32 this reference states. It is
    spelled out because a TPU computes a float32 matrix-VECTOR product
    exactly whatever precision is asked for (XLA makes it a
    multiply-reduce, not an MXU pass; read on the chip, PR 34)."""
    import jax
    import jax.numpy as jnp

    def op(a):
        if operands == "bfloat16":
            return a.astype(jnp.bfloat16).astype(jnp.float32)
        return a

    def cut(X, y, lo):
        d = X.shape[1]
        xb = jax.lax.dynamic_slice(X, (lo, 0), (rows, d)).astype(jnp.float32)
        return op(xb), jax.lax.dynamic_slice(y, (lo,), (rows,))

    def value_grad(X, y, w, lo):
        with jax.default_matmul_precision("highest"):
            xb, yb = cut(X, y, lo)
            m = xb @ op(w)
            val = jnp.sum(jax.nn.softplus(-(2.0 * yb - 1.0) * m))
            return val, op(jax.nn.sigmoid(m) - yb) @ xb

    def hvp(X, y, w, v, lo):
        with jax.default_matmul_precision("highest"):
            xb, _ = cut(X, y, lo)
            p = jax.nn.sigmoid(xb @ op(w))
            return op(p * (1.0 - p) * (xb @ op(v))) @ xb

    return jax.jit(value_grad), jax.jit(hvp)


def value_grad(X, labels, w, l2: float, block_rows: int = 1 << 15,
               operands: str = "float32"):
    """(f(w), g(w)) as float64 numpy. ``operands="bfloat16"`` is one
    precision down (``_passes``): what the check's limits must tell from
    this."""
    import jax.numpy as jnp

    n, d = X.shape
    w64 = np.asarray(w, np.float64)
    w32 = jnp.asarray(w64, jnp.float32)
    y = jnp.asarray(labels, jnp.float32)
    value, grad = 0.0, np.zeros(d, np.float64)
    for lo, rows in _blocks(n, min(block_rows, n)):
        val, g = _passes(rows, operands)[0](X, y, w32, lo)
        value += float(val)
        grad += np.asarray(g, np.float64)
    return value + 0.5 * l2 * float(w64 @ w64), grad + l2 * w64


def hvp(X, labels, w, v, l2: float, block_rows: int = 1 << 15,
        operands: str = "float32"):
    """H(w) v as float64 numpy."""
    import jax.numpy as jnp

    n, d = X.shape
    v64 = np.asarray(v, np.float64)
    w32 = jnp.asarray(np.asarray(w, np.float64), jnp.float32)
    v32 = jnp.asarray(v64, jnp.float32)
    y = jnp.asarray(labels, jnp.float32)
    out = np.zeros(d, np.float64)
    for lo, rows in _blocks(n, min(block_rows, n)):
        out += np.asarray(
            _passes(rows, operands)[1](X, y, w32, v32, lo), np.float64
        )
    return out + l2 * v64


def _trcg(hv, g, delta: float, max_cg: int):
    """``tron.cpp``'s ``trcg``: CG on H s = -g inside |s| <= delta. Returns
    (s, r, steps) with r the residual -g - H s."""
    s = np.zeros_like(g)
    r = -g
    d = r.copy()
    cg_tol = CG_XI * np.linalg.norm(g)
    rtr = float(r @ r)
    steps = 0
    while steps < max_cg and np.sqrt(rtr) > cg_tol:
        steps += 1
        hd = hv(d)
        alpha = rtr / float(d @ hd)
        if np.linalg.norm(s + alpha * d) > delta:
            std, sts, dtd = float(s @ d), float(s @ s), float(d @ d)
            dsq = delta * delta
            rad = np.sqrt(std * std + dtd * (dsq - sts))
            alpha = (dsq - sts) / (std + rad) if std >= 0 else (rad - std) / dtd
            s = s + alpha * d
            r = r - alpha * hd
            break
        s = s + alpha * d
        r = r - alpha * hd
        rtr_new = float(r @ r)
        d = r + (rtr_new / rtr) * d
        rtr = rtr_new
    return s, r, steps


def tron(X, labels, l2: float, tolerance: float, max_iterations: int,
         max_cg_iterations: int, block_rows: int = 1 << 15) -> dict:
    """Fit from w = 0 until the gradient norm is ``tolerance`` of its value
    at 0, or ``max_iterations`` outer iterations. Returns ``w``, ``value``,
    ``grad_norm``, ``grad_norm_0``, ``iterations``, ``cg_steps`` and
    ``passes`` (1 + iterations + cg_steps: what the program counts)."""
    d = X.shape[1]
    fg = lambda w: value_grad(X, labels, w, l2, block_rows)
    w = np.zeros(d, np.float64)
    f, g = fg(w)
    gnorm0 = gnorm = float(np.linalg.norm(g))
    delta = gnorm0
    iterations = cg_steps = 0
    while iterations < max_iterations and gnorm > tolerance * max(1.0, gnorm0):
        s, r, steps = _trcg(
            lambda v: hvp(X, labels, w, v, l2, block_rows), g, delta,
            max_cg_iterations,
        )
        cg_steps += steps
        gs = float(g @ s)
        prered = -0.5 * (gs - float(s @ r))
        f_new, g_new = fg(w + s)
        actred = f - f_new
        snorm = float(np.linalg.norm(s))
        if iterations == 0:
            delta = min(delta, snorm)
        denom = f_new - f - gs
        alpha = SIGMA3 if denom <= 0 else max(SIGMA1, -0.5 * gs / denom)
        if actred < ETA0 * prered:
            delta = min(max(alpha, SIGMA1) * snorm, SIGMA2 * delta)
        elif actred < ETA1 * prered:
            delta = max(SIGMA1 * delta, min(alpha * snorm, SIGMA2 * delta))
        elif actred < ETA2 * prered:
            delta = max(SIGMA1 * delta, min(alpha * snorm, SIGMA3 * delta))
        else:
            delta = max(delta, min(alpha * snorm, SIGMA3 * delta))
        iterations += 1
        if actred > ETA0 * prered:
            w, f, g = w + s, f_new, g_new
            gnorm = float(np.linalg.norm(g))
        if f < -1e32 or (abs(actred) <= 0 and prered <= 0) or (
                abs(actred) <= 1e-12 * abs(f) and abs(prered) <= 1e-12 * abs(f)):
            break
    return {
        "w": w, "value": f, "grad_norm": gnorm, "grad_norm_0": gnorm0,
        "iterations": iterations, "cg_steps": cg_steps,
        "passes": 1 + iterations + cg_steps,
    }
