"""Plain reference for one entity's random-effect solve.

Independent of ``photon_ml_tpu``. One entity of a GLMix random effect is a
small L2-regularised logistic regression on that entity's rows, with the
other coordinates' scores as fixed offsets:

    f(w) = sum_i softplus(-(2 y_i - 1) (x_i . w + o_i)) + 0.5 * l2 * |w|^2

It is strictly convex, so its minimiser is unique and any solver that
converges agrees with this one: damped Newton in float64 numpy, run to a
gradient far below the program's tolerance.
"""

from __future__ import annotations

import numpy as np


def entity_newton(X, y, offsets, l2: float, max_iter: int = 100,
                  grad_tol: float = 1e-12) -> np.ndarray:
    X = np.asarray(X, np.float64)
    y = np.asarray(y, np.float64)
    o = np.asarray(offsets, np.float64)
    d = X.shape[1]
    w = np.zeros(d)

    def value(w):
        m = X @ w + o
        return np.sum(np.logaddexp(0.0, -(2.0 * y - 1.0) * m)) + 0.5 * l2 * w @ w

    f = value(w)
    for _ in range(max_iter):
        p = 0.5 * (1.0 + np.tanh(0.5 * (X @ w + o)))
        g = X.T @ (p - y) + l2 * w
        if np.linalg.norm(g) <= grad_tol * max(1.0, abs(f)):
            break
        H = (X * (p * (1.0 - p))[:, None]).T @ X + l2 * np.eye(d)
        step = np.linalg.solve(H, g)
        t = 1.0
        while t > 1e-10:
            f_new = value(w - t * step)
            if f_new <= f - 1e-4 * t * (g @ step):
                break
            t *= 0.5
        w, f = w - t * step, f_new
    return w
