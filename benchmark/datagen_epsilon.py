"""Synthetic rows at the shape and preprocessing of LIBSVM's
``epsilon_normalized`` (``glm_dense_epsilon``): a dense float32 matrix whose
columns are standardised and whose rows are then scaled to unit length, two
about balanced classes.

The raw features are Gaussian with a low-rank common part, ``x = z + f B``
(``z`` white, ``f`` a row's ``factors`` common factors, ``B`` seeded loadings
whose strengths fall off geometrically), so that the columns are correlated
and the Hessian's spectrum is a flat bulk with ``factors`` eigenvalues above
it: a CG solve then takes a step an eigenvalue and a few for the bulk, not
two. Columns are standardised by their population moments (mean 0, variance
``1 + sum_k B_kj^2``: no pass over the data), and each row is scaled to unit
L2 norm. Labels are 0/1 draws from a logistic model on the stored rows whose
seeded coefficient vector is scaled to margins of standard deviation 2.

The matrix is filled block by block into one buffer on the device, so
neither a second copy nor a host copy ever exists. As in ``datagen.py`` the
PROBLEM is the configuration's (``data_seed``); no ``seed`` enters here: a
float32 fit that stops on a gradient test at 1e-4 of its start nears the
resolution of its summed loss at the end, where a relabelling (another
order of the columns, hence of every sum) can move a CG step or an
acceptance and with it the pass count, which would say nothing of the
program (``datagen.py``, finding 5 of PERF.md).
"""

from __future__ import annotations

import numpy as np

from benchmark.datagen import _key


def _problem(d: int, factors: int, strength: float, decay: float,
             margin_std: float, data_seed: int):
    """(loadings B (factors, d), 1 / column standard deviation (d,), the
    labels' coefficient vector (d,)) as float32 numpy."""
    host = np.random.default_rng(data_seed)
    scale = strength * decay ** np.arange(factors)
    B = scale[:, None] * host.standard_normal((factors, d))
    inv_sd = 1.0 / np.sqrt(1.0 + np.sum(B * B, axis=0))
    w = host.standard_normal(d)
    # var(x.w) of a unit row is about w' C w / d, C the columns' correlation
    u = inv_sd * w
    cw = inv_sd * (u + B.T @ (B @ u))
    w = w * margin_std / np.sqrt(float(w @ cw) / d)
    return B.astype(np.float32), inv_sd.astype(np.float32), w.astype(np.float32)


def epsilon_rows(n: int, d: int, block_rows: int, data_seed: int,
                 factors: int = 16, strength: float = 0.6, decay: float = 0.9,
                 margin_std: float = 2.0):
    """``(X, y)`` on the default device: ``X`` ``(n, d)`` float32 with unit
    rows, ``y`` ``(n,)`` float32 0/1."""
    import jax
    import jax.numpy as jnp

    if n % block_rows:
        raise ValueError(f"{n} rows do not divide into blocks of {block_rows}")
    B, inv_sd, w_true = _problem(d, factors, strength, decay, margin_std, data_seed)
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def make(key, B, inv_sd, w_true):
        def body(i, carry):
            X, y = carry
            kz, kf, ky = jax.random.split(jax.random.fold_in(key, i), 3)
            z = jax.random.normal(kz, (block_rows, d), jnp.float32)
            f = jax.random.normal(kf, (block_rows, factors), jnp.float32)
            xb = (z + jnp.dot(f, B, precision=hi)) * inv_sd
            xb = xb * jax.lax.rsqrt(jnp.sum(xb * xb, axis=1, keepdims=True))
            m = jnp.dot(xb, w_true, precision=hi)
            yb = jax.random.uniform(ky, (block_rows,)) < jax.nn.sigmoid(m)
            X = jax.lax.dynamic_update_slice(X, xb, (i * block_rows, 0))
            y = jax.lax.dynamic_update_slice(
                y, yb.astype(jnp.float32), (i * block_rows,)
            )
            return X, y

        return jax.lax.fori_loop(
            0, n // block_rows, body,
            (jnp.zeros((n, d), jnp.float32), jnp.zeros((n,), jnp.float32)),
        )

    return make(_key(data_seed, 6), B, inv_sd, w_true)
