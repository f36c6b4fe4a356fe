"""Plain reference for the GLMix score and its log-loss.

Independent of ``photon_ml_tpu``. A GLMix (GAME) model scores a row as the
sum of its coordinates' contributions:

    s_i = x_i^fixed . w  +  sum_c  x_i^c . W_c[e_c(i)]

with ``W_c`` the (entities, d_c) coefficient matrix of random effect ``c``
and ``e_c(i)`` the row's entity. Float32 ``jax.numpy`` at ``highest``
matmul precision, on whatever device holds the features.
"""

from __future__ import annotations


def score(fixed, random_effects):
    """``fixed`` is ``(X, w)`` or None; ``random_effects`` is a list of
    ``(X_c, entity_ids_c, W_c)``. Returns the (n,) float32 scores."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    total = None
    if fixed is not None:
        X, w = fixed
        total = jnp.dot(X.astype(jnp.float32), jnp.asarray(w, jnp.float32),
                        precision=hi)
    for X_c, ids, W in random_effects:
        rows = jnp.asarray(W, jnp.float32)[ids]
        part = jnp.sum(X_c.astype(jnp.float32) * rows, axis=1)
        total = part if total is None else total + part
    return total


def log_loss(scores, labels) -> float:
    """Mean logistic loss of raw scores against 0/1 labels."""
    import jax
    import jax.numpy as jnp

    y = jnp.asarray(labels, jnp.float32)
    return float(jnp.mean(jax.nn.softplus(-(2.0 * y - 1.0) * scores)))
