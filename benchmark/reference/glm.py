"""Plain reference for the L2-regularised logistic GLM objective.

Independent of ``photon_ml_tpu``: what the benchmark holds a fit to.

    f(w) = sum_i softplus(-(2 y_i - 1) m_i) + 0.5 * l2 * sum_j mask_j w_j^2
    m    = X w + offsets                       (labels y in {0, 1})
    g(w) = X^T (sigmoid(m) - y) + l2 * mask * w

``mask`` is 0 on the intercept column (never regularised) and 1 elsewhere.

Two forms, same semantics:

- ``sparse_value_grad``: padded-sparse rows ``(n, k)`` of (index, value)
  on the HOST in float64 numpy: gather for the margins, ``bincount`` (a
  segment sum) for the gradient. Duplicate (row, column) pairs add, padding
  is (0, 0.0). A 10^8-nonzero evaluation takes a few seconds on one core,
  so it runs once per check, never per iteration.
- ``dense_value_grad``: a dense, possibly row-sharded ``X`` in row blocks
  with float32 ``jax.numpy`` at ``highest`` matmul precision (a TPU's
  default float32 matmul is one bf16 pass). ``X`` may be stored in
  bfloat16; it is widened exactly, and ``w`` is NOT rounded: a program
  that rounds its vector operand differs from this by that rounding, which
  the configuration's tolerance states.
"""

from __future__ import annotations

import numpy as np


def _softplus(z: np.ndarray) -> np.ndarray:
    return np.logaddexp(0.0, z)


def _sigmoid(z: np.ndarray) -> np.ndarray:
    return 0.5 * (1.0 + np.tanh(0.5 * z))


def l2_mask(d: int, intercept_index: int | None) -> np.ndarray:
    mask = np.ones(d, np.float64)
    if intercept_index is not None:
        mask[intercept_index] = 0.0
    return mask


def sparse_value_grad(indices, values, labels, w, l2, intercept_index=None,
                      block_rows: int = 1 << 18):
    """(f(w), g(w)) in float64 for padded-sparse rows held on the host."""
    indices = np.asarray(indices)
    values = np.asarray(values)
    y = np.asarray(labels, np.float64)
    w = np.asarray(w, np.float64)
    d = w.shape[0]
    value = 0.0
    grad = np.zeros(d, np.float64)
    for lo in range(0, indices.shape[0], block_rows):
        idx = indices[lo:lo + block_rows]
        val = values[lo:lo + block_rows].astype(np.float64)
        yb = y[lo:lo + block_rows]
        m = np.sum(val * w[idx], axis=1)
        value += float(np.sum(_softplus(-(2.0 * yb - 1.0) * m)))
        r = _sigmoid(m) - yb
        grad += np.bincount(
            idx.reshape(-1), weights=(val * r[:, None]).reshape(-1), minlength=d
        )
    mask = l2_mask(d, intercept_index)
    value += 0.5 * l2 * float(np.sum(mask * w * w))
    return value, grad + l2 * mask * w


def _dense_blocks(X, y, off, w, *, block_rows: int):
    """Per-block (value, X_b^T r_b) over the rows of this device."""
    import jax
    import jax.numpy as jnp

    d = X.shape[1]
    hi = jax.lax.Precision.HIGHEST

    def block(i):
        # sliced, not reshaped: a reshape of a tiled 8 GiB operand may be
        # a copy, a dynamic slice of a loop invariant never is
        lo = i * block_rows
        xb = jax.lax.dynamic_slice(X, (lo, 0), (block_rows, d))
        yb = jax.lax.dynamic_slice(y, (lo,), (block_rows,))
        ob = jax.lax.dynamic_slice(off, (lo,), (block_rows,))
        xb = xb.astype(jnp.float32)
        m = jnp.dot(xb, w, precision=hi) + ob
        val = jnp.sum(jax.nn.softplus(-(2.0 * yb - 1.0) * m))
        r = jax.nn.sigmoid(m) - yb
        return val, jnp.dot(r, xb, precision=hi)

    return jax.lax.map(block, jnp.arange(X.shape[0] // block_rows))


def dense_value_grad(X, labels, w, l2, intercept_index=None, offsets=None,
                     block_rows: int = 1 << 15):
    """(f(w), g(w)) for a dense device-resident ``X`` (any sharding over
    rows). Returns float64 numpy; the sums run in float32 on the device
    per row block and are added in float64 on the host."""
    import functools

    import jax
    import jax.numpy as jnp

    n, d = X.shape
    block_rows = min(block_rows, n)
    w32 = jnp.asarray(w, jnp.float32)
    y = jnp.asarray(labels, jnp.float32)
    off = jnp.zeros((n,), jnp.float32) if offsets is None else offsets
    local = functools.partial(_dense_blocks, block_rows=block_rows)

    # a row-sharded X stays where it is: each device maps over its own
    # blocks and the per-block partial sums come back stacked
    mesh = getattr(getattr(X, "sharding", None), "mesh", None)
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P

        axis = mesh.axis_names[0]
        if (n // mesh.size) % block_rows:
            raise ValueError(
                f"{n // mesh.size} rows a device do not divide into blocks "
                f"of {block_rows}"
            )
        local = jax.shard_map(
            local, mesh=mesh, in_specs=(P(axis), P(axis), P(axis), P()),
            out_specs=(P(axis), P(axis)),
        )
    vals, grads = jax.jit(local)(X, y, off, w32)
    value = float(np.sum(np.asarray(vals, np.float64)))
    grad = np.sum(np.asarray(grads, np.float64), axis=0)
    tail = n % block_rows  # one device only: the last, shorter block
    if tail:
        lo = n - tail
        vals, grads = jax.jit(
            functools.partial(_dense_blocks, block_rows=tail)
        )(X[lo:], y[lo:], off[lo:], w32)
        value += float(np.asarray(vals, np.float64)[0])
        grad += np.asarray(grads, np.float64)[0]
    w64 = np.asarray(w, np.float64)
    mask = l2_mask(d, intercept_index)
    return (
        value + 0.5 * l2 * float(np.sum(mask * w64 * w64)),
        grad + l2 * mask * w64,
    )
