"""The plain GLMix reference of ``glmix.py``, in blocks of rows.

Independent of ``photon_ml_tpu``. At MovieLens-20M's whole size the
feature matrices lie row-sharded over four chips and no one device holds
``W[ids]`` for every row, so the scorer walks the rows a block at a time,
each block on the device that holds it: ``glmix.score`` itself, float32 at
``highest`` matmul precision, no kernels, on ``X[lo:hi]``. The scores come
back as one host array in row order; the log-loss is taken there in float64.
"""

from __future__ import annotations

import contextlib

import numpy as np

from benchmark.reference import glmix


def _blocks(X, block_rows: int):
    """``(lo, hi, device block)`` over the rows of ``X``, shard by shard
    where ``X`` is a sharded ``jax.Array``, in row order."""
    shards = getattr(X, "addressable_shards", None)
    parts = (
        [(0, X)] if not shards
        else sorted((s.index[0].start or 0, s.data) for s in shards)
    )
    for start, data in parts:
        for lo in range(0, data.shape[0], block_rows):
            hi = min(lo + block_rows, data.shape[0])
            yield start + lo, start + hi, data[lo:hi]


def score(fixed, random_effects, block_rows: int = 1 << 20, operand=None):
    """``glmix.score`` of the same arguments, as an (n,) float32 host array.
    Matrices may be sharded over devices by rows; entity ids and coefficients
    are host arrays. ``operand``, a dtype, rounds every matrix and
    coefficient to it first (and back to float32): what the scorer would
    read one precision down."""
    import jax
    import jax.numpy as jnp

    def rounded(a):
        a = jnp.asarray(a, jnp.float32)
        return a if operand is None else a.astype(operand).astype(jnp.float32)

    first = fixed[0] if fixed is not None else random_effects[0][0]
    out = np.zeros((first.shape[0],), np.float32)
    terms = ([("fixed", fixed[0], None, fixed[1])] if fixed is not None else []) + [
        ("re", X, np.asarray(ids), W) for X, ids, W in random_effects
    ]
    for kind, X, ids, coef in terms:
        for lo, hi, block in _blocks(X, block_rows):
            # beside the block, where a device holds it
            devices = getattr(block, "devices", None)
            with (
                jax.default_device(next(iter(devices()))) if devices
                else contextlib.nullcontext()
            ):
                c = rounded(np.asarray(coef, np.float32))
                if kind == "fixed":
                    part = glmix.score((rounded(block), c), [])
                else:
                    part = glmix.score(None, [(rounded(block), jnp.asarray(ids[lo:hi]), c)])
            out[lo:hi] += np.asarray(part)
    return out


def log_loss(scores, labels) -> float:
    """Mean logistic loss of raw scores against 0/1 labels, over the rows
    given, in float64 on the host."""
    s = np.asarray(scores, np.float64)
    y = np.asarray(labels, np.float64)
    return float(np.mean(np.logaddexp(0.0, -(2.0 * y - 1.0) * s)))
