"""Synthetic rows at the shape of a hashed click-through data set.

The Criteo display-advertising rows as LIBSVM distributes them
(``glm_sparse_criteo``): every row has one nonzero a FIELD, the (field,
value) pair hashed to one of ``d`` columns, every value ``1 / sqrt(fields)``.
A value's popularity inside its field is a power law over ranks (the
field's ``cardinality`` of them), so a field of 3 values fills three
columns in a third of the rows each, and a field of 10^7 values spreads
over the whole width. The hash is a 32-bit mix of rank and field: nothing
here needs 64-bit arithmetic or a table, at any width (``datagen.py``'s
affine permutation stops at 65,536 columns).

As in ``datagen.py`` the PROBLEM is the configuration's (``data_seed``) and
``--seed`` relabels it (the order of a row's nonzeros): no seed moves a
shape or a pass count.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.datagen import _key


def _mix32(h):
    """A 32-bit finaliser (lowbias32): every input bit reaches every
    output bit, so ``mod d`` of it is uniform for any ``d``."""
    import jax.numpy as jnp

    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> jnp.uint32(15))
    h = h * jnp.uint32(0x846CA68B)
    return h ^ (h >> jnp.uint32(16))


def hashed_field_rows(seed: int, n: int, d: int, cardinalities, data_seed: int,
                      label_scale: float, label_shift: float):
    """Padded-sparse rows of one nonzero a field: ``(n, F)`` int32 column
    ids, ``(n, F)`` float32 values, ``(n,)`` float32 0/1 labels, for the
    ``F = len(cardinalities)`` fields.

    Field f's value is a rank drawn as ``floor((C_f + 1)^u) - 1`` (Zipf 1.0
    over its ``C_f`` ranks) and its column ``mix32(rank + f * golden) mod
    d``. Two fields of a row may hash to one column; such entries add, as
    in any padded-sparse row (the source's rows have the same collisions).
    Labels follow a logistic model whose coefficient for a column is a hash
    of the column, margins scaled by ``label_scale`` and shifted by
    ``label_shift``. ``seed`` rotates the order of every row's nonzeros."""
    import jax
    import jax.numpy as jnp

    fields = len(cardinalities)
    card = np.asarray(cardinalities, np.float64)
    if card.min() < 1 or card.max() >= 2**32:
        raise ValueError("a field holds 1 to 2^32 - 1 values")
    log_card = jnp.asarray(np.log(card + 1.0), jnp.float32)
    top = jnp.asarray(card - 1.0, jnp.uint32)
    salt = jnp.asarray(
        (np.arange(fields, dtype=np.uint64) * 0x9E3779B9) % 2**32, jnp.uint32
    )
    value = 1.0 / math.sqrt(fields)
    shift = jnp.int32(np.random.default_rng(seed).integers(0, fields))

    @jax.jit
    def make(key, shift):  # the seed's part is an argument: one program
        ku, ky = jax.random.split(key)
        u = jax.random.uniform(ku, (n, fields), jnp.float32)
        rank = (jnp.exp(u * log_card) - 1.0).astype(jnp.uint32)
        rank = jnp.minimum(rank, top)
        col = _mix32(rank + salt) % jnp.uint32(d)
        # coefficient of a column: its id hashed to (-1, 1)
        h = _mix32(col + jnp.uint32(0x68E31DA4)) >> jnp.uint32(8)
        w_true = h.astype(jnp.float32) * (2.0 / (1 << 24)) - 1.0
        margin = label_scale * value * jnp.sum(w_true, axis=1) + label_shift
        y = jax.random.uniform(ky, (n,), jnp.float32) < jax.nn.sigmoid(margin)
        col = jnp.roll(col.astype(jnp.int32), shift, axis=1)
        val = jnp.full((n, fields), value, jnp.float32)
        return col, val, y.astype(jnp.float32)

    return make(_key(data_seed, 1), shift)
