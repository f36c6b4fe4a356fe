"""Synthetic data at a configuration's shape.

There is no network and no dataset on disk, so every cell's data are drawn
here: on the device, in one jitted call, in the type they are used in.
Only what the program's host code needs crosses to the host (entity ids
for grouping, sparse rows for the layout build). The distributions are
stated in each configuration file under ``assumed``.

The PROBLEM is the configuration's (its ``data_seed``): the matrix, the
labels, the rows each entity has. ``--seed`` draws how that problem is
LABELLED: the order of a sparse row's nonzeros, which id each entity bears
(and, for a dense bfloat16 matrix, nothing: see ``dense_glm_rows``). The
same seed gives the same inputs and another seed gives other arrays, but no
seed changes

- a shape the program compiles for (tile-COO streams are as long as the
  pattern's cells are full, bucket tensors as large as their classes are
  populated), so only a checkout's first run compiles; nor
- the work of a unit: an iterative fit takes as many passes as its data
  ask for, and with the data drawn from the seed the same fit took 21
  passes under one seed and 22 under the next, a step of 4.7% in ``fit_s``
  that says nothing of the program (my chip runs, PR 22). A relabelled
  problem has the same optimum and, rounding aside, the same path to it.

Bits come from the ``rbg`` generator (the chip's own bit generator): the
default threefry costs tens of integer operations an element, which at
10^10 elements is most of a minute of set-up that serves no fit.
"""

from __future__ import annotations

import math

import numpy as np


def _key(seed: int, stream: int):
    import jax

    return jax.random.fold_in(jax.random.key(seed, impl="rbg"), stream)


def _coprime_multiplier(rng: np.random.Generator, d: int) -> int:
    while True:
        a = int(rng.integers(1, d))
        if math.gcd(a, d) == 1:
            return a


def sparse_glm_rows(seed: int, n: int, d: int, k: int, zipf_exponent: float,
                    data_seed: int):
    """Padded-sparse rows for a logistic GLM: ``(n, k)`` int32 column ids,
    ``(n, k)`` float32 values, ``(n,)`` float32 0/1 labels.

    Every row has ``k`` nonzeros. A nonzero's popularity rank is drawn from
    a continuous power law with ``zipf_exponent`` floored to an integer (for
    exponent 1: ``floor((d + 1)^u) - 1``, so rank r has probability
    ``log((r + 2) / (r + 1)) / log(d + 1)``), and the rank is sent through
    an affine permutation ``(a * rank + b) mod d`` of the column ids, so
    popular columns are spread over the whole feature space as in a hashed
    or alphabetically indexed vocabulary. A row may draw a column twice;
    such entries add, as in any padded-sparse row. Values are positive and
    each row has unit L2 norm (cosine-normalised TF-IDF). Labels follow a
    logistic model whose coefficient for a column is a hash of the column
    id, so no table is gathered anywhere. ``seed`` rotates the order of
    every row's nonzeros: the same matrix in other arrays."""
    import jax
    import jax.numpy as jnp

    if d >= 1 << 16:
        # (a * rank) must fit 32 bits without wrapping
        raise ValueError(f"affine column permutation needs d < 65536, got {d}")
    host = np.random.default_rng(data_seed)
    a = _coprime_multiplier(host, d)
    b = int(host.integers(0, d))
    shift = jnp.int32(np.random.default_rng(seed).integers(0, k))

    @jax.jit
    def make(key, shift):  # the seed's part is an argument: one program
        ku, kv, ky = jax.random.split(key, 3)
        u = jax.random.uniform(ku, (n, k), jnp.float32)
        if zipf_exponent == 1.0:
            rank = jnp.exp(u * math.log(d + 1.0)) - 1.0
        else:
            e = 1.0 - zipf_exponent
            rank = (u * ((d + 1.0) ** e - 1.0) + 1.0) ** (1.0 / e) - 1.0
        rank = jnp.clip(rank.astype(jnp.uint32), 0, d - 1)
        col = (rank * jnp.uint32(a) + jnp.uint32(b)) % jnp.uint32(d)
        val = jax.random.uniform(kv, (n, k), jnp.float32, 0.05, 1.0)
        val = val / jnp.sqrt(jnp.sum(val * val, axis=1, keepdims=True))
        # coefficient of a column: its id hashed to (-1, 1)
        h = ((col + jnp.uint32(1)) * jnp.uint32(2654435761)) >> jnp.uint32(8)
        w_true = h.astype(jnp.float32) * (2.0 / (1 << 24)) - 1.0
        margin = 4.0 * jnp.sum(val * w_true, axis=1)
        y = jax.random.uniform(ky, (n,), jnp.float32) < jax.nn.sigmoid(margin)
        col = jnp.roll(col.astype(jnp.int32), shift, axis=1)
        return col, jnp.roll(val, shift, axis=1), y.astype(jnp.float32)

    return make(_key(data_seed, 1), shift)


def dense_glm_rows(n: int, d: int, dtype, mesh, block_rows: int,
                   data_seed: int, scale_spread: float = 1.0):
    """A dense logistic problem row-sharded over ``mesh``'s one axis:
    ``X`` ``(n, d)`` in ``dtype`` with a last column of ones (the
    intercept) and ``(n,)`` float32 0/1 labels.

    Features are uniform, column j scaled by ``scale_spread ** u_j`` with
    ``u_j`` uniform on (-1, 1): real columns do not share one scale, and
    with one scale L-BFGS is at float32's resolution of the summed loss
    after a dozen iterations, where the order of a sum decides whether the
    next step counts (PR 22: 17, 18 or 19 passes by the column order
    alone). Each device fills its own shard block by block into one
    buffer, so neither the whole matrix nor a float32 copy of a shard ever
    exists on a device. Margins have a standard deviation near 2, so the
    classes overlap and the fit is not a separable one.

    No ``seed`` here. With bfloat16 operands the fit is chaotic: the same
    problem with its columns in another order (a relabelling that leaves
    the float32 sparse fit on its path) ends 20 iterations at losses 1e-3
    apart and takes 23 or 24 passes (my chip runs, PR 22), because one
    coefficient rounding the other way is a change of 2^-9. So every run of
    a dense cell fits bit-identical data."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]
    n_local = n // mesh.size
    if n % mesh.size or n_local % block_rows:
        raise ValueError(
            f"{n} rows over {mesh.size} devices do not divide into blocks "
            f"of {block_rows}"
        )
    scale = scale_spread ** jax.random.uniform(
        _key(data_seed, 5), (d,), jnp.float32, -1.0, 1.0
    )
    w_true = jax.random.normal(_key(data_seed, 2), (d,), jnp.float32)
    w_true = w_true * (2.0 / d**0.5) / scale
    span = 3.0**0.5

    def local(key, w_true, scale):
        key = jax.random.fold_in(key, jax.lax.axis_index(axis))

        def body(i, carry):
            X, y = carry
            kx, ky = jax.random.split(jax.random.fold_in(key, i))
            xb = jax.random.uniform(kx, (block_rows, d), jnp.float32, -span, span)
            xb = (xb * scale).at[:, d - 1].set(1.0).astype(dtype)
            # labels from the stored (rounded) features: the data ARE X
            m = jnp.dot(xb.astype(jnp.float32), w_true,
                        precision=jax.lax.Precision.HIGHEST)
            yb = jax.random.uniform(ky, (block_rows,)) < jax.nn.sigmoid(m)
            X = jax.lax.dynamic_update_slice(X, xb, (i * block_rows, 0))
            y = jax.lax.dynamic_update_slice(
                y, yb.astype(jnp.float32), (i * block_rows,)
            )
            return X, y

        return jax.lax.fori_loop(
            0, n_local // block_rows, body,
            (jnp.zeros((n_local, d), dtype), jnp.zeros((n_local,), jnp.float32)),
        )

    make = jax.jit(
        jax.shard_map(
            local, mesh=mesh, in_specs=(P(), P(), P()),
            out_specs=(P(axis), P(axis)), check_vma=False,
        )
    )
    return make(_key(data_seed, 3), w_true, scale)


def lognormal_counts(entities: int, total: int, floor: int, mu: float,
                     sigma: float) -> np.ndarray:
    """Rows per entity, ascending: ``floor`` plus the log-normal's quantiles
    at ``(j + 0.5) / entities``, scaled so that the counts sum to ``total``
    exactly. No draw: the same counts in every run."""
    from scipy.special import ndtri

    raw = np.exp(mu + sigma * ndtri((np.arange(entities) + 0.5) / entities))
    spare = total - floor * entities
    if spare < 0:
        raise ValueError(f"{total} rows cannot give {entities} entities {floor} each")
    part = np.floor(raw * (spare / raw.sum())).astype(np.int64)
    part[entities - (spare - int(part.sum())):] += 1  # the remainder, to the largest
    return part + floor


def glmix_rows(seed: int, n: int, d_fixed: int, effects: dict, data_seed: int):
    """A three-coordinate logistic GLMix data set.

    ``effects`` maps a random effect's id tag to ``{"entities", "width",
    "rows_floor", "lognormal_mu", "lognormal_sigma", "assignment"}``. An
    entity's row count is ``rows_floor`` plus a log-normal quantile
    (``lognormal_counts``). With ``"assignment": "blocks"`` an entity's
    rows are consecutive (a ratings file sorted by user); with
    ``"shuffled"`` they are spread over the file by a permutation (the
    items those users rated). ``seed`` draws which id each entity bears.
    Entity ids are made on the host, where the program's grouping needs
    them anyway.

    Returns ``(labels, X_fixed, {tag: X_tag}, {tag: ids})``: features and
    labels on the device, ids as host int32 arrays. ``X_fixed`` is
    ``(n, d_fixed + 1)`` with a last column of ones; labels follow a
    logistic model with a fixed vector and per-entity vectors, so every
    coordinate has signal to fit."""
    import jax
    import jax.numpy as jnp

    host = np.random.default_rng(data_seed)
    names = np.random.default_rng(seed)
    canonical: dict[str, np.ndarray] = {}
    ids: dict[str, np.ndarray] = {}
    for tag, spec in effects.items():
        e = int(spec["entities"])
        counts = lognormal_counts(
            e, n, int(spec.get("rows_floor", 0)),
            float(spec["lognormal_mu"]), float(spec["lognormal_sigma"]),
        )
        column = np.repeat(host.permutation(e).astype(np.int32), counts)
        if spec["assignment"] == "shuffled":
            column = column[host.permutation(n)]
        elif spec["assignment"] != "blocks":
            raise ValueError(f"unknown assignment {spec['assignment']!r}")
        canonical[tag] = column
        ids[tag] = names.permutation(e).astype(np.int32)[column]
    tags = list(effects)
    widths = [int(effects[t]["width"]) for t in tags]
    counts = [int(effects[t]["entities"]) for t in tags]
    span = 3.0**0.5

    @jax.jit
    def make(key, *id_cols):
        kf, kw, ky, *ke = jax.random.split(key, 3 + 2 * len(tags))
        Xf = jax.random.uniform(kf, (n, d_fixed + 1), jnp.float32, -span, span)
        Xf = Xf.at[:, d_fixed].set(1.0)
        w = jax.random.normal(kw, (d_fixed + 1,), jnp.float32) * (1.0 / d_fixed**0.5)
        margin = Xf @ w
        Xe = []
        for j, (width, e) in enumerate(zip(widths, counts)):
            X = jax.random.uniform(ke[2 * j], (n, width), jnp.float32, -span, span)
            W = jax.random.normal(ke[2 * j + 1], (e, width), jnp.float32)
            W = W * (0.7 / width**0.5)
            margin = margin + jnp.sum(X * W[id_cols[j]], axis=1)
            Xe.append(X)
        y = jax.random.uniform(ky, (n,), jnp.float32) < jax.nn.sigmoid(margin)
        return y.astype(jnp.float32), Xf, tuple(Xe)

    # the model that made the labels knows an entity by what it is, not by
    # the id this run gives it
    y, Xf, Xe = make(_key(data_seed, 4), *(jnp.asarray(canonical[t]) for t in tags))
    return y, Xf, dict(zip(tags, Xe)), ids
