"""A GLMix data set drawn over a mesh: every chip makes its own rows.

``datagen.glmix_rows`` draws the whole data set in one ``jit`` on one device;
at MovieLens-20M's whole size no one chip holds it. Here the id columns are
made on the host exactly as there (``datagen.lognormal_counts``, the
``data_seed``'s permutations, ``--seed``'s relabelling), and features and
labels are drawn under ``shard_map``: a chip draws the block of rows it will
hold, from the configuration's key folded with its index in the mesh, as
``datagen.dense_glm_rows`` does for ``glm_dense_dp4``. No chip ever holds
another chip's rows, and nothing but the id columns crosses from the host.

The row count is padded to a multiple of the mesh with rows of weight 0,
label 0 and features 0 after the last real row, which is the rule of the
program's ``game/data.place_game_batch`` (the arrays come out of here placed
the way it would place them, so it leaves them where they are). The id columns
that the program GROUPS are the real rows' (``[:n]``): a padded row belongs to
no bucket; in the batch it bears id 0 and scores 0.
"""

from __future__ import annotations

import numpy as np

from benchmark import datagen


def id_columns(seed: int, n: int, effects: dict, data_seed: int):
    """``(canonical, ids)``, each ``{tag: (n,) int32}`` on the host: the
    entity a row belongs to as the label model knows it, and under the id
    this run gives it. The same columns ``datagen.glmix_rows`` makes: row
    counts are ``lognormal_counts``' quantiles (no draw), ``data_seed``
    decides which entity has which count and, for a ``shuffled`` effect,
    where its rows lie; ``seed`` only relabels."""
    host = np.random.default_rng(data_seed)
    names = np.random.default_rng(seed)
    canonical: dict[str, np.ndarray] = {}
    ids: dict[str, np.ndarray] = {}
    for tag, spec in effects.items():
        e = int(spec["entities"])
        counts = datagen.lognormal_counts(
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
    return canonical, ids


def glmix_mesh_rows(seed: int, n: int, d_fixed: int, effects: dict,
                    data_seed: int, mesh):
    """The data set of ``datagen.glmix_rows``' distribution, row-sharded
    over ``mesh``'s one axis.

    Returns ``(labels, weights, X_fixed, {tag: X_tag}, {tag: ids})``. The
    device arrays have ``n`` rounded up to a multiple of the mesh rows and
    lie ``P(axis)``; a padded row has weight, label and features 0. ``ids``
    are host int32 columns of the padded length, id 0 in the padded rows.
    ``X_fixed`` is ``(rows, d_fixed + 1)`` with a last column of ones in the
    real rows. Labels follow a logistic model with a fixed vector and
    per-entity vectors drawn from ``data_seed``, which knows an entity by
    what it is and not by the id ``seed`` gives it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    axis = mesh.axis_names[0]
    chips = mesh.size
    n_local = -(-n // chips)
    n_pad = n_local * chips
    canonical, ids = id_columns(seed, n, effects, data_seed)
    pad = lambda col: np.concatenate([col, np.zeros(n_pad - n, np.int32)])
    ids = {t: pad(c) for t, c in ids.items()}
    tags = list(effects)
    widths = [int(effects[t]["width"]) for t in tags]
    counts = [int(effects[t]["entities"]) for t in tags]
    span = 3.0**0.5
    rows = NamedSharding(mesh, P(axis))

    def local(key, *id_cols):
        chip = jax.lax.axis_index(axis)
        kf, ky, *ke = jax.random.split(jax.random.fold_in(key, chip), 2 + len(tags))
        kw, *kW = jax.random.split(jax.random.fold_in(key, chips), 1 + len(tags))
        real = (chip * n_local + jnp.arange(n_local)) < n
        keep = real.astype(jnp.float32)
        Xf = jax.random.uniform(kf, (n_local, d_fixed + 1), jnp.float32, -span, span)
        Xf = Xf.at[:, d_fixed].set(1.0) * keep[:, None]
        w = jax.random.normal(kw, (d_fixed + 1,), jnp.float32) * (1.0 / d_fixed**0.5)
        margin = jnp.dot(Xf, w, precision=jax.lax.Precision.HIGHEST)
        Xe = []
        for j, (width, e) in enumerate(zip(widths, counts)):
            X = jax.random.uniform(ke[j], (n_local, width), jnp.float32, -span, span)
            X = X * keep[:, None]
            # the same matrix on every chip: the key does not hold the chip
            W = jax.random.normal(kW[j], (e, width), jnp.float32) * (0.7 / width**0.5)
            margin = margin + jnp.sum(X * W[id_cols[j]], axis=1)
            Xe.append(X)
        y = jax.random.uniform(ky, (n_local,), jnp.float32) < jax.nn.sigmoid(margin)
        return y.astype(jnp.float32) * keep, keep, Xf, tuple(Xe)

    make = jax.jit(
        jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(),) + (P(axis),) * len(tags),
            out_specs=(P(axis), P(axis), P(axis), (P(axis),) * len(tags)),
            check_vma=False,
        )
    )
    y, weights, Xf, Xe = make(
        datagen._key(data_seed, 6),
        *(jax.device_put(pad(canonical[t]), rows) for t in tags),
    )
    return y, weights, Xf, dict(zip(tags, Xe)), ids
