"""Synthetic data for a GLMix whose per-user random effect is over the
ITEM's sparse descriptor (configuration ``glmix_sparse_re``).

As ``datagen.glmix_rows`` (whose counts, assignments and relabelling this
keeps: the problem is the configuration's ``data_seed``, ``--seed`` only
relabels it), with one shard sparse: an effect whose spec has
``"kind": "sparse"`` and ``"descriptor_of": <tag>`` gives every row the
sparse descriptor of the row's ``<tag>`` entity, one vector per such entity.
All rows of an item therefore share their nonzeros, and the columns a user's
rows touch are the union over the items that user rated.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.datagen import _coprime_multiplier, _key, lognormal_counts


def item_descriptors(entities: int, width: int, nonzeros: int,
                     zipf_exponent: float, data_seed: int):
    """One sparse vector per entity: ``(entities, nonzeros)`` int32 column
    ids, DISTINCT within a vector, and float32 values, positive with unit L2
    norm (``datagen.sparse_glm_rows``'s columns: a power law over popularity
    ranks, sent through an affine permutation of the column ids). On the
    host: a table of a few thousand vectors."""
    host = np.random.default_rng([data_seed, 7])
    a = _coprime_multiplier(host, width)
    b = int(host.integers(0, width))
    cols = np.empty((entities, nonzeros), np.int64)
    for e in range(entities):
        seen: list[int] = []
        while len(seen) < nonzeros:
            u = host.random(4 * nonzeros)
            if zipf_exponent == 1.0:
                rank = np.exp(u * math.log(width + 1.0)) - 1.0
            else:
                ex = 1.0 - zipf_exponent
                rank = (u * ((width + 1.0) ** ex - 1.0) + 1.0) ** (1.0 / ex) - 1.0
            for r in np.clip(rank.astype(np.int64), 0, width - 1):
                if r not in seen:
                    seen.append(int(r))
                    if len(seen) == nonzeros:
                        break
        cols[e] = seen
    cols = (cols * a + b) % width
    vals = host.uniform(0.05, 1.0, size=(entities, nonzeros))
    vals /= np.sqrt((vals * vals).sum(axis=1, keepdims=True))
    return cols.astype(np.int32), vals.astype(np.float32)


def glmix_sparse_rows(seed: int, n: int, d_fixed: int, effects: dict,
                      data_seed: int):
    """A three-coordinate logistic GLMix data set with sparse and dense
    random-effect shards.

    ``effects`` maps an id tag to ``datagen.glmix_rows``'s spec; a spec with
    ``"kind": "sparse"`` also has ``"nonzeros"``, ``"zipf_exponent"`` and
    ``"descriptor_of"``. ``seed`` draws which id each entity bears and
    rotates the order of every row's nonzeros; no shape and no entity's rows
    or support follow it.

    Returns ``(labels, X_fixed, {tag: shard}, {tag: ids})``: a dense shard
    is an ``(n, width)`` device array, a sparse one a pair of ``(n,
    nonzeros)`` device arrays (int32 column ids, float32 values); ids are
    host int32 arrays. Labels follow a logistic model with a fixed vector, a
    dense vector per entity of a dense effect (a table) and a sparse vector
    per entity of a sparse effect (a hash of entity and column: at 17,312 x
    16,384 a table would be a gigabyte that serves nothing else)."""
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
    tables = {
        t: item_descriptors(
            int(effects[s["descriptor_of"]]["entities"]), int(s["width"]),
            int(s["nonzeros"]), float(s["zipf_exponent"]), data_seed,
        )
        for t, s in effects.items() if s.get("kind") == "sparse"
    }
    shifts = {
        t: jnp.int32(names.integers(0, int(effects[t]["nonzeros"])))
        for t in tables
    }
    span = 3.0**0.5

    @jax.jit
    def make(key, id_cols, tables, shifts):
        kf, kw, ky, *ke = jax.random.split(key, 3 + 2 * len(tags))
        Xf = jax.random.uniform(kf, (n, d_fixed + 1), jnp.float32, -span, span)
        Xf = Xf.at[:, d_fixed].set(1.0)
        w = jax.random.normal(kw, (d_fixed + 1,), jnp.float32) * (1.0 / d_fixed**0.5)
        margin = Xf @ w
        shards = {}
        for j, tag in enumerate(tags):
            spec = effects[tag]
            if tag in tables:
                of = id_cols[spec["descriptor_of"]]
                col, val = tables[tag][0][of], tables[tag][1][of]
                # the entity's coefficient for a column: both hashed to
                # (-1, 1), scaled so that the effect's margin has the
                # dense effects' standard deviation, 0.7
                h = (id_cols[tag].astype(jnp.uint32)[:, None] * jnp.uint32(40503)
                     + col.astype(jnp.uint32) + jnp.uint32(1))
                h = (h * jnp.uint32(2654435761)) >> jnp.uint32(8)
                w_e = h.astype(jnp.float32) * (2.0 / (1 << 24)) - 1.0
                margin = margin + (0.7 * span) * jnp.sum(val * w_e, axis=1)
                shards[tag] = (
                    jnp.roll(col, shifts[tag], axis=1),
                    jnp.roll(val, shifts[tag], axis=1),
                )
                continue
            width, e = int(spec["width"]), int(spec["entities"])
            X = jax.random.uniform(ke[2 * j], (n, width), jnp.float32, -span, span)
            W = jax.random.normal(ke[2 * j + 1], (e, width), jnp.float32)
            W = W * (0.7 / width**0.5)
            margin = margin + jnp.sum(X * W[id_cols[tag]], axis=1)
            shards[tag] = X
        y = jax.random.uniform(ky, (n,), jnp.float32) < jax.nn.sigmoid(margin)
        return y.astype(jnp.float32), Xf, shards

    # the model that made the labels knows an entity by what it is, not by
    # the id this run gives it
    y, Xf, shards = make(
        _key(data_seed, 6), {t: jnp.asarray(canonical[t]) for t in tags},
        {t: (jnp.asarray(c), jnp.asarray(v)) for t, (c, v) in tables.items()},
        shifts,
    )
    return y, Xf, shards, ids
