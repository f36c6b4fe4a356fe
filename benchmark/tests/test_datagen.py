"""The generators: one seed one data set, and shapes no seed can move."""

import numpy as np

from benchmark import datagen


def test_sparse_rows_are_the_same_matrix_under_every_seed():
    a = datagen.sparse_glm_rows(1, 512, 4096, 8, 1.0, data_seed=0)
    b = datagen.sparse_glm_rows(1, 512, 4096, 8, 1.0, data_seed=0)
    c = datagen.sparse_glm_rows(2, 512, 4096, 8, 1.0, data_seed=0)
    other = datagen.sparse_glm_rows(1, 512, 4096, 8, 1.0, data_seed=1)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # another seed: other arrays, the same rows (a rotation of each)
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))
    np.testing.assert_array_equal(np.asarray(a[2]), np.asarray(c[2]))
    for i in (0, 17, 511):
        pairs = lambda t: sorted(zip(np.asarray(t[0])[i], np.asarray(t[1])[i]))
        assert pairs(a) == pairs(c)
    assert not np.array_equal(np.asarray(a[2]), np.asarray(other[2]))
    idx, val = np.asarray(a[0]), np.asarray(a[1])
    assert idx.min() >= 0 and idx.max() < 4096 and (val > 0).all()
    np.testing.assert_allclose(np.sum(val * val, axis=1), 1.0, rtol=1e-5)
    # a power law: the most popular column takes far more than 1/d
    assert np.bincount(idx.ravel()).max() > 20 * idx.size / 4096


def test_lognormal_counts_sum_and_floor():
    counts = datagen.lognormal_counts(1000, 150000, 20, 4.17, 1.14)
    assert counts.sum() == 150000 and counts.min() >= 20
    assert (np.diff(counts) >= 0).all() and counts.max() > 10 * counts.min()


def test_glmix_is_the_same_problem_under_other_entity_ids():
    effects = {
        "u": {"entities": 40, "width": 3, "rows_floor": 5, "lognormal_mu": 3.0,
              "lognormal_sigma": 1.0, "assignment": "blocks"},
        "i": {"entities": 15, "width": 2, "rows_floor": 1, "lognormal_mu": 4.0,
              "lognormal_sigma": 1.2, "assignment": "shuffled"},
    }
    y1, Xf1, Xe1, ids1 = datagen.glmix_rows(1, 3000, 4, effects, data_seed=0)
    y2, Xf2, Xe2, ids2 = datagen.glmix_rows(2, 3000, 4, effects, data_seed=0)
    assert Xf1.shape == (3000, 5) and Xe1["u"].shape == (3000, 3)
    assert np.all(np.asarray(Xf1[:, 4]) == 1.0)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    np.testing.assert_array_equal(np.asarray(Xe1["i"]), np.asarray(Xe2["i"]))
    for tag in effects:
        assert not np.array_equal(ids1[tag], ids2[tag])
        # a relabelling: rows that shared an entity still do
        pairs = set(zip(ids1[tag].tolist(), ids2[tag].tolist()))
        assert len(pairs) == effects[tag]["entities"]
    assert (np.diff(ids1["u"]) != 0).sum() == 39  # blocks: one run an entity
    assert 0.2 < float(np.asarray(y1).mean()) < 0.8
    y3, *_ = datagen.glmix_rows(1, 3000, 4, effects, data_seed=5)
    assert not np.array_equal(np.asarray(y1), np.asarray(y3))


def test_dense_rows_are_sharded_and_blockwise():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    X, y = datagen.dense_glm_rows(1024, 128, jnp.bfloat16, mesh, 64, data_seed=0)
    assert X.shape == (1024, 128) and X.dtype == jnp.bfloat16
    assert len(X.sharding.device_set) == 4
    Xh = np.asarray(X.astype(jnp.float32))
    assert np.all(Xh[:, -1] == 1.0)
    # no two blocks and no two devices drew the same rows
    assert len({Xh[i, :8].tobytes() for i in range(0, 1024, 64)}) == 16
    assert abs(Xh[:, :-1].std() - 1.0) < 0.05
    assert 0.2 < float(np.asarray(y).mean()) < 0.8
    # no seed: every run of a dense cell fits the same bits
    X2, y2 = datagen.dense_glm_rows(1024, 128, jnp.bfloat16, mesh, 64, data_seed=0)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))
    np.testing.assert_array_equal(Xh, np.asarray(X2.astype(jnp.float32)))
    # columns on different scales
    X3, _ = datagen.dense_glm_rows(1024, 128, jnp.float32, mesh, 64, data_seed=0,
                                   scale_spread=4.0)
    stds = np.asarray(X3)[:, :-1].std(axis=0)
    assert stds.max() / stds.min() > 6.0
