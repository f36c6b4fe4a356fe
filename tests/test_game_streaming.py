"""Out-of-core GAME training vs the in-memory coordinate descent."""

from __future__ import annotations

import numpy as np
import pytest

from photon_ml_tpu.config import (
    FixedEffectCoordinateConfig,
    GameTrainingConfig,
    OptimizationConfig,
    OptimizerConfig,
    RandomEffectCoordinateConfig,
    RegularizationContext,
)
from photon_ml_tpu.game.streaming import StreamedGameData, StreamedGameTrainer
from photon_ml_tpu.types import RegularizationType, TaskType


# n=440 keeps the ragged final chunk at chunk_rows=128 (3 full + 56);
# streamed-vs-in-memory equivalence is row-count-independent
def _data(rng, n=440, d=6, E=8, dr=3):
    w_fixed = (rng.normal(size=d) * 0.6).astype(np.float32)
    W_re = (rng.normal(size=(E, dr)) * 0.6).astype(np.float32)
    X = rng.normal(size=(n, d)).astype(np.float32)
    Xr = rng.normal(size=(n, dr)).astype(np.float32)
    ids = rng.integers(0, E, size=n).astype(np.int32)
    margin = X @ w_fixed + np.sum(W_re[ids] * Xr, axis=1)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    return X, Xr, ids, y, margin


def _config(iters=2):
    opt = OptimizationConfig(
        # both arms of every equivalence test share this bound, so the
        # parity is bound-independent; 28 halves the per-coordinate solves
        optimizer=OptimizerConfig(max_iterations=28, tolerance=1e-8),
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    return GameTrainingConfig(
        task_type=TaskType.LOGISTIC_REGRESSION,
        coordinate_update_sequence=("fixed", "user"),
        coordinate_descent_iterations=iters,
        fixed_effect_coordinates={
            "fixed": FixedEffectCoordinateConfig(
                feature_shard_id="g", optimization=opt
            )
        },
        random_effect_coordinates={
            "user": RandomEffectCoordinateConfig(
                feature_shard_id="r", random_effect_type="uid", optimization=opt
            )
        },
    )


def test_streamed_game_matches_in_memory(rng):
    from photon_ml_tpu.estimators import GameEstimator
    from photon_ml_tpu.evaluation.evaluators import auc_roc
    from photon_ml_tpu.game import make_game_batch

    X, Xr, ids, y, margin = _data(rng)
    cfg = _config()

    # in-memory reference fit
    batch = make_game_batch(y, {"g": X, "r": Xr}, id_tags={"uid": ids})
    mem_model = GameEstimator(cfg).fit(batch)[0].model
    mem_auc = float(auc_roc(mem_model.score(batch), batch.labels))

    # streamed fit: tiny chunks force MANY chunk sweeps (the out-of-core path)
    data = StreamedGameData(
        labels=y, features={"g": X, "r": Xr}, id_tags={"uid": ids}
    )
    model, info = StreamedGameTrainer(cfg, chunk_rows=128).fit(data)
    stream_auc = float(auc_roc(model.score(batch), batch.labels))

    assert info["fixed"].converged or info["fixed"].iterations > 0
    # both trainers solve the same optimization problem; host-vs-device
    # optimizer twins differ only in arithmetic detail
    assert abs(stream_auc - mem_auc) < 0.01, (stream_auc, mem_auc)

    w_mem = np.asarray(mem_model.models["fixed"].model.coefficients.means)
    w_str = np.asarray(model.models["fixed"].model.coefficients.means)
    np.testing.assert_allclose(w_str, w_mem, rtol=0.1, atol=5e-2)
    W_mem = np.asarray(mem_model.models["user"].coefficients)
    W_str = np.asarray(model.models["user"].coefficients)
    np.testing.assert_allclose(W_str, W_mem, rtol=0.2, atol=0.1)


def test_streamed_game_chunking_invariance(rng):
    """Chunk size must not change the result (same objective, same data)."""
    X, Xr, ids, y, _ = _data(rng, n=400)
    cfg = _config(iters=1)
    data = StreamedGameData(
        labels=y, features={"g": X, "r": Xr}, id_tags={"uid": ids}
    )
    m1, _ = StreamedGameTrainer(cfg, chunk_rows=64).fit(data)
    m2, _ = StreamedGameTrainer(cfg, chunk_rows=400).fit(data)
    np.testing.assert_allclose(
        np.asarray(m1.models["fixed"].model.coefficients.means),
        np.asarray(m2.models["fixed"].model.coefficients.means),
        rtol=1e-2, atol=2e-3,
    )
    # f32 chunk-order accumulation in the fixed solve shifts the residual
    # offsets slightly; the RE solves inherit that noise
    np.testing.assert_allclose(
        np.asarray(m1.models["user"].coefficients),
        np.asarray(m2.models["user"].coefficients),
        rtol=1e-2, atol=2e-3,
    )


def test_streamed_device_split_bitwise(rng, monkeypatch):
    """PHOTON_RE_DEVICE_SPLIT in the streamed trainer (the test process
    runs 8 forced CPU devices): per-device owned-bucket dispatch with
    co-committed per-unit inputs is bitwise the knob-off fit, on both
    placement weight axes — and the device gauges actually published."""
    X, Xr, ids, y, _ = _data(rng, n=400)
    cfg = _config(iters=1)

    def fit():
        data = StreamedGameData(
            labels=y, features={"g": X, "r": Xr}, id_tags={"uid": ids}
        )
        model, _ = StreamedGameTrainer(cfg, chunk_rows=128).fit(data)
        return model

    ref = fit()
    monkeypatch.setenv("PHOTON_RE_DEVICE_SPLIT", "1")
    got = fit()
    np.testing.assert_array_equal(
        np.asarray(got.models["user"].coefficients),
        np.asarray(ref.models["user"].coefficients),
    )
    np.testing.assert_array_equal(
        np.asarray(got.models["fixed"].model.coefficients.means),
        np.asarray(ref.models["fixed"].model.coefficients.means),
    )
    from photon_ml_tpu.obs.metrics import REGISTRY

    g = REGISTRY.snapshot("re_shard.")["gauges"]
    assert g["re_shard.devices"] >= 2.0
    assert g["re_shard.device_balance"] >= 1.0
    # the bytes weight axis changes WHERE buckets go, never the model
    monkeypatch.setenv("PHOTON_RE_SPLIT_WEIGHT", "bytes")
    got2 = fit()
    np.testing.assert_array_equal(
        np.asarray(got2.models["user"].coefficients),
        np.asarray(ref.models["user"].coefficients),
    )


def test_streamed_game_rejects_unsupported_config(rng):
    cfg = _config()
    projected = GameTrainingConfig(
        task_type=cfg.task_type,
        coordinate_update_sequence=("user",),
        coordinate_descent_iterations=1,
        random_effect_coordinates={
            "user": RandomEffectCoordinateConfig(
                feature_shard_id="r", random_effect_type="uid",
                optimization=cfg.random_effect_coordinates["user"].optimization,
                random_projection_dim=4,
            )
        },
    )
    # projection itself is supported; projection + checkpointing is not
    # (checkpoints store the original-space model, which does not
    # round-trip the projected descent state exactly)
    StreamedGameTrainer(projected)
    with pytest.raises(NotImplementedError, match="checkpoint"):
        StreamedGameTrainer(projected, checkpoint_dir="/tmp/nope")

    from photon_ml_tpu.types import NormalizationType

    subspace_with_norm = GameTrainingConfig(
        task_type=cfg.task_type,
        coordinate_update_sequence=("user",),
        coordinate_descent_iterations=1,
        random_effect_coordinates={
            "user": RandomEffectCoordinateConfig(
                feature_shard_id="r", random_effect_type="uid",
                optimization=cfg.random_effect_coordinates["user"].optimization,
                features_to_samples_ratio_upper_bound=1.0,
            )
        },
        normalization=NormalizationType.SCALE_WITH_STANDARD_DEVIATION,
    )
    # subspace projection alone is supported; subspace + normalization is
    # not (per-entity column maps would need per-entity factor slices)
    with pytest.raises(NotImplementedError, match="subspace"):
        StreamedGameTrainer(subspace_with_norm)


def test_streamed_game_validation_history_matches_in_memory(rng):
    """Per-visit validation tracking: the streamed trainer's validation
    curve must match the in-memory descent's on the same data (parity with
    CoordinateDescent's per-iteration validation, SURVEY.md §2.2)."""
    from photon_ml_tpu.estimators import GameEstimator
    from photon_ml_tpu.game import make_game_batch

    X, Xr, ids, y, _ = _data(rng, n=500)
    Xv, Xrv, idsv, yv, _ = _data(rng, n=300)
    idsv = np.minimum(idsv, ids.max())  # validation entities ⊆ training
    cfg = _config(iters=2)

    batch = make_game_batch(y, {"g": X, "r": Xr}, id_tags={"uid": ids})
    vbatch = make_game_batch(yv, {"g": Xv, "r": Xrv}, id_tags={"uid": idsv})
    mem = GameEstimator(cfg).fit(batch, vbatch)[0]
    mem_hist = [
        {cid: res.metrics for cid, res in it_val.items()}
        for it_val in mem.descent.validation_history
    ]

    data = StreamedGameData(labels=y, features={"g": X, "r": Xr},
                            id_tags={"uid": ids})
    vdata = StreamedGameData(labels=yv, features={"g": Xv, "r": Xrv},
                             id_tags={"uid": idsv})
    tr = StreamedGameTrainer(cfg, chunk_rows=128, evaluators=("AUC",))
    tr.fit(data, validation=vdata)

    # flatten in-memory history (per outer iter, per coordinate) into the
    # streamed per-visit sequence and compare the shared metric
    flat_mem = [
        (cid, m["AUC"]) for it_val in mem_hist for cid, m in it_val.items()
    ]
    flat_str = [
        (cid, res.metrics["AUC"])
        for entry in tr.validation_history
        for cid, res in entry.items()
    ]
    assert [c for c, _ in flat_str] == [c for c, _ in flat_mem]
    for (c1, a1), (c2, a2) in zip(flat_str, flat_mem):
        assert abs(a1 - a2) < 0.02, (c1, a1, a2)


def test_streamed_game_checkpoint_resume_bit_exact(rng, tmp_path):
    """A run interrupted mid-descent and resumed must be BITWISE identical
    to an uninterrupted run (per-coordinate-visit checkpoints restore the
    residual-exchange state exactly)."""
    X, Xr, ids, y, _ = _data(rng, n=400)
    data = StreamedGameData(labels=y, features={"g": X, "r": Xr},
                            id_tags={"uid": ids})

    # uninterrupted: 3 outer iterations
    m_ref, _ = StreamedGameTrainer(_config(iters=3), chunk_rows=128).fit(data)

    # interrupted: 1 iteration with checkpoints, then extend to 3 in the
    # same directory (iteration count is a non-trajectory field, so the
    # fingerprint matches and the run resumes from the saved visit)
    ck = str(tmp_path / "ckpt")
    StreamedGameTrainer(_config(iters=1), chunk_rows=128,
                        checkpoint_dir=ck).fit(data)
    m_res, _ = StreamedGameTrainer(_config(iters=3), chunk_rows=128,
                                   checkpoint_dir=ck).fit(data)

    np.testing.assert_array_equal(
        np.asarray(m_ref.models["fixed"].model.coefficients.means),
        np.asarray(m_res.models["fixed"].model.coefficients.means),
    )
    np.testing.assert_array_equal(
        np.asarray(m_ref.models["user"].coefficients),
        np.asarray(m_res.models["user"].coefficients),
    )


def test_streamed_game_checkpoint_fingerprint_guard(rng, tmp_path):
    """A checkpoint written under a different configuration must be ignored
    (retrain, not silently resume)."""
    X, Xr, ids, y, _ = _data(rng, n=300)
    data = StreamedGameData(labels=y, features={"g": X, "r": Xr},
                            id_tags={"uid": ids})
    ck = str(tmp_path / "ckpt")
    StreamedGameTrainer(_config(iters=1), chunk_rows=128,
                        checkpoint_dir=ck).fit(data)

    import dataclasses

    cfg2 = _config(iters=1)
    opt2 = dataclasses.replace(
        cfg2.fixed_effect_coordinates["fixed"].optimization,
        regularization_weight=7.5,
    )
    cfg2 = dataclasses.replace(
        cfg2,
        fixed_effect_coordinates={
            "fixed": dataclasses.replace(
                cfg2.fixed_effect_coordinates["fixed"], optimization=opt2
            )
        },
    )
    # different λ → different fingerprint → fresh training (the model must
    # reflect λ=7.5, not the checkpointed λ=1 solution)
    m2, _ = StreamedGameTrainer(cfg2, chunk_rows=128,
                                checkpoint_dir=ck).fit(data)
    m_fresh, _ = StreamedGameTrainer(cfg2, chunk_rows=128).fit(data)
    np.testing.assert_array_equal(
        np.asarray(m2.models["fixed"].model.coefficients.means),
        np.asarray(m_fresh.models["fixed"].model.coefficients.means),
    )


def test_streamed_game_sparse_shards(rng):
    """Sparse feature shards stream through both the fixed-effect objective
    and the random-effect bucket solves; results match the equivalent dense
    representation."""
    from photon_ml_tpu.game.data import SparseFeatures

    n, d, E, dr = 400, 8, 6, 4
    k = 3
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    X_dense = np.zeros((n, d), np.float32)
    np.add.at(X_dense, (np.arange(n)[:, None], idx), val)
    Xr = rng.normal(size=(n, dr)).astype(np.float32)
    ids = rng.integers(0, E, size=n).astype(np.int32)
    w = (rng.normal(size=d) * 0.5).astype(np.float32)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(X_dense @ w)))).astype(np.float32)

    cfg = _config(iters=1)
    sparse = StreamedGameData(
        labels=y,
        features={"g": SparseFeatures(indices=idx, values=val, num_features=d),
                  "r": Xr},
        id_tags={"uid": ids},
    )
    dense = StreamedGameData(
        labels=y, features={"g": X_dense, "r": Xr}, id_tags={"uid": ids}
    )
    m_sp, info_sp = StreamedGameTrainer(cfg, chunk_rows=128).fit(sparse)
    m_de, _ = StreamedGameTrainer(cfg, chunk_rows=128).fit(dense)
    np.testing.assert_allclose(
        np.asarray(m_sp.models["fixed"].model.coefficients.means),
        np.asarray(m_de.models["fixed"].model.coefficients.means),
        rtol=1e-4, atol=1e-5,
    )
    # the RE solves consume the fixed coordinate's residual offsets, so the
    # sparse-vs-dense float-path epsilon in the fixed solve is amplified by
    # the per-entity optimizers — compare with correspondingly wider bounds
    np.testing.assert_allclose(
        np.asarray(m_sp.models["user"].coefficients),
        np.asarray(m_de.models["user"].coefficients),
        rtol=5e-2, atol=5e-3,
    )


def test_streamed_game_honest_re_diagnostics(rng):
    """Random-effect diagnostics must reflect the actual solves: real
    iteration counts (> 1 on a non-trivial problem) and a convergence flag
    that can be False when iterations are capped."""
    X, Xr, ids, y, _ = _data(rng, n=400)
    import dataclasses

    cfg = _config(iters=1)
    # cap RE iterations at 1: convergence is impossible on this problem
    tight = dataclasses.replace(
        cfg.random_effect_coordinates["user"],
        optimization=dataclasses.replace(
            cfg.random_effect_coordinates["user"].optimization,
            optimizer=dataclasses.replace(
                cfg.random_effect_coordinates["user"].optimization.optimizer,
                max_iterations=1,
            ),
        ),
    )
    cfg_tight = dataclasses.replace(
        cfg, random_effect_coordinates={"user": tight}
    )
    data = StreamedGameData(labels=y, features={"g": X, "r": Xr},
                            id_tags={"uid": ids})
    _, info = StreamedGameTrainer(cfg_tight, chunk_rows=128).fit(data)
    assert info["user"].iterations == 1
    assert info["user"].converged is False

    _, info2 = StreamedGameTrainer(cfg, chunk_rows=128).fit(data)
    assert info2["user"].iterations > 1
    assert info2["user"].converged is True


def test_streamed_game_warm_start(rng):
    """Warm start: the initial model's coordinates contribute scores
    before their first visit, so a warm 1-iteration fit continues the
    cold fit's trajectory (fixed coefficients move FROM the warm point,
    and a warm+1 fit beats a cold 1-iteration fit's loss)."""
    X, Xr, ids, y, _ = _data(rng, n=500)
    data = StreamedGameData(labels=y, features={"g": X, "r": Xr},
                            id_tags={"uid": ids})
    cold1, info_cold1 = StreamedGameTrainer(_config(iters=1), chunk_rows=128).fit(data)
    warm2, info_warm = StreamedGameTrainer(_config(iters=1), chunk_rows=128).fit(
        data, initial_model=cold1
    )
    straight2, info_2 = StreamedGameTrainer(_config(iters=2), chunk_rows=128).fit(data)
    # warm-started second iteration ~ the straight 2-iteration run
    np.testing.assert_allclose(
        np.asarray(warm2.models["fixed"].model.coefficients.means),
        np.asarray(straight2.models["fixed"].model.coefficients.means),
        rtol=1e-3, atol=1e-4,
    )
    np.testing.assert_allclose(
        np.asarray(warm2.models["user"].coefficients),
        np.asarray(straight2.models["user"].coefficients),
        rtol=1e-3, atol=1e-4,
    )


def test_streamed_driver_warm_start_roundtrip(tmp_path, rng):
    """Driver-level warm start: a saved streamed run seeds a second
    streamed run via model_input_dir (entity maps re-used, new entities
    cold-start)."""
    import dataclasses
    import json as _json

    from photon_ml_tpu.data.synthetic import synthetic_game_data

    from tests.test_drivers import _game_config, _quiet, _write_game_avro

    data = synthetic_game_data(rng, 300, d_fixed=3, effects={"userId": (8, 2)})
    train_path = tmp_path / "train.avro"
    _write_game_avro(str(train_path), rng, data=data)
    first = tmp_path / "first"
    from photon_ml_tpu.cli import train as train_cli

    cfg = _game_config(coordinate_descent_iterations=1)
    train_cli.run(
        cfg, [str(train_path)], str(first), logger=_quiet(tmp_path),
        streaming_chunk_rows=64,
    )
    cfg_warm = dataclasses.replace(cfg, model_input_dir=str(first / "best"))
    second = tmp_path / "second"
    model = train_cli.run(
        cfg_warm, [str(train_path)], str(second), logger=_quiet(tmp_path),
        streaming_chunk_rows=64,
    )
    # same data, same entity dictionary: rows line up
    with open(first / "entity-maps.json") as f:
        m1 = _json.load(f)
    with open(second / "entity-maps.json") as f:
        m2 = _json.load(f)
    assert m1 == m2
    assert np.isfinite(
        np.asarray(model.models["per_user"].coefficients)
    ).all()


def test_streamed_game_warm_start_preserves_absent_entities(rng):
    """A warm model's rows for entities ABSENT from the new data must
    survive (the saved dictionary is authoritative, not max-seen-id+1):
    regression for a truncation where a 5-entity warm model fit on data
    mentioning only entities 0..2 came back with 3 rows."""
    E_warm = 5
    X, Xr, ids, y, _ = _data(rng, n=300, E=3)  # new data touches ids 0..2
    data = StreamedGameData(labels=y, features={"g": X, "r": Xr},
                            id_tags={"uid": ids})
    cold, _ = StreamedGameTrainer(_config(iters=1), chunk_rows=128).fit(data)

    # build a 5-entity warm model by padding the cold model's RE matrix
    import dataclasses as _dc

    import jax.numpy as jnp

    sub = cold.models["user"]
    W = np.asarray(sub.coefficients, np.float32)
    pad = rng.normal(size=(E_warm - W.shape[0], W.shape[1])).astype(np.float32)
    W5 = np.concatenate([W, pad])
    warm_model = cold.updated(
        "user", _dc.replace(sub, coefficients=jnp.asarray(W5), variances=None)
    )

    out, _ = StreamedGameTrainer(_config(iters=1), chunk_rows=128).fit(
        data, initial_model=warm_model
    )
    W_out = np.asarray(out.models["user"].coefficients)
    assert W_out.shape[0] == E_warm, W_out.shape
    # warm-only entities have no data rows this fit: their rows survive
    np.testing.assert_allclose(W_out[3:], W5[3:], rtol=1e-6, atol=1e-6)

    # the declared-dictionary floor alone (no warm model) must also hold
    t = StreamedGameTrainer(
        _config(iters=1), chunk_rows=128, num_entities={"uid": E_warm}
    )
    out2, _ = t.fit(data)
    assert np.asarray(out2.models["user"].coefficients).shape[0] == E_warm


def test_streamed_game_normalization_and_variance_match_in_memory(rng):
    """STANDARDIZATION + SIMPLE variances on the streamed GAME path vs the
    in-memory estimator (VERDICT r3 missing #1: the reference supports both
    on its only, arbitrarily-scalable path). The fixed shard carries an
    intercept (absorbs shifts); the RE shard has none, so STANDARDIZATION
    degrades to scale-only — identically on both paths."""
    import dataclasses

    from photon_ml_tpu.estimators import GameEstimator
    from photon_ml_tpu.game import make_game_batch
    from photon_ml_tpu.types import NormalizationType, VarianceComputationType

    X, Xr, ids, y, _ = _data(rng, n=500)
    X = X.copy()
    X[:, 0] = X[:, 0] * 7.0 + 2.0  # badly scaled feature
    X[:, -1] = 1.0  # intercept column on the fixed shard
    cfg = dataclasses.replace(
        _config(iters=2),
        normalization=NormalizationType.STANDARDIZATION,
        variance_computation=VarianceComputationType.SIMPLE,
    )
    intercepts = {"g": X.shape[1] - 1}

    batch = make_game_batch(y, {"g": X, "r": Xr}, id_tags={"uid": ids})
    mem_model = GameEstimator(cfg, intercept_indices=intercepts).fit(batch)[0].model

    data = StreamedGameData(
        labels=y, features={"g": X, "r": Xr}, id_tags={"uid": ids}
    )
    st_model, info = StreamedGameTrainer(
        cfg, chunk_rows=128, intercept_indices=intercepts
    ).fit(data)

    np.testing.assert_allclose(
        np.asarray(st_model.models["fixed"].model.coefficients.means),
        np.asarray(mem_model.models["fixed"].model.coefficients.means),
        rtol=5e-2, atol=5e-3,
    )
    np.testing.assert_allclose(
        np.asarray(st_model.models["user"].coefficients),
        np.asarray(mem_model.models["user"].coefficients),
        rtol=0.2, atol=0.05,
    )
    v_st = st_model.models["fixed"].model.coefficients.variances
    v_mem = mem_model.models["fixed"].model.coefficients.variances
    assert v_st is not None and v_mem is not None
    np.testing.assert_allclose(
        np.asarray(v_st), np.asarray(v_mem), rtol=5e-2, atol=1e-6
    )
    V_st = st_model.models["user"].variances
    V_mem = mem_model.models["user"].variances
    assert V_st is not None and V_mem is not None
    np.testing.assert_allclose(
        np.asarray(V_st), np.asarray(V_mem), rtol=0.2, atol=1e-4
    )


def test_streamed_game_checkpoint_cadence_resume(rng, tmp_path):
    """checkpoint_every_n_visits > 1: fewer durable points, but resuming
    from whichever visit was last saved still reaches the uninterrupted
    run's exact result (VERDICT r3 weak #6 done criterion)."""
    import os

    X, Xr, ids, y, _ = _data(rng, n=400)
    data = StreamedGameData(labels=y, features={"g": X, "r": Xr},
                            id_tags={"uid": ids})
    m_ref, _ = StreamedGameTrainer(_config(iters=3), chunk_rows=128).fit(data)

    ck = str(tmp_path / "ckpt")
    t1 = StreamedGameTrainer(
        _config(iters=2), chunk_rows=128, checkpoint_dir=ck,
        checkpoint_every_n_visits=3,
    )
    t1.fit(data)
    # 2 iters x 2 coordinates = 4 visits; cadence 3 -> only visit 3 saved
    from photon_ml_tpu.checkpoint import load_checkpoint

    saved = load_checkpoint(ck)
    assert (saved.next_iteration, saved.next_coordinate) == (1, 1)

    t2 = StreamedGameTrainer(
        _config(iters=3), chunk_rows=128, checkpoint_dir=ck,
        checkpoint_every_n_visits=3,
    )
    m_res, _ = t2.fit(data)
    assert t2.resumed_from == (1, 1)
    np.testing.assert_array_equal(
        np.asarray(m_ref.models["fixed"].model.coefficients.means),
        np.asarray(m_res.models["fixed"].model.coefficients.means),
    )
    np.testing.assert_array_equal(
        np.asarray(m_ref.models["user"].coefficients),
        np.asarray(m_res.models["user"].coefficients),
    )


def test_streamed_game_down_sampling_matches_in_memory(rng):
    """Fixed-effect down-sampling on the streamed path (VERDICT r3
    next-10): same seeded subset as the in-memory estimator (seed 0,
    single process), so the two paths solve the same weighted objective."""
    import dataclasses

    from photon_ml_tpu.estimators import GameEstimator
    from photon_ml_tpu.game import make_game_batch

    X, Xr, ids, y, _ = _data(rng, n=600)
    cfg = _config(iters=1)
    opt_ds = dataclasses.replace(
        cfg.fixed_effect_coordinates["fixed"].optimization,
        down_sampling_rate=0.5,
    )
    cfg = dataclasses.replace(
        cfg,
        fixed_effect_coordinates={
            "fixed": dataclasses.replace(
                cfg.fixed_effect_coordinates["fixed"], optimization=opt_ds
            )
        },
    )
    batch = make_game_batch(y, {"g": X, "r": Xr}, id_tags={"uid": ids})
    mem = GameEstimator(cfg).fit(batch)[0].model
    data = StreamedGameData(
        labels=y, features={"g": X, "r": Xr}, id_tags={"uid": ids}
    )
    st, info = StreamedGameTrainer(cfg, chunk_rows=128).fit(data)
    np.testing.assert_allclose(
        np.asarray(st.models["fixed"].model.coefficients.means),
        np.asarray(mem.models["fixed"].model.coefficients.means),
        rtol=5e-2, atol=5e-3,
    )
    np.testing.assert_allclose(
        np.asarray(st.models["user"].coefficients),
        np.asarray(mem.models["user"].coefficients),
        rtol=0.2, atol=0.05,
    )


def test_streamed_game_random_projection_matches_in_memory(rng):
    """Shared random projection on the streamed path (VERDICT r3 missing
    #2): same seed-0 projector as the estimator, so both paths solve the
    same projected problem and map back score-exactly."""
    import dataclasses

    from photon_ml_tpu.estimators import GameEstimator
    from photon_ml_tpu.game import make_game_batch

    X, Xr, ids, y, _ = _data(rng, n=500, dr=6)
    cfg = _config(iters=2)
    cfg = dataclasses.replace(
        cfg,
        random_effect_coordinates={
            "user": dataclasses.replace(
                cfg.random_effect_coordinates["user"],
                random_projection_dim=3,
            )
        },
    )
    batch = make_game_batch(y, {"g": X, "r": Xr}, id_tags={"uid": ids})
    mem = GameEstimator(cfg).fit(batch)[0].model
    data = StreamedGameData(
        labels=y, features={"g": X, "r": Xr}, id_tags={"uid": ids}
    )
    st, info = StreamedGameTrainer(cfg, chunk_rows=128).fit(data)
    # both models live in the ORIGINAL feature space after map-back
    W_st = np.asarray(st.models["user"].coefficients)
    W_mem = np.asarray(mem.models["user"].coefficients)
    assert W_st.shape == W_mem.shape == (np.asarray(ids).max() + 1, 6)
    np.testing.assert_allclose(W_st, W_mem, rtol=0.2, atol=0.05)
    np.testing.assert_allclose(
        np.asarray(st.models["fixed"].model.coefficients.means),
        np.asarray(mem.models["fixed"].model.coefficients.means),
        rtol=5e-2, atol=5e-3,
    )
    assert st.models["user"].variances is None


def test_streamed_game_subspace_projection_matches_in_memory(rng):
    """Per-entity subspace projection on the streamed path (VERDICT r3
    missing #2: projection matters MOST at scale): each entity solves
    over its most-frequent columns, computed owner-side; parity with the
    in-memory estimator."""
    import dataclasses

    from photon_ml_tpu.estimators import GameEstimator
    from photon_ml_tpu.game import make_game_batch

    X, Xr, ids, y, _ = _data(rng, n=500, dr=8)
    Xr = Xr.copy()
    Xr[rng.uniform(size=Xr.shape) < 0.5] = 0.0  # sparse-ish columns
    cfg = _config(iters=1)
    cfg = dataclasses.replace(
        cfg,
        random_effect_coordinates={
            "user": dataclasses.replace(
                cfg.random_effect_coordinates["user"],
                features_to_samples_ratio_upper_bound=0.05,
            )
        },
    )
    batch = make_game_batch(y, {"g": X, "r": Xr}, id_tags={"uid": ids})
    mem = GameEstimator(cfg).fit(batch)[0].model
    data = StreamedGameData(
        labels=y, features={"g": X, "r": Xr}, id_tags={"uid": ids}
    )
    st, info = StreamedGameTrainer(cfg, chunk_rows=128).fit(data)
    W_st = np.asarray(st.models["user"].coefficients)
    W_mem = np.asarray(mem.models["user"].coefficients)
    assert W_st.shape == W_mem.shape
    # both solve width-p subspaces per entity; unselected columns are 0
    np.testing.assert_array_equal(W_st == 0.0, W_mem == 0.0)
    np.testing.assert_allclose(W_st, W_mem, rtol=0.2, atol=0.05)


def test_streamed_game_projection_with_subspace_and_intercept(rng):
    """Random projection + subspace + a registered RE intercept must fit
    (the projected solve space has no intercept column; regression for
    the subspace-column builder passing the original-space index)."""
    import dataclasses

    X, Xr, ids, y, _ = _data(rng, n=400, dr=8)
    cfg = _config(iters=1)
    cfg = dataclasses.replace(
        cfg,
        random_effect_coordinates={
            "user": dataclasses.replace(
                cfg.random_effect_coordinates["user"],
                random_projection_dim=4,
                features_to_samples_ratio_upper_bound=0.02,
            )
        },
    )
    data = StreamedGameData(
        labels=y, features={"g": X, "r": Xr}, id_tags={"uid": ids}
    )
    model, _ = StreamedGameTrainer(
        cfg, chunk_rows=128, intercept_indices={"r": 7}
    ).fit(data)
    W = np.asarray(model.models["user"].coefficients)
    assert W.shape[1] == 8 and np.isfinite(W).all()


def test_streamed_game_full_variance_matches_in_memory(rng):
    """FULL variances (diag of the dense Hessian inverse) on the streamed
    GAME path vs the in-memory estimator — the fixed effect accumulates its
    d×d Hessian chunk-wise, the per-entity solves invert their small dense
    Hessians on device, both exactly like in-memory (VERDICT r4 missing #2:
    every out-of-core path rejected FULL)."""
    import dataclasses

    from photon_ml_tpu.estimators import GameEstimator
    from photon_ml_tpu.game import make_game_batch
    from photon_ml_tpu.types import VarianceComputationType

    X, Xr, ids, y, _ = _data(rng, n=500)
    cfg = dataclasses.replace(
        _config(iters=2),
        variance_computation=VarianceComputationType.FULL,
    )

    batch = make_game_batch(y, {"g": X, "r": Xr}, id_tags={"uid": ids})
    mem_model = GameEstimator(cfg).fit(batch)[0].model

    data = StreamedGameData(
        labels=y, features={"g": X, "r": Xr}, id_tags={"uid": ids}
    )
    st_model, _ = StreamedGameTrainer(cfg, chunk_rows=128).fit(data)

    v_st = st_model.models["fixed"].model.coefficients.variances
    v_mem = mem_model.models["fixed"].model.coefficients.variances
    assert v_st is not None and v_mem is not None
    np.testing.assert_allclose(
        np.asarray(v_st), np.asarray(v_mem), rtol=5e-2, atol=1e-7
    )
    V_st = st_model.models["user"].variances
    V_mem = mem_model.models["user"].variances
    assert V_st is not None and V_mem is not None
    np.testing.assert_allclose(
        np.asarray(V_st), np.asarray(V_mem), rtol=0.2, atol=1e-4
    )


def test_streamed_game_incremental_prior_matches_in_memory(rng):
    """Incremental MAP training on the streamed path vs in-memory: the
    loaded model's means/variances anchor BOTH the fixed-effect streamed
    objective and the per-entity bucket solves (VERDICT r4 missing #3)."""
    import dataclasses

    from photon_ml_tpu.estimators import GameEstimator
    from photon_ml_tpu.game import make_game_batch
    from photon_ml_tpu.types import VarianceComputationType

    # streamed-vs-in-memory equivalence is row-count-independent; 320 rows
    # at chunk_rows=80 keeps the same 4-chunk structure as 500/128
    X, Xr, ids, y, _ = _data(rng, n=320)
    base_cfg = dataclasses.replace(
        _config(iters=2),
        variance_computation=VarianceComputationType.SIMPLE,
    )
    batch = make_game_batch(y, {"g": X, "r": Xr}, id_tags={"uid": ids})

    # a first-generation model WITH variances → per-coordinate precisions
    gen0 = GameEstimator(base_cfg).fit(batch)[0].model

    inc_cfg = dataclasses.replace(base_cfg, incremental=True)
    mem_model = GameEstimator(inc_cfg).fit(batch, initial_model=gen0)[0].model

    data = StreamedGameData(
        labels=y, features={"g": X, "r": Xr}, id_tags={"uid": ids}
    )
    st_model, _ = StreamedGameTrainer(inc_cfg, chunk_rows=80).fit(
        data, initial_model=gen0
    )
    np.testing.assert_allclose(
        np.asarray(st_model.models["fixed"].model.coefficients.means),
        np.asarray(mem_model.models["fixed"].model.coefficients.means),
        rtol=5e-2, atol=5e-3,
    )
    np.testing.assert_allclose(
        np.asarray(st_model.models["user"].coefficients),
        np.asarray(mem_model.models["user"].coefficients),
        rtol=0.2, atol=0.05,
    )
    # the prior must PULL: an incremental refit differs from a plain refit
    plain_model, _ = StreamedGameTrainer(base_cfg, chunk_rows=128).fit(
        data, initial_model=gen0
    )
    assert not np.allclose(
        np.asarray(st_model.models["fixed"].model.coefficients.means),
        np.asarray(plain_model.models["fixed"].model.coefficients.means),
        atol=1e-4,
    )


def test_grouped_metric_dropped_sentinel_fraction_logged(rng):
    """Grouped (Multi*) metrics drop sentinel -1 rows; the trainer must
    count and log the dropped fraction and warn LOUDLY when it is large,
    so a near-empty grouped metric on a validation-only tag cannot be
    mistaken for a real full-validation score (ADVICE r5)."""
    import warnings

    X, Xr, ids, y, _ = _data(rng, n=400)
    Xv, Xrv, idsv, yv, _ = _data(rng, n=200)
    idsv = np.minimum(idsv, ids.max())
    # a VALIDATION-ONLY grouped tag where most rows carry the -1 sentinel
    vtag = rng.integers(0, 4, size=200).astype(np.int64)
    vtag[: 150] = -1  # 75% dropped

    data = StreamedGameData(labels=y, features={"g": X, "r": Xr},
                            id_tags={"uid": ids})
    vdata = StreamedGameData(
        labels=yv, features={"g": Xv, "r": Xrv},
        id_tags={"uid": idsv, "vtag": vtag},
    )
    logs: list[str] = []
    tr = StreamedGameTrainer(
        _config(iters=1), chunk_rows=128,
        evaluators=("AUC", "MULTI_AUC(vtag)"), logger=logs.append,
    )
    with pytest.warns(RuntimeWarning, match="vtag.*75.0%|75.0%.*vtag"):
        tr.fit(data, validation=vdata)
    assert any(
        "vtag" in m and "150/200" in m and "75.0%" in m for m in logs
    ), logs

    # below the warning threshold: counted and logged, but NO loud warning
    vtag_ok = rng.integers(0, 4, size=200).astype(np.int64)
    vtag_ok[:20] = -1  # 10% dropped
    vdata_ok = StreamedGameData(
        labels=yv, features={"g": Xv, "r": Xrv},
        id_tags={"uid": idsv, "vtag": vtag_ok},
    )
    logs2: list[str] = []
    tr2 = StreamedGameTrainer(
        _config(iters=1), chunk_rows=128,
        evaluators=("AUC", "MULTI_AUC(vtag)"), logger=logs2.append,
    )
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        tr2.fit(data, validation=vdata_ok)
    assert not [
        w for w in caught if "unseen-entity sentinel" in str(w.message)
    ]
    assert any("vtag" in m and "20/200" in m for m in logs2), logs2


@pytest.mark.kernel
def test_game_visit_scoring_on_tile_coo_matches_the_untiled_chunks(rng, monkeypatch):
    """The GAME visit-scoring consumer: ``ops.streaming.stream_scores`` with
    tile-COO layouts (the per-visit validation/coordinate scorer's kernel
    path, riding the process-wide layout cache) against the same chunks on
    the XLA path (interpret mode, retuned-down constants); the second visit
    packs nothing and scores bit for bit the same."""
    import photon_ml_tpu.ops.sparse_tiled as st_mod
    from photon_ml_tpu.ops import tile_cache
    from photon_ml_tpu.ops.streaming import sparse_chunks, stream_scores

    monkeypatch.setattr(st_mod, "GROUPS_PER_STEP", 8)
    monkeypatch.setattr(st_mod, "SEGMENTS_PER_DMA", 2)
    tile_cache.clear()
    n, d, k = 2048, 4096, 4
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    chunks = sparse_chunks(idx, val, y, chunk_rows=1024)
    w = rng.normal(size=d).astype(np.float32)
    got = stream_scores(chunks, w, num_rows=n, num_features=d, tile_sparse=True)
    misses = tile_cache.stats()["misses"]
    assert misses == 2  # one layout a chunk
    ref = stream_scores(chunks, w, num_rows=n, num_features=d,
                        tile_sparse=False)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
    again = stream_scores(chunks, w, num_rows=n, num_features=d, tile_sparse=True)
    np.testing.assert_array_equal(again, got)
    s = tile_cache.stats()
    assert (s["hits"], s["misses"]) == (2, misses)
    tile_cache.clear()


def test_atomic_savez_fsyncs_before_and_after_rename(tmp_path, monkeypatch):
    """Per-visit score shards must be DURABLY committed: data fsync'd
    before the atomic rename (a kill between rename and writeback could
    otherwise leave a truncated shard under the final name for
    `_load_resume_state` to half-parse) and the directory fsync'd after,
    so the shard is on disk before the metadata commit point. A failed
    write leaves neither the final file nor a temp turd."""
    import os

    from photon_ml_tpu.game.streaming import _atomic_savez

    events = []
    real_fsync, real_replace = os.fsync, os.replace
    monkeypatch.setattr(
        os, "fsync", lambda fd: (events.append("fsync"), real_fsync(fd))[1]
    )
    monkeypatch.setattr(
        os, "replace",
        lambda a, b: (events.append("replace"), real_replace(a, b))[1],
    )
    d = str(tmp_path / "ck")
    final = os.path.join(d, "scores-shard-00000.npz")
    _atomic_savez(d, final, {"total": np.arange(5, dtype=np.float32)})
    # file fsync BEFORE the rename, directory fsync AFTER it
    assert events == ["fsync", "replace", "fsync"]
    with np.load(final) as z:
        np.testing.assert_array_equal(
            z["total"], np.arange(5, dtype=np.float32)
        )

    # failure mid-write: no final file, no leftover temp file
    class Boom(RuntimeError):
        pass

    def bad_savez(f, **kw):
        raise Boom()

    monkeypatch.setattr(np, "savez", bad_savez)
    final2 = os.path.join(d, "scores-shard-00001.npz")
    with pytest.raises(Boom):
        _atomic_savez(d, final2, {"total": np.arange(5, dtype=np.float32)})
    assert not os.path.exists(final2)
    assert [p for p in os.listdir(d) if p.endswith(".tmp")] == []
