"""Multi-host runtime smoke tests.

Spawns TWO separate processes that join one ``jax.distributed`` runtime
over loopback (each with 2 virtual CPU devices → a 4-device global mesh),
assemble a globally-sharded batch from per-host row slices, run the full
distributed L-BFGS step over it, and check the result against a
single-process solve on the concatenated data. This is the test-strategy
analog of the reference's local-mode Spark cluster tests (SURVEY.md §4,
§2.6 Spark-replacement table).
"""

import json
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_WORKER = textwrap.dedent(
    """
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    # cross-process CPU collectives: newer jaxlib CPU clients implement
    # multiprocess computations only through an explicit collectives
    # backend (gloo over TCP) — without this every worker dies with
    # "Multiprocess computations aren't implemented on the CPU backend"
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    coordinator, pid = sys.argv[1], int(sys.argv[2])

    from photon_ml_tpu.parallel.multihost import (
        global_batch_from_host_shards,
        host_shard_of_paths,
        initialize_multihost,
        runtime_summary,
        shard_batch_multihost,
    )

    info = initialize_multihost(coordinator, num_processes=2, process_id=pid)
    assert info["process_count"] == 2, info
    assert info["global_devices"] == 4, info

    import jax.numpy as jnp
    import numpy as np
    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.ops.batch import DenseBatch
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.parallel import data_mesh
    from photon_ml_tpu.parallel.distributed import sharded_minimize
    from photon_ml_tpu.optim import lbfgs_minimize
    from photon_ml_tpu.types import TaskType

    # deterministic global dataset; THIS host takes its row slice
    rng = np.random.default_rng(0)
    n, d = 64, 5
    X = rng.normal(size=(n, d)).astype(np.float32)
    w_true = (rng.normal(size=d) * 0.5).astype(np.float32)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ w_true)))).astype(np.float32)
    lo, hi = pid * (n // 2), (pid + 1) * (n // 2)
    local = DenseBatch(
        X=X[lo:hi], labels=y[lo:hi],
        offsets=np.zeros(hi - lo, np.float32),
        weights=np.ones(hi - lo, np.float32),
    )

    mesh = data_mesh()  # global: 4 devices across 2 processes
    gbatch = shard_batch_multihost(local, mesh)
    assert gbatch.X.shape == (64, 5), gbatch.X.shape

    cfg = OptimizerConfig(max_iterations=50, tolerance=1e-9)
    res = sharded_minimize(
        lbfgs_minimize, gbatch, jnp.zeros((d,), jnp.float32), cfg, mesh,
        loss_for_task(TaskType.LOGISTIC_REGRESSION), l2_weight=1.0,
    )
    # path round-robin check
    mine = host_shard_of_paths(["p0", "p1", "p2", "p3"])
    expected = [["p0", "p2"], ["p1", "p3"]][pid]
    assert mine == expected, (mine, expected)

    print("RESULT " + json.dumps({
        "pid": pid,
        "w": np.asarray(res.w).tolist(),
        "value": float(res.value),
    }))
    """
)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_two_process_distributed_training(tmp_path):
    coordinator = f"127.0.0.1:{_free_port()}"
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
    }
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER, coordinator, str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"worker failed:\n{out}\n{err}"
        outs.append(out)

    results = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("RESULT "):
                r = json.loads(line[len("RESULT "):])
                results[r["pid"]] = r
    assert set(results) == {0, 1}
    # both processes computed the same replicated optimum
    np.testing.assert_allclose(results[0]["w"], results[1]["w"], rtol=1e-6)

    # single-process reference on the same global data
    import jax.numpy as jnp

    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.ops.batch import dense_batch_from_numpy
    from photon_ml_tpu.ops.glm import make_objective
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.optim import lbfgs_minimize
    from photon_ml_tpu.types import TaskType

    rng = np.random.default_rng(0)
    n, d = 64, 5
    X = rng.normal(size=(n, d)).astype(np.float32)
    w_true = (rng.normal(size=d) * 0.5).astype(np.float32)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(X @ w_true)))).astype(np.float32)
    obj = make_objective(
        dense_batch_from_numpy(X, y), loss_for_task(TaskType.LOGISTIC_REGRESSION),
        l2_weight=1.0,
    )
    ref = lbfgs_minimize(obj, jnp.zeros((d,), jnp.float32),
                         OptimizerConfig(max_iterations=50, tolerance=1e-9))
    np.testing.assert_allclose(
        results[0]["w"], np.asarray(ref.w), rtol=1e-3, atol=1e-4
    )


_GLM_WORKER = textwrap.dedent(
    """
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    # cross-process CPU collectives: newer jaxlib CPU clients implement
    # multiprocess computations only through an explicit collectives
    # backend (gloo over TCP) — without this every worker dies with
    # "Multiprocess computations aren't implemented on the CPU backend"
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    coordinator, pid, data_dir, out_dir = sys.argv[1:5]
    os.environ["JAX_COORDINATOR_ADDRESS"] = coordinator
    os.environ["JAX_NUM_PROCESSES"] = "2"
    os.environ["JAX_PROCESS_ID"] = pid

    from photon_ml_tpu.cli import train_glm
    train_glm.main([
        "--task", "LOGISTIC_REGRESSION",
        "--train-data", data_dir,
        "--format", "avro",
        "--weights", "1.0",
        "--max-iterations", "60",
        "--tolerance", "1e-8",
        "--streaming-chunk-rows", "64",
        "--multihost",
        "--output-dir", out_dir,
    ])
    print("GLM WORKER DONE", pid)
    """
)


@pytest.mark.slow
def test_two_process_streamed_glm_matches_single(tmp_path, rng):
    """--multihost streamed GLM: two hosts each read half the part files;
    the trained model must match a single-process streamed run on all files."""
    from photon_ml_tpu.io import TRAINING_EXAMPLE_SCHEMA, write_avro_file

    data_dir = tmp_path / "data"
    data_dir.mkdir()
    for part in range(2):
        recs = []
        for i in range(120):
            feats = [
                {"name": "g", "term": str(j), "value": float(rng.normal())}
                for j in range(3)
            ]
            recs.append(
                {
                    "uid": f"p{part}s{i}", "response": float(rng.integers(0, 2)),
                    "offset": None, "weight": None, "features": feats,
                    "metadataMap": {},
                }
            )
        write_avro_file(
            str(data_dir / f"part-{part:05d}.avro"),
            json.loads(json.dumps(TRAINING_EXAMPLE_SCHEMA)),
            recs,
        )

    coordinator = f"127.0.0.1:{_free_port()}"
    env = {
        k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
    }
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _GLM_WORKER, coordinator, str(pid),
             str(data_dir), str(tmp_path / f"out{pid}")],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for pid in range(2)
    ]
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"worker failed:\n{out}\n{err}"

    # single-process streamed reference on the same directory
    import io as _io

    from photon_ml_tpu.cli import train_glm as cli
    from photon_ml_tpu.io import read_avro_file
    from photon_ml_tpu.types import TaskType
    from photon_ml_tpu.utils import PhotonLogger

    cli.run(
        TaskType.LOGISTIC_REGRESSION, [str(data_dir)], str(tmp_path / "ref"),
        data_format="avro", weights=[1.0], max_iterations=60, tolerance=1e-8,
        streaming_chunk_rows=64, logger=PhotonLogger(None, stream=_io.StringIO()),
    )

    def coeffs(p):
        _, recs = read_avro_file(p)
        return {(r["name"], r["term"]): r["value"] for r in recs[0]["means"]}

    multi = coeffs(str(tmp_path / "out0" / "best" / "model.avro"))
    ref = coeffs(str(tmp_path / "ref" / "best" / "model.avro"))
    assert set(multi) == set(ref)
    for key in ref:
        np.testing.assert_allclose(multi[key], ref[key], rtol=1e-2, atol=1e-3)
    # only process 0 wrote outputs (models AND sweep checkpoints)
    assert not (tmp_path / "out1" / "best").exists()
    assert (tmp_path / "out0" / "checkpoints" / "sweep-done.npz").exists()
    assert not (tmp_path / "out1" / "checkpoints").exists()

    # RERUN into the same output dir: process 0 loads the completed λ from
    # its checkpoint and broadcasts the decision — both processes must
    # short-circuit identically (no collective mismatch) and reproduce the
    # same best model
    coordinator2 = f"127.0.0.1:{_free_port()}"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _GLM_WORKER, coordinator2, str(pid),
             str(data_dir), str(tmp_path / f"out{pid}")],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for pid in range(2)
    ]
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"resume worker failed:\n{out}\n{err}"
    rerun = coeffs(str(tmp_path / "out0" / "best" / "model.avro"))
    assert set(rerun) == set(multi)
    for key in multi:
        np.testing.assert_allclose(rerun[key], multi[key], rtol=1e-6)


_SCORE_WORKER = textwrap.dedent(
    """
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    # cross-process CPU collectives: newer jaxlib CPU clients implement
    # multiprocess computations only through an explicit collectives
    # backend (gloo over TCP) — without this every worker dies with
    # "Multiprocess computations aren't implemented on the CPU backend"
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    coordinator, pid, model_dir, data_dir, out_dir, cfg = sys.argv[1:7]
    os.environ["JAX_COORDINATOR_ADDRESS"] = coordinator
    os.environ["JAX_NUM_PROCESSES"] = "2"
    os.environ["JAX_PROCESS_ID"] = pid

    from photon_ml_tpu.cli import score
    score.main([
        "--model-dir", model_dir, "--data", data_dir,
        "--output-dir", out_dir, "--evaluators", "AUC", "MULTI_AUC(userId)",
        "--config", cfg, "--multihost",
    ])
    print("SCORE WORKER DONE", pid)
    """
)


@pytest.mark.slow
def test_two_process_scoring_matches_single(tmp_path, rng):
    """--multihost scoring: hosts score disjoint file slices and write their
    own partitions; the union of scores and the global metrics must match a
    single-host scoring run."""
    import io as _io

    from photon_ml_tpu.cli import score as score_cli
    from photon_ml_tpu.cli import train as train_cli
    from photon_ml_tpu.config import (
        FeatureShardConfig,
        FixedEffectCoordinateConfig,
        GameTrainingConfig,
        OptimizationConfig,
        OptimizerConfig,
    )
    from photon_ml_tpu.data.synthetic import synthetic_game_data
    from photon_ml_tpu.io import TRAINING_EXAMPLE_SCHEMA, read_avro_file, write_avro_file
    from photon_ml_tpu.types import TaskType
    from photon_ml_tpu.utils import PhotonLogger

    def write_file(path, data, lo, hi, seed_offset=0):
        recs = []
        for i in range(lo, hi):
            recs.append({
                "uid": f"s{seed_offset + i}",
                "response": float(data.y[i]), "offset": None, "weight": None,
                "features": [
                    {"name": "g", "term": str(j), "value": float(data.X[i, j])}
                    for j in range(3)
                ],
                # grouping tag with NO random-effect coordinate: grouped
                # evaluators on multihost scoring owner-route these ids
                # through the training-saved entity map (VERDICT r4 next-7)
                "metadataMap": {"userId": f"user_{i % 17}"},
            })
        write_avro_file(path, json.loads(json.dumps(TRAINING_EXAMPLE_SCHEMA)), recs)

    data = synthetic_game_data(rng, 300, d_fixed=3, effects={})
    train_path = tmp_path / "train.avro"
    write_file(str(train_path), data, 0, 200)
    test_dir = tmp_path / "test"
    test_dir.mkdir()
    write_file(str(test_dir / "part-0.avro"), data, 200, 250)
    write_file(str(test_dir / "part-1.avro"), data, 250, 300)

    cfg = GameTrainingConfig(
        task_type=TaskType.LOGISTIC_REGRESSION,
        coordinate_update_sequence=("fixed",),
        coordinate_descent_iterations=1,
        fixed_effect_coordinates={
            "fixed": FixedEffectCoordinateConfig(
                feature_shard_id="global",
                optimization=OptimizationConfig(
                    optimizer=OptimizerConfig(max_iterations=40, tolerance=1e-8)
                ),
            )
        },
        feature_shards={
            "global": FeatureShardConfig(feature_bags=("features",), has_intercept=True)
        },
        evaluators=("AUC", "MULTI_AUC(userId)"),
    )
    model_dir = tmp_path / "model"
    train_cli.run(
        cfg, [str(train_path)], str(model_dir),
        logger=PhotonLogger(None, stream=_io.StringIO()),
    )
    cfg_path = tmp_path / "config.json"
    cfg_path.write_text(json.dumps(cfg.to_dict()))

    # single-host reference scoring
    ref_out = tmp_path / "ref-scores"
    _, ref_metrics = score_cli.run(
        str(model_dir), [str(test_dir)], str(ref_out),
        evaluators=["AUC", "MULTI_AUC(userId)"],
        feature_shards=dict(cfg.feature_shards),
        logger=PhotonLogger(None, stream=_io.StringIO()),
    )

    coordinator = f"127.0.0.1:{_free_port()}"
    env = {k: v for k, v in os.environ.items() if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    mh_out = tmp_path / "mh-scores"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _SCORE_WORKER, coordinator, str(pid),
             str(model_dir), str(test_dir), str(mh_out), str(cfg_path)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for pid in range(2)
    ]
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"score worker failed:\n{out}\n{err}"

    def read_scores(root):
        out = {}
        d = os.path.join(root, "scores")
        for fn in sorted(os.listdir(d)):
            _, recs = read_avro_file(os.path.join(d, fn))
            for r in recs:
                out[r["uid"]] = r["predictionScore"]
        return out

    ref = read_scores(str(ref_out))
    mh = read_scores(str(mh_out))
    assert set(ref) == set(mh) and len(ref) == 100
    for uid in ref:
        np.testing.assert_allclose(mh[uid], ref[uid], rtol=1e-5, atol=1e-6)
    # two partitions, one per host
    assert sorted(os.listdir(mh_out / "scores")) == ["part-00000.avro", "part-00001.avro"]
    with open(mh_out / "metrics.json") as f:
        mh_metrics = json.load(f)
    np.testing.assert_allclose(mh_metrics["AUC"], ref_metrics["AUC"], rtol=1e-6)
    # grouped metric: owner-routed per-group partials vs single-host exact
    np.testing.assert_allclose(
        mh_metrics["MULTI_AUC(userId)"], ref_metrics["MULTI_AUC(userId)"],
        rtol=1e-6,
    )


_GAME_WORKER = textwrap.dedent(
    """
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    # cross-process CPU collectives: newer jaxlib CPU clients implement
    # multiprocess computations only through an explicit collectives
    # backend (gloo over TCP) — without this every worker dies with
    # "Multiprocess computations aren't implemented on the CPU backend"
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    coordinator, pid, cfg_path, data_dir, val_dir, out_dir = sys.argv[1:7]
    os.environ["JAX_COORDINATOR_ADDRESS"] = coordinator
    os.environ["JAX_NUM_PROCESSES"] = "2"
    os.environ["JAX_PROCESS_ID"] = pid

    from photon_ml_tpu.cli import train
    train.main([
        "--config", cfg_path,
        "--train-data", data_dir,
        "--validation-data", val_dir,
        "--streaming-chunk-rows", "64",
        "--multihost",
        "--output-dir", out_dir,
    ])
    print("GAME WORKER DONE", pid)
    """
)


@pytest.mark.slow
def test_two_process_streamed_game_matches_single(tmp_path, rng):
    """--multihost streamed GAME: each host ingests half the part files
    (no host holds the global dataset); the random-effect entity exchange
    routes rows to their owners; the trained model must match a
    single-process streamed run on all files (VERDICT r2 missing #1 done
    criterion)."""
    import json as _json

    from photon_ml_tpu.config import (
        FeatureShardConfig,
        FixedEffectCoordinateConfig,
        GameTrainingConfig,
        OptimizationConfig,
        OptimizerConfig,
        RandomEffectCoordinateConfig,
        RegularizationContext,
    )
    from photon_ml_tpu.data.synthetic import synthetic_game_data
    from photon_ml_tpu.io import TRAINING_EXAMPLE_SCHEMA, write_avro_file
    from photon_ml_tpu.types import RegularizationType, TaskType

    data = synthetic_game_data(rng, 360, d_fixed=3, effects={"userId": (10, 2)})

    def write_file(path, lo, hi):
        recs = []
        for i in range(lo, hi):
            recs.append({
                "uid": f"s{i}",
                "response": float(data.y[i]), "offset": None, "weight": None,
                "features": [
                    {"name": "g", "term": str(j), "value": float(data.X[i, j])}
                    for j in range(3)
                ],
                "userFeatures": [
                    {"name": "u", "term": str(j),
                     "value": float(data.entity_X["userId"][i, j])}
                    for j in range(2)
                ],
                "metadataMap": {
                    "userId": f"user_{data.entity_ids['userId'][i]}",
                    # VALIDATION-ONLY grouping tag: no coordinate of this
                    # type exists — exercises the dedicated owner-routing
                    # pass for grouped evaluators (VERDICT r4 next-7)
                    "queryId": f"q_{i // 6}",
                },
            })
        schema = _json.loads(_json.dumps(TRAINING_EXAMPLE_SCHEMA))
        schema["fields"].insert(
            5,
            {"name": "userFeatures",
             "type": {"type": "array", "items": "NameTermValueAvro"},
             "default": []},
        )
        write_avro_file(path, schema, recs)

    data_dir = tmp_path / "train"
    data_dir.mkdir()
    write_file(str(data_dir / "part-00000.avro"), 0, 150)
    write_file(str(data_dir / "part-00001.avro"), 150, 300)
    val_dir = tmp_path / "val"
    val_dir.mkdir()
    write_file(str(val_dir / "part-00000.avro"), 300, 330)
    write_file(str(val_dir / "part-00001.avro"), 330, 360)

    opt = OptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=40, tolerance=1e-8),
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    cfg = GameTrainingConfig(
        task_type=TaskType.LOGISTIC_REGRESSION,
        coordinate_update_sequence=("fixed", "per_user"),
        coordinate_descent_iterations=2,
        fixed_effect_coordinates={
            "fixed": FixedEffectCoordinateConfig(
                feature_shard_id="global", optimization=opt
            )
        },
        random_effect_coordinates={
            "per_user": RandomEffectCoordinateConfig(
                random_effect_type="userId", feature_shard_id="per_user",
                optimization=opt,
            )
        },
        feature_shards={
            "global": FeatureShardConfig(
                feature_bags=("features",), has_intercept=True
            ),
            "per_user": FeatureShardConfig(
                feature_bags=("userFeatures",), has_intercept=False
            ),
        },
        evaluators=("AUC", "MULTI_AUC(queryId)"),
    )
    cfg_path = tmp_path / "config.json"
    cfg_path.write_text(_json.dumps(cfg.to_dict()))

    coordinator = f"127.0.0.1:{_free_port()}"
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
    }
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _GAME_WORKER, coordinator, str(pid),
             str(cfg_path), str(data_dir), str(val_dir),
             str(tmp_path / f"out{pid}")],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for pid in range(2)
    ]
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, f"game worker failed:\n{out}\n{err}"

    # single-process streamed reference on all files
    import io as _io

    from photon_ml_tpu.cli import train as train_cli
    from photon_ml_tpu.io.model_io import load_game_model
    from photon_ml_tpu.utils import PhotonLogger

    ref = train_cli.run(
        cfg, [str(data_dir)], str(tmp_path / "ref"),
        validation_data=[str(val_dir)],
        logger=PhotonLogger(None, stream=_io.StringIO()),
        streaming_chunk_rows=64,
    )

    # process 0 wrote the model; load both and compare coefficient values
    from photon_ml_tpu.data.index_map import IndexMap

    imaps = {
        sid: IndexMap.load(str(tmp_path / "out0" / "index-maps" / f"{sid}.npz"))
        for sid in ("global", "per_user")
    }
    with open(tmp_path / "out0" / "entity-maps.json") as f:
        ent_maps = json.load(f)
    mh_model = load_game_model(
        str(tmp_path / "out0" / "best"),
        index_maps=imaps,
        entity_ids={"per_user": ent_maps["userId"]},
    )
    np.testing.assert_allclose(
        np.asarray(mh_model.models["fixed"].model.coefficients.means),
        np.asarray(ref.models["fixed"].model.coefficients.means),
        rtol=1e-3, atol=1e-4,
    )
    # entity rows compare through each run's own entity dictionary (file
    # order differs between the sharded and single-process ingests)
    with open(tmp_path / "ref" / "entity-maps.json") as f:
        ref_ent = json.load(f)
    W_mh = np.asarray(mh_model.models["per_user"].coefficients)
    W_ref = np.asarray(ref.models["per_user"].coefficients)
    for name, mh_row in ent_maps["userId"].items():
        np.testing.assert_allclose(
            W_mh[mh_row], W_ref[ref_ent["userId"][name]],
            rtol=5e-3, atol=1e-3, err_msg=name,
        )
    # validation history recorded with global metrics
    with open(tmp_path / "out0" / "metrics.json") as f:
        mh_metrics = json.load(f)
    assert len(mh_metrics["validation_history"]) == 4
    with open(tmp_path / "ref" / "metrics.json") as f:
        ref_metrics = json.load(f)
    for a, b in zip(
        mh_metrics["validation_history"], ref_metrics["validation_history"]
    ):
        (ca, ma), = a.items()
        (cb, mb), = b.items()
        assert ca == cb
        np.testing.assert_allclose(ma["AUC"], mb["AUC"], atol=5e-3)
        # grouped metric on the validation-only tag: the multihost
        # owner-routed partials must agree with the single-process value
        np.testing.assert_allclose(
            ma["MULTI_AUC(queryId)"], mb["MULTI_AUC(queryId)"], atol=5e-3
        )
    # only process 0 wrote outputs
    assert not (tmp_path / "out1" / "best").exists()


_TRAFFIC_WORKER = textwrap.dedent(
    """
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    # cross-process CPU collectives: newer jaxlib CPU clients implement
    # multiprocess computations only through an explicit collectives
    # backend (gloo over TCP) — without this every worker dies with
    # "Multiprocess computations aren't implemented on the CPU backend"
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    coordinator, pid = sys.argv[1], int(sys.argv[2])
    jax.distributed.initialize(coordinator, num_processes=2, process_id=pid)

    import numpy as np
    from photon_ml_tpu.config import (
        GameTrainingConfig, OptimizationConfig, OptimizerConfig,
        RandomEffectCoordinateConfig, RegularizationContext,
    )
    from photon_ml_tpu.game.streaming import StreamedGameData, StreamedGameTrainer
    from photon_ml_tpu.types import RegularizationType, TaskType
    import photon_ml_tpu.parallel.multihost as mh

    # record every per-visit exchange's accounting
    calls = []
    orig = mh.exchange_rows
    def recording(arrays, dest, **kw):
        out = orig(arrays, dest, **kw)
        calls.append(dict(mh.LAST_EXCHANGE_STATS, n_keys=len(arrays)))
        return out
    mh.exchange_rows = recording
    import photon_ml_tpu.game.streaming as gs

    n_local, E, dr = 200, 16, 3
    rng = np.random.default_rng(42 + pid)
    Xr = rng.normal(size=(n_local, dr)).astype(np.float32)
    ids = rng.integers(0, E, size=n_local).astype(np.int64)
    y = (rng.uniform(size=n_local) < 0.5).astype(np.float32)
    data = StreamedGameData(labels=y, features={"r": Xr}, id_tags={"uid": ids})

    opt = OptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=20, tolerance=1e-7),
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    cfg = GameTrainingConfig(
        task_type=TaskType.LOGISTIC_REGRESSION,
        coordinate_update_sequence=("user",),
        coordinate_descent_iterations=2,
        random_effect_coordinates={
            "user": RandomEffectCoordinateConfig(
                feature_shard_id="r", random_effect_type="uid",
                optimization=opt,
            )
        },
    )
    trainer = StreamedGameTrainer(cfg, chunk_rows=64, multihost=True)
    model, info = trainer.fit(data)

    # ingest: ceil(200/64) = 4 point-to-point rounds (the entity shuffle
    # is p2p now too); then 2 descent iterations x (offsets + scores)
    assert len(calls) == 4 + 4, calls
    for c in calls:
        # O(owned rows): offsets exchanges send exactly this host's rows;
        # score exchanges send its owned rows (n_global/P up to entity
        # imbalance) — and the padded all-to-all volume stays within a
        # small imbalance factor of the routed rows. NOT P x n rows.
        assert c["rows_sent"] <= 1.5 * n_local, c
        assert c["padded_rows"] <= 2.0 * c["rows_sent"] * c["n_keys"], c
    W = np.asarray(model.models["user"].coefficients)
    assert W.shape[0] == E and np.isfinite(W).all()
    print("TRAFFIC WORKER DONE", pid, len(calls))
    """
)


@pytest.mark.slow
def test_two_process_exchange_traffic_is_point_to_point(tmp_path):
    """Per-visit offset/score exchanges route O(owned-row) bytes through
    the all-to-all, not the O(P·n) broadcast round 3 used (VERDICT r3
    weak #5 done criterion). The ingest-time entity shuffle remains the
    only O(P·n) step."""
    coordinator = f"127.0.0.1:{_free_port()}"
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
    }
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _TRAFFIC_WORKER, coordinator, str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        )
        for pid in range(2)
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=420)
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed:\n{out}\n{err[-2000:]}"
        assert "TRAFFIC WORKER DONE" in out


_SHARDED_CKPT_WORKER = textwrap.dedent(
    """
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    # cross-process CPU collectives: newer jaxlib CPU clients implement
    # multiprocess computations only through an explicit collectives
    # backend (gloo over TCP) — without this every worker dies with
    # "Multiprocess computations aren't implemented on the CPU backend"
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    coordinator, pid, ckdir = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    jax.distributed.initialize(coordinator, num_processes=2, process_id=pid)

    import numpy as np
    from photon_ml_tpu.config import (
        FixedEffectCoordinateConfig, GameTrainingConfig, OptimizationConfig,
        OptimizerConfig, RandomEffectCoordinateConfig, RegularizationContext,
    )
    from photon_ml_tpu.game.streaming import StreamedGameData, StreamedGameTrainer
    from photon_ml_tpu.types import RegularizationType, TaskType

    n_local, E, d, dr = 150, 12, 4, 3
    rng = np.random.default_rng(7 + pid)
    X = rng.normal(size=(n_local, d)).astype(np.float32)
    Xr = rng.normal(size=(n_local, dr)).astype(np.float32)
    ids = rng.integers(0, E, size=n_local).astype(np.int64)
    y = (rng.uniform(size=n_local) < 0.5).astype(np.float32)
    data = StreamedGameData(
        labels=y, features={"g": X, "r": Xr}, id_tags={"uid": ids}
    )

    opt = OptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=25, tolerance=1e-8),
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    def cfg(iters):
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
                    feature_shard_id="r", random_effect_type="uid",
                    optimization=opt,
                )
            },
        )

    def T(iters, ck=None):
        return StreamedGameTrainer(
            cfg(iters), chunk_rows=64, multihost=True, checkpoint_dir=ck
        )

    # interrupted (1 iter) -> sharded checkpoint files, metadata-only main
    T(1, ckdir).fit(data)
    assert os.path.exists(os.path.join(ckdir, f"scores-shard-{pid:05d}.npz"))
    if pid == 0:
        from photon_ml_tpu.checkpoint import load_checkpoint
        saved = load_checkpoint(ckdir)
        assert saved is not None and saved.scores is None, "main file must hold metadata only"

    # resume to 2 iterations == straight 2-iteration run, bitwise
    t2 = T(2, ckdir)
    m_res, _ = t2.fit(data)
    assert t2.resumed_from == (1, 0), t2.resumed_from
    m_ref, _ = T(2).fit(data)
    np.testing.assert_array_equal(
        np.asarray(m_res.models["fixed"].model.coefficients.means),
        np.asarray(m_ref.models["fixed"].model.coefficients.means),
    )
    np.testing.assert_array_equal(
        np.asarray(m_res.models["user"].coefficients),
        np.asarray(m_ref.models["user"].coefficients),
    )
    print("SHARDED CKPT WORKER DONE", pid)
    """
)


@pytest.mark.slow
def test_two_process_sharded_checkpoint_resume(tmp_path):
    """Multi-host checkpoints write per-host score-slice files (O(n/P) per
    host, no cross-host score traffic); resume restores each host's slice
    from its own shard and matches an uninterrupted run bitwise (VERDICT
    r3 weak #6 done criterion)."""
    ckdir = tmp_path / "ckpt"
    ckdir.mkdir()
    coordinator = f"127.0.0.1:{_free_port()}"
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
    }
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _SHARDED_CKPT_WORKER, coordinator,
             str(pid), str(ckdir)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        )
        for pid in range(2)
    ]
    for p in procs:
        out, err = p.communicate(timeout=420)
        assert p.returncode == 0, f"worker failed:\n{out}\n{err[-2500:]}"
        assert "SHARDED CKPT WORKER DONE" in out


_SKEW_WORKER = textwrap.dedent(
    """
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax
    jax.config.update("jax_platforms", "cpu")
    # cross-process CPU collectives: newer jaxlib CPU clients implement
    # multiprocess computations only through an explicit collectives
    # backend (gloo over TCP) — without this every worker dies with
    # "Multiprocess computations aren't implemented on the CPU backend"
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    coordinator, pid = sys.argv[1], int(sys.argv[2])
    jax.distributed.initialize(coordinator, num_processes=4, process_id=pid)

    import numpy as np
    import photon_ml_tpu.parallel.multihost as mh

    P, E, n_local = 4, 16, 600

    def draw(seed):
        # Zipf(s=2) over E entities: the head entity carries ~63% of rows,
        # so its owner process is hot — the skew regime VERDICT r4 weak #7
        # says is the COMMON case at the 16-host north star.
        rng = np.random.default_rng(100 + seed)
        probs = np.arange(1, E + 1, dtype=np.float64) ** -2.0
        probs /= probs.sum()
        ids = rng.choice(E, size=n_local, p=probs).astype(np.int64)
        vals = (
            ids[:, None] * 1000.0 + seed * 100.0
            + (np.arange(n_local)[:, None] % 7) + np.arange(3)[None, :]
        ).astype(np.float32)
        return ids, vals

    def expected_for(me, seed_base):
        exp_i, exp_v = [], []
        for s in range(P):
            sids, svals = draw(seed_base + s)
            order = np.argsort(sids % P, kind="stable")
            rows = order[(sids % P)[order] == me]
            exp_i.append(sids[rows]); exp_v.append(svals[rows])
        return np.concatenate(exp_i), np.concatenate(exp_v)

    # --- skewed exchange: must take the zero-padding host p2p transport
    ids, vals = draw(pid)
    out = mh.exchange_rows({"id": ids, "v": vals}, (ids % P))
    st = dict(mh.LAST_EXCHANGE_STATS)
    assert st["transport"] == "p2p_host", st
    assert st["padded_rows"] <= 2 * st["rows_sent"] * 2, st  # 2 keys
    exp_i, exp_v = expected_for(pid, 0)
    assert np.array_equal(out["id"], exp_i)
    assert np.array_equal(out["v"], exp_v)

    # --- again with fresh data: the socket mesh is cached, not rebuilt
    ids2, vals2 = draw(pid + 40)
    out2 = mh.exchange_rows({"id": ids2, "v": vals2}, (ids2 % P))
    assert dict(mh.LAST_EXCHANGE_STATS)["transport"] == "p2p_host"
    exp_i2, exp_v2 = expected_for(pid, 40)
    assert np.array_equal(out2["id"], exp_i2)
    assert np.array_equal(out2["v"], exp_v2)

    # --- balanced exchange: stays on the compiled all_to_all (ICI lane)
    ids_b = np.arange(n_local, dtype=np.int64)
    vals_b = (ids_b[:, None] + pid * 10000.0).astype(np.float32) + np.arange(3)
    out_b = mh.exchange_rows({"id": ids_b, "v": vals_b}, (ids_b % P))
    st_b = dict(mh.LAST_EXCHANGE_STATS)
    assert st_b["transport"] == "all_to_all", st_b
    assert st_b["padded_rows"] <= 2 * st_b["rows_sent"] * 2, st_b

    # --- streamed GAME training under entity skew at P=4: every ingest
    # and per-visit exchange obeys the padding bound; skewed rounds ride
    # p2p. (Extends the P=2 uniform traffic test — VERDICT r4 next-4.)
    calls = []
    orig = mh.exchange_rows
    def recording(arrays, dest, **kw):
        res = orig(arrays, dest, **kw)
        calls.append(dict(mh.LAST_EXCHANGE_STATS, n_keys=len(arrays)))
        return res
    mh.exchange_rows = recording

    from photon_ml_tpu.config import (
        GameTrainingConfig, OptimizationConfig, OptimizerConfig,
        RandomEffectCoordinateConfig, RegularizationContext,
    )
    from photon_ml_tpu.game.streaming import StreamedGameData, StreamedGameTrainer
    from photon_ml_tpu.types import RegularizationType, TaskType

    n_tr, dr = 200, 3
    rng = np.random.default_rng(7 + pid)
    tids, _ = draw(pid + 80)
    tids = tids[:n_tr]
    Xr = rng.normal(size=(n_tr, dr)).astype(np.float32)
    y = (rng.uniform(size=n_tr) < 0.5).astype(np.float32)
    data = StreamedGameData(
        labels=y, features={"r": Xr}, id_tags={"uid": tids}
    )
    opt = OptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=15, tolerance=1e-7),
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    cfg = GameTrainingConfig(
        task_type=TaskType.LOGISTIC_REGRESSION,
        coordinate_update_sequence=("user",),
        coordinate_descent_iterations=2,
        random_effect_coordinates={
            "user": RandomEffectCoordinateConfig(
                feature_shard_id="r", random_effect_type="uid",
                optimization=opt,
            )
        },
    )
    trainer = StreamedGameTrainer(cfg, chunk_rows=64, multihost=True)
    model, info = trainer.fit(data)

    # ingest: ceil(200/64) = 4 p2p rounds; then 2 iterations x
    # (offsets + scores) = 8 exchanges total, same count as P=2 — the
    # exchange COUNT is iteration-structural, independent of P.
    assert len(calls) == 4 + 4, [c.get("transport") for c in calls]
    assert any(c["transport"] == "p2p_host" for c in calls), calls
    for c in calls:
        assert c["padded_rows"] <= 2.0 * c["rows_sent"] * c["n_keys"], c
    W = np.asarray(model.models["user"].coefficients)
    # Zipf tail entities may be unseen in the draw — the model covers the
    # ENTITIES OBSERVED, which is why <= E rather than == E
    assert 4 <= W.shape[0] <= E and np.isfinite(W).all()
    print("SKEW WORKER DONE", pid, len(calls))
    """
)


@pytest.mark.slow
def test_four_process_skewed_exchange_is_padding_bounded(tmp_path):
    """Entity skew (Zipf head entity -> one hot owner) must not inflate
    exchange traffic to O(P x payload): the transport falls back from the
    uniform-bucket all_to_all to a true point-to-point host exchange, and
    every ingest/per-visit exchange in a skewed P=4 streamed GAME fit
    keeps padded_rows <= 2 x rows_sent (VERDICT r4 weak #7 / next-4 done
    criterion)."""
    coordinator = f"127.0.0.1:{_free_port()}"
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
    }
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _SKEW_WORKER, coordinator, str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        )
        for pid in range(4)
    ]
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, f"worker failed:\n{out}\n{err[-2500:]}"
        assert "SKEW WORKER DONE" in out


class TestExchangeHardening:
    """Single-process unit tests for the exchange transport's failure
    hygiene (ADVICE r5): a failed point-to-point exchange must tear the
    socket mesh down (partially-drained streams mis-frame length
    prefixes), and loopback address discovery must fail fast instead of
    advertising an undialable address to remote peers."""

    def test_p2p_error_resets_host_links(self, monkeypatch):
        import jax

        import photon_ml_tpu.parallel.multihost as mh

        class FakeSock:
            def __init__(self):
                self.closed = False

            def close(self):
                self.closed = True

            def sendall(self, *_):
                if self.closed:
                    raise OSError("closed")

            def recv(self, *_):
                raise ConnectionError("peer died mid-stream")

        send_sock, recv_sock = FakeSock(), FakeSock()
        links = {"send": {1: send_sock}, "recv": {1: recv_sock}}
        monkeypatch.setattr(mh, "_HOST_LINKS", links)
        monkeypatch.setattr(mh, "_host_links", lambda: links)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(jax, "process_index", lambda: 0)

        arrays = {"v": np.arange(4, dtype=np.float32)}
        order = np.arange(4, dtype=np.int64)
        starts = np.asarray([0, 2, 4], np.int64)
        counts_matrix = np.asarray([[2, 2], [2, 2]], np.int64)
        with pytest.raises(ConnectionError):
            mh._host_p2p_exchange(arrays, order, starts, counts_matrix)
        # the mesh is gone and every cached socket is closed: the NEXT
        # exchange rebuilds from scratch instead of mis-framing a
        # partially-drained stream
        assert mh._HOST_LINKS is None
        assert send_sock.closed and recv_sock.closed

    def test_p2p_timeout_knob_reads_env(self, monkeypatch):
        import photon_ml_tpu.parallel.multihost as mh

        monkeypatch.delenv("PHOTON_P2P_TIMEOUT_S", raising=False)
        assert mh._p2p_timeout_s() == 300.0  # generous default
        monkeypatch.setenv("PHOTON_P2P_TIMEOUT_S", "7.5")
        assert mh._p2p_timeout_s() == 7.5
        # 0 (or negative) = disable: blocking sockets, the knob convention
        monkeypatch.setenv("PHOTON_P2P_TIMEOUT_S", "0")
        assert mh._p2p_timeout_s() is None
        monkeypatch.setenv("PHOTON_P2P_TIMEOUT_S", "-1")
        assert mh._p2p_timeout_s() is None

    def test_silent_peer_times_out_and_reaches_reset_path(self, monkeypatch):
        """A DELIBERATELY SILENT server (accepts, never sends a byte): the
        exchange's recv must raise ``socket.timeout`` within the knob
        budget instead of hanging forever, and — raised from inside
        ``_host_p2p_exchange`` — the error must reach the existing
        ``_reset_host_links`` teardown."""
        import socket
        import threading
        import time as _time

        import jax

        import photon_ml_tpu.parallel.multihost as mh

        monkeypatch.setenv("PHOTON_P2P_TIMEOUT_S", "0.3")
        srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)
        accepted = []

        def accept_and_go_silent():
            conn, _ = srv.accept()
            accepted.append(conn)  # hold open, never send

        t = threading.Thread(target=accept_and_go_silent, daemon=True)
        t.start()
        recv_sock = socket.create_connection(srv.getsockname(), timeout=5.0)
        mh._configure_link_socket(recv_sock)  # the mesh's socket policy
        assert recv_sock.gettimeout() == 0.3

        class SendSock:
            closed = False

            def sendall(self, *_):
                pass

            def close(self):
                self.closed = True

        send_sock = SendSock()
        links = {"send": {1: send_sock}, "recv": {1: recv_sock}}
        monkeypatch.setattr(mh, "_HOST_LINKS", links)
        monkeypatch.setattr(mh, "_host_links", lambda: links)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(jax, "process_index", lambda: 0)
        arrays = {"v": np.arange(4, dtype=np.float32)}
        order = np.arange(4, dtype=np.int64)
        starts = np.asarray([0, 2, 4], np.int64)
        counts_matrix = np.asarray([[2, 2], [2, 2]], np.int64)
        t0 = _time.perf_counter()
        with pytest.raises((socket.timeout, TimeoutError)):
            mh._host_p2p_exchange(arrays, order, starts, counts_matrix)
        elapsed = _time.perf_counter() - t0
        assert elapsed < 30.0  # timed out, did not hang on the dead peer
        # the failure reached the reset path: mesh gone, sockets closed
        assert mh._HOST_LINKS is None
        assert send_sock.closed
        srv.close()
        for c in accepted:
            c.close()

    def test_reset_host_links_tolerates_empty(self):
        import photon_ml_tpu.parallel.multihost as mh

        before = mh._HOST_LINKS
        try:
            mh._HOST_LINKS = None
            mh._reset_host_links()  # no-op, no raise
            assert mh._HOST_LINKS is None
        finally:
            mh._HOST_LINKS = before

    def test_local_ip_explicit_override_wins(self, monkeypatch):
        import photon_ml_tpu.parallel.multihost as mh

        monkeypatch.setenv("PHOTON_EXCHANGE_HOST", "10.0.0.7")
        assert mh._local_ip() == "10.0.0.7"

    def test_local_ip_fails_fast_on_loopback_multiprocess(self, monkeypatch):
        """EVERY discovery source loopback + process_count > 1 +
        non-loopback coordinator: raise immediately (the 300 s
        alternative is every remote peer dialing itself). A single
        loopback probe result must NOT raise — later probes may still
        find the real NIC."""
        import jax

        import photon_ml_tpu.parallel.multihost as mh

        monkeypatch.delenv("PHOTON_EXCHANGE_HOST", raising=False)
        monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        # coordinator from JAX's distributed global state (no env var set)
        monkeypatch.setattr(mh, "_coordinator_address",
                            lambda: "10.1.2.3:1234")
        import socket as socket_mod

        probes = []

        class FakeUDP:
            def __init__(self, *a, **k):
                pass

            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False

            def connect(self, addr):
                probes.append(addr[0])
                # the docstring's own failure case: the hostname maps to
                # 127.0.1.1, so the probe toward the coordinator routes
                # locally — but so does everything else on this fake host
                if addr[0] == "10.1.2.3":
                    self._ip = "127.0.1.1"
                else:
                    self._ip = "127.0.0.1"

            def getsockname(self):
                return (self._ip, 33333)

        monkeypatch.setattr(socket_mod, "socket", FakeUDP)
        monkeypatch.setattr(
            socket_mod, "gethostbyname",
            lambda *_: (_ for _ in ()).throw(OSError("no resolver")),
        )
        with pytest.raises(RuntimeError, match="PHOTON_EXCHANGE_HOST"):
            mh._local_ip()
        # the coordinator probe coming up loopback did NOT abort the
        # sweep: the 8.8.8.8 probe was still tried before failing fast
        assert probes == ["10.1.2.3", "8.8.8.8"]

    def test_local_ip_allows_loopback_under_loopback_coordinator(
        self, monkeypatch
    ):
        """A loopback COORDINATOR proves a single-machine runtime (the
        multi-process test harness): loopback peers are dialable, no
        fail-fast."""
        import jax

        import photon_ml_tpu.parallel.multihost as mh

        monkeypatch.delenv("PHOTON_EXCHANGE_HOST", raising=False)
        monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "127.0.0.1:9999")
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        import socket as socket_mod

        class FakeUDP:
            def __init__(self, *a, **k):
                pass

            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False

            def connect(self, *_):
                pass

            def getsockname(self):
                return ("127.0.0.1", 33333)

        monkeypatch.setattr(socket_mod, "socket", FakeUDP)
        monkeypatch.setattr(
            socket_mod, "gethostbyname",
            lambda *_: (_ for _ in ()).throw(OSError("no resolver")),
        )
        assert mh._local_ip() == "127.0.0.1"

    def test_local_ip_keeps_probing_past_a_loopback_result(self, monkeypatch):
        """One loopback probe result is not an error: the 8.8.8.8 probe
        still runs and its non-loopback discovery wins."""
        import jax

        import photon_ml_tpu.parallel.multihost as mh

        monkeypatch.delenv("PHOTON_EXCHANGE_HOST", raising=False)
        monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "badhost:1234")
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        import socket as socket_mod

        class FakeUDP:
            def __init__(self, *a, **k):
                pass

            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False

            def connect(self, addr):
                self._ip = (
                    "127.0.1.1" if addr[0] == "badhost" else "10.0.0.5"
                )

            def getsockname(self):
                return (self._ip, 33333)

        monkeypatch.setattr(socket_mod, "socket", FakeUDP)
        assert mh._local_ip() == "10.0.0.5"

    def test_local_ip_allows_hostname_resolving_to_loopback(
        self, monkeypatch
    ):
        """The single-machine carve-out must RESOLVE a hostname
        coordinator: stock Debian/Ubuntu maps the machine's own hostname
        to 127.0.1.1, and a harness passing that hostname worked before
        the fail-fast existed — it must keep working."""
        import jax

        import photon_ml_tpu.parallel.multihost as mh

        monkeypatch.delenv("PHOTON_EXCHANGE_HOST", raising=False)
        monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "myhost:9999")
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        import socket as socket_mod

        class FakeUDP:
            def __init__(self, *a, **k):
                pass

            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False

            def connect(self, *_):
                pass

            def getsockname(self):
                return ("127.0.1.1", 33333)

        monkeypatch.setattr(socket_mod, "socket", FakeUDP)
        monkeypatch.setattr(
            socket_mod, "gethostbyname", lambda h: "127.0.1.1"
        )
        assert mh._local_ip() == "127.0.1.1"

    def test_coordinator_address_reads_jax_global_state(self, monkeypatch):
        import photon_ml_tpu.parallel.multihost as mh

        monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
        from jax._src import distributed as jdist

        monkeypatch.setattr(
            jdist.global_state, "coordinator_address", "10.9.8.7:4321",
            raising=False,
        )
        assert mh._coordinator_address() == "10.9.8.7:4321"
        # env var wins when set
        monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:1")
        assert mh._coordinator_address() == "10.0.0.1:1"


# -- entity-sharded random-effect solves (PHOTON_RE_SHARD) -------------------

_RE_SHARD_WORKER = textwrap.dedent(
    """
    import hashlib, json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    coordinator, pid, nproc, knob = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
    os.environ["PHOTON_RE_SHARD"] = knob
    # optional 5th arg: the sub-bucket placement knob (PHOTON_RE_SPLIT)
    os.environ["PHOTON_RE_SPLIT"] = sys.argv[5] if len(sys.argv) > 5 else "0"
    import jax
    jax.config.update("jax_platforms", "cpu")
    if nproc > 1:
        # the gloo CPU collectives client needs the distributed runtime;
        # a single-process reference run must keep the plain CPU client
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    import numpy as np

    if nproc > 1:
        from photon_ml_tpu.parallel.multihost import initialize_multihost
        initialize_multihost(coordinator, num_processes=nproc, process_id=pid)

    import jax.numpy as jnp
    from photon_ml_tpu.config import (
        GameTrainingConfig, OptimizationConfig, OptimizerConfig,
        RandomEffectCoordinateConfig, RegularizationContext,
    )
    from photon_ml_tpu.game.models import GameModel, RandomEffectModel
    from photon_ml_tpu.game.streaming import StreamedGameData, StreamedGameTrainer
    from photon_ml_tpu.types import (
        RegularizationType, TaskType, VarianceComputationType,
    )

    # Zipf-skewed entity traffic (R_re_skew-style): head entities carry
    # most rows, so naive modular/round-robin owners lose a shard to them
    rng = np.random.default_rng(42)
    E = 24
    sizes = np.maximum((80.0 / (1 + np.arange(E)) ** 1.1).astype(int), 3)
    ids = np.repeat(np.arange(E), sizes).astype(np.int64)
    ids = ids[rng.permutation(len(ids))]
    n = len(ids)
    X = rng.normal(size=(n, 3)).astype(np.float32)
    W_true = (rng.normal(size=(E, 3)) * 0.5).astype(np.float32)
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(
        -np.sum(W_true[ids] * X, axis=1)))).astype(np.float32)
    # warm start + incremental MAP prior: the acceptance criterion covers
    # variances AND priors through the sharded path
    W0 = (rng.normal(size=(E, 3)) * 0.1).astype(np.float32)
    V0 = (0.5 + rng.uniform(size=(E, 3))).astype(np.float32)

    opt = OptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=8, tolerance=1e-9),
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    cfg = GameTrainingConfig(
        task_type=TaskType.LOGISTIC_REGRESSION,
        coordinate_update_sequence=("per_entity",),
        coordinate_descent_iterations=2,
        fixed_effect_coordinates={},
        random_effect_coordinates={
            "per_entity": RandomEffectCoordinateConfig(
                random_effect_type="eid", feature_shard_id="r",
                optimization=opt,
            )
        },
        variance_computation=VarianceComputationType.SIMPLE,
        incremental=True,
    )
    warm = GameModel(
        models={
            "per_entity": RandomEffectModel(
                coefficients=jnp.asarray(W0), variances=jnp.asarray(V0),
                random_effect_type="eid", feature_shard_id="r",
                task_type=cfg.task_type,
            )
        },
        task_type=cfg.task_type,
    )
    # validation rows: a deterministic tail draw over the SAME entity
    # dictionary, plus unseen-entity sentinels — exercises the
    # validation re-shard's reuse of the TRAINING owner layout (scoring
    # re_W rows through a re-planned validation layout was the review
    # bug) and the grouped owner-routed metric path
    vrng = np.random.default_rng(7)
    n_val = 60
    val_ids = vrng.integers(0, E, size=n_val).astype(np.int64)
    val_ids[::15] = -1  # unseen-entity sentinel rows
    val_X = vrng.normal(size=(n_val, 3)).astype(np.float32)
    val_y = (vrng.uniform(size=n_val) < 0.5).astype(np.float32)
    if nproc > 1:
        bounds = np.linspace(0, n, nproc + 1).astype(int)
        lo, hi = bounds[pid], bounds[pid + 1]
        vbounds = np.linspace(0, n_val, nproc + 1).astype(int)
        vlo, vhi = vbounds[pid], vbounds[pid + 1]
    else:
        lo, hi = 0, n
        vlo, vhi = 0, n_val
    data = StreamedGameData(
        labels=y[lo:hi], features={"r": X[lo:hi]},
        id_tags={"eid": ids[lo:hi]},
    )
    validation = StreamedGameData(
        labels=val_y[vlo:vhi], features={"r": val_X[vlo:vhi]},
        id_tags={"eid": val_ids[vlo:vhi]},
    )
    trainer = StreamedGameTrainer(
        cfg, chunk_rows=1 << 16, multihost=nproc > 1,
        evaluators=("AUC", "MULTI_AUC(eid)"),
    )
    model, info = trainer.fit(data, validation=validation, initial_model=warm)
    val_metrics = [
        {k: v.metrics for k, v in h.items()}
        for h in trainer.validation_history
    ]
    W = np.asarray(model.models["per_entity"].coefficients, np.float64)
    V = np.asarray(model.models["per_entity"].variances, np.float64)

    # in-memory owned-bucket leg: train_random_effects under a mesh with
    # the SAME knob — whole buckets solve on one owner each, results
    # combine across processes; must equal the unsharded solve bitwise
    from photon_ml_tpu.config import OptimizerConfig as _OC
    from photon_ml_tpu.game import bucket_entities, group_by_entity
    from photon_ml_tpu.game.data import DenseFeatures
    from photon_ml_tpu.game.random_effect import train_random_effects
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.parallel import data_mesh

    mem_kwargs = dict(
        features=DenseFeatures(X=jnp.asarray(X)),
        labels=y,
        offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32),
        buckets=bucket_entities(group_by_entity(ids, num_entities=E)),
        num_entities=E,
        loss=loss_for_task(cfg.task_type),
        config=_OC(max_iterations=6, tolerance=1e-9),
        l2_weight=1.0,
        initial_coefficients=jnp.asarray(W0),
        variance_computation=VarianceComputationType.SIMPLE,
        prior_coefficients=jnp.asarray(W0),
        prior_variances=jnp.asarray(V0),
    )
    # knob on: the owned-bucket sharded schedule under the global mesh;
    # knob off / single process: the plain unsharded solve (the
    # reference anchor) — the legacy LANE-sharded mesh path is not
    # exercised here (it has no cross-process bitwise contract)
    mem = train_random_effects(
        mesh=data_mesh() if (nproc > 1 and knob == "1") else None,
        **mem_kwargs
    )
    W_mem = np.asarray(jax.device_get(mem.coefficients), np.float64)
    V_mem = np.asarray(jax.device_get(mem.variances), np.float64)
    it_mem = np.asarray(mem.iterations, np.int64)

    # satellite: repeated identical-shape exchanges reuse ONE executable
    from photon_ml_tpu.parallel import multihost as mh
    a2a_growth = None
    if nproc > 1:
        probe = {"v": np.arange(8, dtype=np.float32)}
        dest = np.arange(8, dtype=np.int64) % nproc  # balanced -> all_to_all
        mh.exchange_rows(probe, dest)
        before = mh._a2a_cache_size()
        mh.exchange_rows(probe, dest)
        mh.exchange_rows(probe, dest)
        a2a_growth = mh._a2a_cache_size() - before

    from photon_ml_tpu.obs.metrics import REGISTRY
    snap = REGISTRY.snapshot()
    gauges = {
        k: v for k, v in snap.get("gauges", {}).items()
        if k.startswith("re_shard.")
    }
    launches = snap.get("counters", {}).get(
        "re_solve.launches", {}
    ).get("value", 0.0)
    print("RESULT " + json.dumps({
        "pid": pid, "knob": knob,
        "W": W.tolist(), "V": V.tolist(),
        "W_mem": W_mem.tolist(), "V_mem": V_mem.tolist(),
        "it_mem": it_mem.tolist(),
        "val_metrics": val_metrics,
        "gauges": gauges,
        "launches": launches,
        "a2a_growth": a2a_growth,
        "last_transport": mh.LAST_EXCHANGE_STATS.get("transport"),
    }))
    """
)


def _run_re_shard_workers(nproc: int, knob: str, split: str = "0") -> dict:
    coordinator = f"127.0.0.1:{_free_port()}"
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
    }
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _RE_SHARD_WORKER, coordinator,
             str(pid), str(nproc), knob, split],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for pid in range(nproc)
    ]
    results = {}
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, f"worker failed:\n{out}\n{err[-4000:]}"
        for line in out.splitlines():
            if line.startswith("RESULT "):
                r = json.loads(line[len("RESULT "):])
                results[r["pid"]] = r
    assert set(results) == set(range(nproc))
    return results


@pytest.mark.slow
def test_entity_sharded_re_solve_bitwise_matches_single_process(tmp_path):
    """PHOTON_RE_SHARD=1 on 2 AND 4 processes (loopback coordinator):
    the streamed random-effect solve — including SIMPLE variances, a
    warm start and an incremental MAP prior — and the in-memory
    owned-bucket solve are BITWISE identical (assert_array_equal, not
    allclose) to the single-process solve on a Zipf-skewed entity
    distribution. The skew-aware placement gauges and the
    exchange-overlap ratio ride the registry on every process, and
    repeated identical-shape exchanges reuse one all_to_all executable
    (zero jit-cache growth)."""
    ref = _run_re_shard_workers(1, "0")[0]
    for nproc in (2, 4):
        got = _run_re_shard_workers(nproc, "1")
        for pid, r in got.items():
            tag = f"nproc={nproc} pid={pid}"
            np.testing.assert_array_equal(
                np.asarray(r["W"]), np.asarray(ref["W"]), err_msg=tag
            )
            np.testing.assert_array_equal(
                np.asarray(r["V"]), np.asarray(ref["V"]), err_msg=tag
            )
            np.testing.assert_array_equal(
                np.asarray(r["W_mem"]), np.asarray(ref["W_mem"]),
                err_msg=tag,
            )
            np.testing.assert_array_equal(
                np.asarray(r["V_mem"]), np.asarray(ref["V_mem"]),
                err_msg=tag,
            )
            np.testing.assert_array_equal(
                np.asarray(r["it_mem"]), np.asarray(ref["it_mem"]),
                err_msg=tag,
            )
            # per-visit validation through the TRAINING owner layout:
            # grouped per-entity AUC partials are exact sums over
            # complete owner-side groups (float order drift only);
            # scalar AUC rides the sharded histogram recipe (<~1e-4
            # off the single-process exact sort)
            assert len(r["val_metrics"]) == len(ref["val_metrics"])
            for got_h, ref_h in zip(r["val_metrics"], ref["val_metrics"]):
                for coord, m_ref in ref_h.items():
                    m_got = got_h[coord]
                    np.testing.assert_allclose(
                        m_got["MULTI_AUC(eid)"], m_ref["MULTI_AUC(eid)"],
                        rtol=1e-6, err_msg=tag,
                    )
                    np.testing.assert_allclose(
                        m_got["AUC"], m_ref["AUC"], atol=2e-4,
                        err_msg=tag,
                    )
            # placement + overlap instruments present on every process
            assert r["gauges"].get("re_shard.shards") == float(nproc), r["gauges"]
            assert "re_shard.exchange_overlap_ratio" in r["gauges"], tag
            assert r["gauges"].get("re_shard.balance", 99.0) <= 1.5, r["gauges"]
            # identical-shape exchange reuse: no executable-cache growth
            assert r["a2a_growth"] == 0, tag
    # sub-bucket placement atoms (PHOTON_RE_SPLIT): the streamed owner
    # map and the in-memory owned-bucket prep both place by the atom
    # ladder — still BITWISE the single-process unsplit solve, with the
    # placement gauges recording the finer granularity
    got = _run_re_shard_workers(2, "1", split="12")
    for pid, r in got.items():
        tag = f"split nproc=2 pid={pid}"
        for field in ("W", "V", "W_mem", "V_mem", "it_mem"):
            np.testing.assert_array_equal(
                np.asarray(r[field]), np.asarray(ref[field]), err_msg=tag
            )
        assert r["gauges"].get("re_shard.split_classes", 0.0) >= 1.0, (
            r["gauges"]
        )
        assert r["gauges"]["re_shard.atoms"] > 2.0, r["gauges"]


@pytest.mark.slow
def test_entity_shard_knob_off_keeps_legacy_schedule(tmp_path):
    """PHOTON_RE_SHARD=0 on 2 processes: the legacy modular owner rule and
    blocking exchange schedule — no placement gauges, no async transport,
    and the same per-process launch counter the pre-sharding code
    produced (one launch per owned bucket per visit)."""
    got = _run_re_shard_workers(2, "0")
    for pid, r in got.items():
        assert not any(
            k.startswith("re_shard.") for k in r["gauges"]
        ), r["gauges"]
        assert r["last_transport"] in ("all_to_all", "p2p_host"), r
        assert r["launches"] > 0


class TestExchangeExecutableReuse:
    """Satellite: repeated coordinate-descent exchanges with identical
    shapes must reuse ONE all_to_all executable (audit finding asserted
    as a cache-growth tripwire, the test_streaming idiom)."""

    def test_a2a_jit_cache_growth_only_on_new_shapes(self):
        import jax
        import jax.numpy as jnp
        from jax.experimental import multihost_utils as mhu
        from jax.sharding import PartitionSpec as P

        import photon_ml_tpu.parallel.multihost as mh

        mesh = mh._process_mesh()  # 1-process mesh in tier-1

        def call(shape):
            local = np.zeros(shape, np.float32)
            g = mhu.host_local_array_to_global_array(local, mesh, P("proc"))
            return np.asarray(
                mhu.global_array_to_host_local_array(
                    mh._all_to_all_jit()(g), mesh, P("proc")
                )
            )

        call((1, 4))
        size_after_first = mh._a2a_cache_size()
        assert size_after_first >= 1
        call((1, 4))
        call((1, 4))
        assert mh._a2a_cache_size() == size_after_first  # reuse, no growth
        call((1, 8))  # a genuinely new shape compiles exactly one more
        assert mh._a2a_cache_size() == size_after_first + 1

    def test_framed_p2p_row_count_validation(self):
        """The collective-free framing mode rejects frames that are not a
        whole number of rows (a mis-framed stream must fail loudly, not
        reshape garbage)."""
        import struct

        import photon_ml_tpu.parallel.multihost as mh

        class FrameSock:
            def __init__(self, frames):
                self.buf = b"".join(
                    struct.pack("!q", len(f)) + f for f in frames
                )

            def recv(self, n):
                out, self.buf = self.buf[:n], self.buf[n:]
                return out

            def sendall(self, *_):
                pass

            def close(self):
                pass

        import jax

        import pytest as _pytest

        links = {
            "send": {1: FrameSock([])},
            # 6 bytes is not a multiple of the 4-byte f32 row
            "recv": {1: FrameSock([b"\x00" * 6])},
        }
        orig_links, mh._HOST_LINKS = mh._HOST_LINKS, links
        orig_count = jax.process_count
        orig_index = jax.process_index
        jax.process_count = lambda: 2
        jax.process_index = lambda: 0
        try:
            arrays = {"v": np.arange(4, dtype=np.float32)}
            order = np.arange(4, dtype=np.int64)
            starts = np.asarray([0, 2, 4], np.int64)
            with _pytest.raises(RuntimeError, match="not a multiple"):
                mh._host_p2p_exchange(arrays, order, starts, None)
            assert mh._HOST_LINKS is None  # error tore the mesh down
        finally:
            jax.process_count = orig_count
            jax.process_index = orig_index
            mh._HOST_LINKS = orig_links


class TestBarrierTagSuffix:
    """Satellite: every ``sync_processes`` call gets a monotonic ``#n``
    suffix, so two overlapping barriers with the same caller tag cannot
    alias across the pipelined exchange schedule."""

    def test_suffix_is_per_call_monotonic(self, monkeypatch):
        import jax
        from jax.experimental import multihost_utils

        import photon_ml_tpu.parallel.multihost as mh

        seen = []
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(
            multihost_utils, "sync_global_devices", seen.append
        )
        mh.sync_processes("ckpt")
        mh.sync_processes("ckpt")
        mh.sync_processes("other")
        assert len(seen) == 3 and len(set(seen)) == 3
        bases = [t.rsplit("#", 1)[0] for t in seen]
        seqs = [int(t.rsplit("#", 1)[1]) for t in seen]
        assert bases == ["ckpt", "ckpt", "other"]
        assert seqs == sorted(seqs) and len(set(seqs)) == 3

    def test_single_process_is_noop(self):
        from photon_ml_tpu.parallel.multihost import sync_processes

        sync_processes("anything")  # must not touch collectives


class TestInitializeHeartbeatKnob:
    """``PHOTON_COORD_MAX_MISSING_HEARTBEATS`` reaches
    ``jax.distributed.initialize`` as ``heartbeat_timeout_seconds`` (10 s a
    heartbeat) — the keyword the installed jax takes; the older
    ``*_max_missing_heartbeats`` pair raised ``TypeError`` before any
    process joined."""

    def _init(self, monkeypatch, knob):
        import jax

        import photon_ml_tpu.parallel.multihost as mh

        seen = {}
        monkeypatch.setattr(
            jax.distributed, "initialize", lambda **kw: seen.update(kw)
        )
        if knob is None:
            monkeypatch.delenv(
                "PHOTON_COORD_MAX_MISSING_HEARTBEATS", raising=False
            )
        else:
            monkeypatch.setenv("PHOTON_COORD_MAX_MISSING_HEARTBEATS", knob)
        summary = mh.initialize_multihost("127.0.0.1:1", 1, 0)
        assert summary["process_count"] == 1
        return seen

    def test_knob_maps_to_heartbeat_timeout_seconds(self, monkeypatch):
        import inspect

        import jax

        seen = self._init(monkeypatch, "360")
        assert seen["heartbeat_timeout_seconds"] == 3600
        assert seen["coordinator_address"] == "127.0.0.1:1"
        # every keyword passed is one the installed initialize() accepts
        monkeypatch.undo()
        accepted = inspect.signature(jax.distributed.initialize).parameters
        assert set(seen) <= set(accepted)

    def test_knob_absent_keeps_jax_default(self, monkeypatch):
        assert "heartbeat_timeout_seconds" not in self._init(monkeypatch, None)

    def test_typo_names_the_knob_value(self, monkeypatch):
        with pytest.raises(ValueError, match="3x"):
            self._init(monkeypatch, "3x")


class TestAsyncExchangeSingleProcess:
    """The overlapped-exchange surface on one process: identity value,
    memoized result, and the overlap-ratio gauge present."""

    def test_identity_handle_and_overlap_gauge(self):
        from photon_ml_tpu.obs.metrics import REGISTRY
        from photon_ml_tpu.parallel.multihost import exchange_rows_async

        arrays = {"off": np.arange(6, dtype=np.float32)}
        handle = exchange_rows_async(arrays, np.zeros(6, np.int64))
        out = handle.result()
        np.testing.assert_array_equal(out["off"], arrays["off"])
        assert handle.result() is out  # memoized
        g = REGISTRY.snapshot("re_shard.")["gauges"]
        assert "re_shard.exchange_overlap_ratio" in g
        assert 0.0 <= g["re_shard.exchange_overlap_ratio"] <= 1.0


class TestP2PTelemetry:
    """Unmarked host-side tests for the per-link telemetry the framed
    exchange emits: correlated send/recv events (both ends derive the
    same id from the submission-order frame-set counters), the blocked-
    recv heartbeat, and the no-sink fast path staying event-free."""

    def _sink(self, tmp_path):
        import photon_ml_tpu.obs as obs

        return obs.configure(str(tmp_path / "tel"), run_id="p2p")

    def _records(self, path):
        import photon_ml_tpu.obs as obs
        from photon_ml_tpu.obs.report import load_run

        obs.shutdown()
        return load_run(path)

    def test_framed_exchange_emits_correlated_link_events(
        self, tmp_path, monkeypatch
    ):
        import struct

        import jax

        import photon_ml_tpu.obs as obs
        import photon_ml_tpu.parallel.multihost as mh

        class FrameSock:
            def __init__(self, frames):
                self.buf = b"".join(
                    struct.pack("!q", len(f)) + f for f in frames
                )

            def recv(self, n):
                out, self.buf = self.buf[:n], self.buf[n:]
                return out

            def fileno(self):  # select() in the heartbeat path
                raise AssertionError(
                    "heartbeat path must not engage when data is ready"
                )

            def sendall(self, *_):
                pass

            def close(self):
                pass

        path = self._sink(tmp_path)
        # peer 1 sends 2 f32 rows (8 bytes) in framed mode
        links = {
            "send": {1: FrameSock([])},
            "recv": {1: FrameSock([np.arange(2, dtype=np.float32)
                                   .tobytes()])},
        }
        monkeypatch.setattr(mh, "_HOST_LINKS", links)
        monkeypatch.setattr(mh, "_host_links", lambda: links)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(jax, "process_index", lambda: 0)
        monkeypatch.setattr(
            mh, "_LINK_SEQ", {"send": {}, "recv": {}}
        )
        # heartbeat would need select(); frames are pre-buffered, so
        # disable it — the plain recv path must emit the same events
        monkeypatch.setenv("PHOTON_P2P_HEARTBEAT_S", "0")
        try:
            arrays = {"v": np.arange(4, dtype=np.float32)}
            order = np.arange(4, dtype=np.int64)
            starts = np.asarray([0, 2, 4], np.int64)
            out = mh._host_p2p_exchange(
                arrays, order, starts, None, tag="offsets"
            )
            # own rows (order[0:2]) then peer 1's 2-row frame
            np.testing.assert_array_equal(
                out["v"],
                np.concatenate([arrays["v"][:2], [0.0, 1.0]]),
            )
        finally:
            records = self._records(path)
        sends = [r for r in records if r["event"] == "p2p_send"]
        recvs = [r for r in records if r["event"] == "p2p_recv"]
        assert len(sends) == 1 and len(recvs) == 1
        # this end's send to peer 1 is frame-set #1 of link 0->1; its
        # recv from peer 1 is frame-set #1 of link 1->0 — the ids peer
        # 1's shard derives for the SAME frame-sets, so a fleet report
        # joins them with zero unmatched pairs
        assert sends[0]["corr"] == "p2p:0>1#1"
        assert recvs[0]["corr"] == "p2p:1>0#1"
        for r in sends + recvs:
            assert r["tag"] == "offsets"
            assert r["bytes"] == 8 and r["rows"] == 2
            assert "t_start" in r and "dur_s" in r

    def test_link_seq_advances_and_resets_with_mesh(self, monkeypatch):
        import photon_ml_tpu.parallel.multihost as mh

        monkeypatch.setattr(
            mh, "_LINK_SEQ", {"send": {}, "recv": {}}
        )
        assert mh._next_link_seq("send", 1) == 1
        assert mh._next_link_seq("send", 1) == 2
        assert mh._next_link_seq("recv", 1) == 1
        assert mh._next_link_seq("send", 2) == 1
        monkeypatch.setattr(mh, "_HOST_LINKS", None)
        mh._reset_host_links()
        assert mh._LINK_SEQ == {"send": {}, "recv": {}}

    def test_heartbeat_surfaces_blocked_recv_before_timeout(
        self, tmp_path, monkeypatch
    ):
        """A silent peer: the framed recv emits rate-limited heartbeat
        events while blocked, then raises within the knob budget."""
        import socket

        import photon_ml_tpu.parallel.multihost as mh

        monkeypatch.setenv("PHOTON_P2P_TIMEOUT_S", "0.25")
        path = self._sink(tmp_path)
        a, b = socket.socketpair()
        try:
            with pytest.raises((socket.timeout, TimeoutError)):
                mh._recv_exact(a, 8, peer=1, tag="scores",
                               heartbeat=0.05)
        finally:
            records = self._records(path)
            a.close()
            b.close()
        beats = [r for r in records if r["event"] == "p2p_heartbeat"]
        # ~0.25s budget at 0.05s cadence: several beats, each naming
        # the silent peer and the blocked wall so far
        assert len(beats) >= 2
        assert all(r["peer"] == 1 and r["tag"] == "scores"
                   for r in beats)
        assert beats[-1]["blocked_s"] >= beats[0]["blocked_s"]
        assert all(r["bytes_remaining"] == 8 for r in beats)

    def test_heartbeat_path_preserves_payload(self, tmp_path):
        """Bytes that arrive while the heartbeat loop polls are
        reassembled exactly (the telemetry path must not reframe)."""
        import socket
        import threading
        import time

        import photon_ml_tpu.obs as obs
        import photon_ml_tpu.parallel.multihost as mh

        path = obs.configure(str(tmp_path / "tel2"), run_id="hb2")
        a, b = socket.socketpair()
        payload = bytes(range(64)) * 4

        def drip():
            for i in range(0, len(payload), 32):
                time.sleep(0.02)
                b.sendall(payload[i:i + 32])

        t = threading.Thread(target=drip)
        t.start()
        try:
            got = mh._recv_exact(a, len(payload), peer=1, tag="x",
                                 heartbeat=0.05)
        finally:
            t.join()
            obs.shutdown()
            a.close()
            b.close()
        assert got == payload

    def test_no_sink_no_events_and_plain_recv(self, monkeypatch):
        """Without a sink the exchange stays on the pre-telemetry recv
        path (no readiness polling, no events) — the hot path is
        byte-identical: the exchange snapshots heartbeat=None once when
        no sink is active, and ``_recv_exact`` with heartbeat=None
        never touches the socket's fd."""
        import photon_ml_tpu.obs as obs
        import photon_ml_tpu.parallel.multihost as mh

        obs.shutdown()
        assert not mh._sink_active()

        class PlainSock:
            def __init__(self, data):
                self.data = data

            def recv(self, n):
                out, self.data = self.data[:n], self.data[n:]
                return out

            def fileno(self):
                raise AssertionError("no-sink recv must not poll fds")

        monkeypatch.setenv("PHOTON_P2P_HEARTBEAT_S", "5")
        assert mh._recv_exact(PlainSock(b"abcd"), 4, peer=1) == b"abcd"


_FLEET_WORKER = textwrap.dedent(
    """
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    os.environ["PHOTON_RE_SHARD"] = "1"
    coordinator, pid, teldir = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    import numpy as np

    from photon_ml_tpu.parallel.multihost import initialize_multihost
    initialize_multihost(coordinator, num_processes=2, process_id=pid)

    import photon_ml_tpu.obs as obs
    # NO run_id: every process must agree through the fleet run-id
    # broadcast, and processes 1..N-1 must write .p<k> shards
    run_path = obs.configure(teldir)

    from photon_ml_tpu.config import (
        GameTrainingConfig, OptimizationConfig, OptimizerConfig,
        RandomEffectCoordinateConfig, RegularizationContext,
    )
    from photon_ml_tpu.game.streaming import (
        StreamedGameData, StreamedGameTrainer,
    )
    from photon_ml_tpu.types import RegularizationType, TaskType

    rng = np.random.default_rng(42)
    E = 16
    sizes = np.maximum((60.0 / (1 + np.arange(E)) ** 1.1).astype(int), 3)
    ids = np.repeat(np.arange(E), sizes).astype(np.int64)
    ids = ids[rng.permutation(len(ids))]
    n = len(ids)
    X = rng.normal(size=(n, 3)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    bounds = np.linspace(0, n, 3).astype(int)
    lo, hi = bounds[pid], bounds[pid + 1]
    opt = OptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=8, tolerance=1e-8),
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    cfg = GameTrainingConfig(
        task_type=TaskType.LOGISTIC_REGRESSION,
        coordinate_update_sequence=("per_entity",),
        coordinate_descent_iterations=2,
        fixed_effect_coordinates={},
        random_effect_coordinates={
            "per_entity": RandomEffectCoordinateConfig(
                random_effect_type="eid", feature_shard_id="r",
                optimization=opt,
            )
        },
    )
    data = StreamedGameData(
        labels=y[lo:hi], features={"r": X[lo:hi]},
        id_tags={"eid": ids[lo:hi]},
    )
    trainer = StreamedGameTrainer(cfg, chunk_rows=1 << 16, multihost=True)
    model, info = trainer.fit(data)
    obs.shutdown()
    print("RESULT " + json.dumps({"pid": pid, "run_path": run_path}))
    """
)


@pytest.mark.slow
def test_fleet_telemetry_two_process_shards_and_report(tmp_path):
    """Fleet-sink acceptance on the 2-process gloo harness: every
    process writes a parseable, schema-valid shard of ONE run (run id
    agreed through the broadcast), the correlated send/recv events of
    the framed exchanges join with ZERO unmatched pairs on a clean run,
    `report fleet` renders the per-process phase-wall and per-link P2P
    tables, and `report gate --fleet` passes against a freshly written
    fleet baseline."""
    teldir = tmp_path / "tel"
    coordinator = f"127.0.0.1:{_free_port()}"
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
    }
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _FLEET_WORKER, coordinator, str(pid),
             str(teldir)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for pid in range(2)
    ]
    results = {}
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, f"worker failed:\n{out}\n{err[-4000:]}"
        for line in out.splitlines():
            if line.startswith("RESULT "):
                r = json.loads(line[len("RESULT "):])
                results[r["pid"]] = r
    assert set(results) == {0, 1}
    # one run id across processes; process 0 canonical, process 1 shard
    p0, p1 = results[0]["run_path"], results[1]["run_path"]
    assert p0.endswith(".jsonl") and not p0.endswith(".p1.jsonl")
    assert p1.endswith(".p1.jsonl")
    assert os.path.basename(p1) == (
        os.path.basename(p0)[:-len(".jsonl")] + ".p1.jsonl"
    )

    from photon_ml_tpu.obs.report import (
        fleet_run_paths,
        format_fleet,
        load_run,
        summarize_fleet,
        validate_run,
    )

    paths = fleet_run_paths(str(teldir))
    assert [os.path.basename(p) for p in paths] == [
        os.path.basename(p0), os.path.basename(p1)
    ]
    for p in paths:  # every shard parseable + schema-valid
        assert validate_run(load_run(p)) == []
    fs = summarize_fleet(paths)
    assert fs["process_count"] == 2 and fs["missing_shards"] == 0
    # clean run: every correlated send/recv pair joins
    assert fs["p2p"]["matched"] > 0
    assert fs["p2p"]["unmatched"] == 0, fs["p2p"]
    assert set(fs["p2p"]["links"]) == {"0->1", "1->0"}
    # per-process phase walls + the overlap gauge from BOTH processes
    assert set(fs["overlap"]) == {"0", "1"}
    for agg in fs["phases"].values():
        assert set(agg["per_process"]) == {"0", "1"}
    text = format_fleet(fs)
    assert "0 unmatched" in text and "0->1" in text

    # gate the merged fleet view against a freshly written baseline
    from photon_ml_tpu.cli import report as cli_report

    base = tmp_path / "fleet-base.json"

    def run_cli(argv):
        try:
            cli_report.main(argv)
        except SystemExit as e:
            return int(e.code or 0)
        return 0

    assert run_cli(["gate", "--fleet", p0,
                    "--write-baseline", str(base)]) == 0
    assert run_cli(["gate", "--fleet", p0, "--baseline", str(base)]) == 0
    assert run_cli(["fleet", str(teldir)]) == 0


# -- chaos drills: deterministic fault plans through the real 2-proc mesh ----

_CHAOS_WORKER = textwrap.dedent(
    """
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    coordinator, pid, nproc = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    mode = json.loads(sys.argv[4])
    rejoin_boot = bool(os.environ.get("PHOTON_REJOIN_BOOT"))
    if nproc > 1:
        os.environ["PHOTON_RE_SHARD"] = "1"
        os.environ.setdefault("PHOTON_P2P_CRC", "1")
        os.environ.setdefault("PHOTON_P2P_RETRIES", "6")
        os.environ.setdefault("PHOTON_P2P_BACKOFF_S", "0.1")
        os.environ.setdefault("PHOTON_P2P_TIMEOUT_S", "3")
        os.environ.setdefault("PHOTON_ROLLCALL_WINDOW_S", "1.5")
        # the repo's roll-call tier, not the jax coordination service,
        # decides who is dead in these drills — without this the
        # service FATALs every survivor ~100 s after a kill
        os.environ.setdefault("PHOTON_COORD_MAX_MISSING_HEARTBEATS", "360")
    if mode.get("rejoin"):
        os.environ["PHOTON_REJOIN"] = "1"
        os.environ.setdefault(
            "PHOTON_REJOIN_WINDOW_S", str(mode.get("rejoin_window", 25))
        )
        os.environ["PHOTON_MESH_CACHE"] = mode["mesh_cache"]
        # >2 survivors exhaust their retry budgets at desynced times:
        # compress the budget (fast detection) and widen the roll-call
        # patience window past the entry spread
        os.environ["PHOTON_P2P_RETRIES"] = "3"
        os.environ["PHOTON_P2P_TIMEOUT_S"] = "2"
        os.environ["PHOTON_ROLLCALL_WINDOW_S"] = "6"
    if mode.get("fault_plan") and not rejoin_boot:
        os.environ["PHOTON_FAULT_PLAN"] = json.dumps(mode["fault_plan"])
    import jax
    jax.config.update("jax_platforms", "cpu")
    if nproc > 1 and not rejoin_boot:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    import numpy as np

    if rejoin_boot:
        # a re-exec'd process cannot re-enter the original
        # jax.distributed cohort: adopt the ORIGINAL identity from the
        # persisted mesh cache and wait to be invited back instead
        from photon_ml_tpu.parallel.multihost import bootstrap_rejoin
        bootstrap_rejoin()
    elif nproc > 1:
        from photon_ml_tpu.parallel.multihost import initialize_multihost
        initialize_multihost(coordinator, num_processes=nproc, process_id=pid)

    run_path = None
    if mode.get("telemetry_dir"):
        import photon_ml_tpu.obs as obs
        run_path = obs.configure(
            mode["telemetry_dir"], run_id=mode.get("run_id")
        )

    from photon_ml_tpu.config import (
        GameTrainingConfig, OptimizationConfig, OptimizerConfig,
        RandomEffectCoordinateConfig, RegularizationContext,
    )
    from photon_ml_tpu.game.streaming import (
        StreamedGameData, StreamedGameTrainer,
    )
    from photon_ml_tpu.types import (
        RegularizationType, TaskType, VarianceComputationType,
    )

    # UNIFORM entity sizes: the ingest exchange stays balanced, so it
    # rides the all_to_all transport and the framed-P2P link seq
    # ordinals are exactly (offsets=1, scores=2) per visit — what the
    # committed fault plans are written against
    rng = np.random.default_rng(42)
    E = 12
    ids = np.repeat(np.arange(E), 6).astype(np.int64)
    ids = ids[rng.permutation(len(ids))]
    n = len(ids)
    X = rng.normal(size=(n, 3)).astype(np.float32)
    W_true = (rng.normal(size=(E, 3)) * 0.5).astype(np.float32)
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(
        -np.sum(W_true[ids] * X, axis=1)))).astype(np.float32)
    half = n // 2
    if nproc > 1:
        # even per-pid split (identical to the historical (0, half) /
        # (half, n) carve at nproc=2, which the committed fault plans'
        # frame-set ordinals were written against)
        per = n // nproc
        lo = pid * per
        hi = (pid + 1) * per if pid < nproc - 1 else n
    else:
        # single-process arms run over PROCESS 0's slice — the
        # degraded-parity contract covers the surviving data
        lo, hi = 0, half
    opt = OptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=6, tolerance=1e-9),
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    cfg = GameTrainingConfig(
        task_type=TaskType.LOGISTIC_REGRESSION,
        coordinate_update_sequence=("per_entity",),
        coordinate_descent_iterations=mode.get("iterations", 2),
        fixed_effect_coordinates={},
        random_effect_coordinates={
            "per_entity": RandomEffectCoordinateConfig(
                random_effect_type="eid", feature_shard_id="r",
                optimization=opt,
            )
        },
        variance_computation=VarianceComputationType.SIMPLE,
    )
    data = StreamedGameData(
        labels=y[lo:hi], features={"r": X[lo:hi]},
        id_tags={"eid": ids[lo:hi]},
    )
    trainer = StreamedGameTrainer(
        cfg, chunk_rows=1 << 16, multihost=nproc > 1,
        checkpoint_dir=mode.get("checkpoint_dir"),
        num_entities={"eid": E},
        sharded_checkpoints=False,
    )
    if mode.get("resume_fingerprint_from"):
        from photon_ml_tpu.checkpoint import peek_fingerprint

        fp = peek_fingerprint(mode["resume_fingerprint_from"])
        assert fp is not None, mode["resume_fingerprint_from"]
        trainer.resume_fingerprints = [fp]
        trainer.resume_row_base = int(mode.get("resume_row_base", 0))
    model, info = trainer.fit(data)
    if run_path is not None:
        obs.shutdown()
    from photon_ml_tpu.obs.metrics import REGISTRY
    snap = REGISTRY.snapshot()
    counters = {
        k: v.get("value", 0.0)
        for k, v in snap.get("counters", {}).items()
        if k.startswith(("p2p.", "fleet."))
    }
    W = np.asarray(model.models["per_entity"].coefficients, np.float64)
    V = np.asarray(model.models["per_entity"].variances, np.float64)
    print("RESULT " + json.dumps({
        "pid": pid,
        "W": W.tolist(), "V": V.tolist(),
        "resumed_from": trainer.resumed_from,
        "counters": counters,
        "run_path": run_path,
    }), flush=True)
    # a degraded survivor must not hang in the distributed runtime's
    # shutdown handshake with a dead peer
    sys.stdout.flush()
    os._exit(0)
    """
)


def _run_chaos_workers(
    nproc: int, modes: dict, allow_kill=(), worker=None
) -> dict:
    """``modes``: pid -> mode dict (JSON-serializable). ``allow_kill``:
    pids whose hard exit (fault-plan ``kill``/``rejoin``) is expected —
    their output is still parsed, because a ``rejoin``-relaunched child
    inherits the dead worker's stdout pipe and prints its own RESULT
    line there. Every worker gets ``PHOTON_REJOIN_CMD`` (its own argv),
    so a ``rejoin`` fault spec can re-exec it without extra plumbing."""
    coordinator = f"127.0.0.1:{_free_port()}"
    script = worker if worker is not None else _CHAOS_WORKER
    base_env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
    }
    cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = {}
    for pid in range(nproc):
        argv = [sys.executable, "-c", script, coordinator, str(pid),
                str(nproc), json.dumps(modes.get(pid, modes.get(0, {})))]
        env = dict(base_env)
        env["PHOTON_REJOIN_CMD"] = json.dumps(argv)
        procs[pid] = subprocess.Popen(
            argv, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env, cwd=cwd,
        )
    results = {}
    for pid, p in procs.items():
        out, err = p.communicate(timeout=600)
        if pid not in allow_kill:
            assert p.returncode == 0, (
                f"worker {pid} failed (rc {p.returncode}):"
                f"\n{out}\n{err[-6000:]}"
            )
        for line in out.splitlines():
            if line.startswith("RESULT "):
                results[pid] = json.loads(line[len("RESULT "):])
    return results


@pytest.mark.slow
@pytest.mark.chaos
def test_transient_fault_retries_to_bitwise_identical_run(tmp_path):
    """A dropped offsets frame set AND a corrupted scores frame set
    (CRC-detected), injected by a deterministic fault plan: both
    exchanges retry through the teardown/rebuild path and the run
    completes with results BITWISE identical to the fault-free run,
    with p2p_retry + fault_injected events in the fleet shards and the
    retry/recovery section live in ``report fleet``."""
    clean = _run_chaos_workers(2, {0: {}, 1: {}})
    teldir = tmp_path / "tel"
    plan = [
        {"op": "drop", "link": [0, 1], "seq": 1, "tag": "offsets"},
        # post-retry the counters restart with the rebuilt mesh, so the
        # first visit's scores exchange is seq 2 again
        {"op": "corrupt", "link": [1, 0], "seq": 2, "tag": "scores"},
    ]
    mode = {"fault_plan": plan, "telemetry_dir": str(teldir)}
    faulted = _run_chaos_workers(2, {0: mode, 1: mode})
    assert set(clean) == set(faulted) == {0, 1}
    for pid in (0, 1):
        np.testing.assert_array_equal(
            np.asarray(faulted[pid]["W"]), np.asarray(clean[pid]["W"]),
            err_msg=f"pid={pid}",
        )
        np.testing.assert_array_equal(
            np.asarray(faulted[pid]["V"]), np.asarray(clean[pid]["V"]),
            err_msg=f"pid={pid}",
        )
    # both sides absorbed the transients in the link layer: retries,
    # zero giveups, zero peer losses
    total_retries = sum(
        r["counters"].get("p2p.retries", 0.0) for r in faulted.values()
    )
    assert total_retries >= 2, faulted[0]["counters"]
    for r in faulted.values():
        assert r["counters"].get("p2p.giveups", 0.0) == 0
        assert "fleet.peer_lost" not in r["counters"]

    from photon_ml_tpu.obs.report import (
        fleet_run_paths,
        format_fleet,
        summarize_fleet,
    )

    fs = summarize_fleet(fleet_run_paths(str(teldir)))
    rec = fs["recovery"]
    assert rec["p2p_retries"] >= 2, rec
    assert rec["faults_injected"] == 2, rec
    assert rec["p2p_giveups"] == 0 and not rec["peer_lost"], rec
    text = format_fleet(fs)
    assert "retry/recovery:" in text and "injected faults" in text


@pytest.mark.slow
@pytest.mark.chaos
def test_peer_kill_recovers_from_checkpoint_bitwise(tmp_path):
    """The peer-loss drill: a fault plan hard-kills process 1 at its
    second-visit offsets send. Process 0's retries exhaust into
    PeerLost, the roll call confirms the loss, the placement re-plan
    degrades the group to one process, and the fit resumes from the
    last atomic checkpoint — producing a final model BITWISE identical
    to a clean single-process run resumed from the same checkpoint."""
    anchor_dir = tmp_path / "anchor-ckpt"
    chaos_dir = tmp_path / "chaos-ckpt"
    teldir = tmp_path / "tel"

    # anchor arm: a clean 2-proc run of ONE outer iteration writes the
    # same checkpoint state the chaos arm checkpoints before the kill
    anchor_mode = {"iterations": 1, "checkpoint_dir": str(anchor_dir)}
    _run_chaos_workers(2, {0: anchor_mode, 1: anchor_mode})
    assert (anchor_dir / "ckpt.npz").exists()

    # chaos arm: 2 iterations; process 1 dies at its visit-2 offsets
    # send (link 1->0 frame set #3: visit-1 offsets=1, scores=2)
    plan = [{"op": "kill", "link": [1, 0], "seq": 3, "tag": "offsets"}]
    chaos_mode = {
        "iterations": 2, "checkpoint_dir": str(chaos_dir),
        "fault_plan": plan, "telemetry_dir": str(teldir),
    }
    chaos = _run_chaos_workers(
        2, {0: chaos_mode, 1: chaos_mode}, allow_kill=(1,)
    )
    assert set(chaos) == {0}
    survivor = chaos[0]
    # the survivor recovered (resumed mid-fit) rather than restarting
    assert survivor["resumed_from"] == [1, 0], survivor["resumed_from"]
    assert survivor["counters"].get("fleet.peer_lost") == 1.0
    assert survivor["counters"].get("fleet.recoveries") == 1.0
    assert survivor["counters"].get("p2p.giveups") == 1.0

    # clean arm: single process over the SURVIVOR'S data, resumed from
    # the anchor checkpoint (the pre-loss fingerprint is peeked from the
    # npz metadata without materializing arrays; row base 0 = process
    # 0's slice)
    clean_mode = {
        "iterations": 2, "checkpoint_dir": str(anchor_dir),
        "resume_fingerprint_from": str(anchor_dir),
        "resume_row_base": 0,
    }
    clean = _run_chaos_workers(1, {0: clean_mode})
    assert clean[0]["resumed_from"] == [1, 0], clean[0]["resumed_from"]
    np.testing.assert_array_equal(
        np.asarray(survivor["W"]), np.asarray(clean[0]["W"])
    )
    np.testing.assert_array_equal(
        np.asarray(survivor["V"]), np.asarray(clean[0]["V"])
    )

    # the survivor's shard carries the full recovery narrative, and the
    # fleet report names the lost peer (process 1's shard necessarily
    # truncates at the kill — a missing run_end, not an error)
    from photon_ml_tpu.obs.report import (
        fleet_run_paths,
        format_fleet,
        summarize_fleet,
    )

    fs = summarize_fleet(fleet_run_paths(str(teldir)))
    rec = fs["recovery"]
    assert rec["p2p_giveups"] >= 1, rec
    assert [pl["peer"] for pl in rec["peer_lost"]] == [1], rec
    assert len(rec["recoveries"]) == 1, rec
    assert rec["recoveries"][0]["survivors"] == [0]
    assert rec["recoveries"][0]["lost"] == [1]
    assert rec["roll_calls"][0]["survivors"] == [0]
    text = format_fleet(fs)
    assert "peer_lost: p0 lost peer 1" in text
    assert "degraded mid-flight" in text


# -- in-place degrade for the in-memory descent + elastic rejoin (ISSUE 14) --

_DESCENT_WORKER = textwrap.dedent(
    """
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    coordinator, pid, nproc = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    mode = json.loads(sys.argv[4])
    if nproc > 1:
        # the in-memory degradable configuration: owned-bucket placement
        # + the host-collective owner-segment combine (the device mesh
        # cannot shrink in-process; these two are what make the solve
        # survivable)
        os.environ["PHOTON_RE_SHARD"] = "1"
        os.environ["PHOTON_RE_COMBINE"] = "segments"
        os.environ.setdefault("PHOTON_P2P_CRC", "1")
        os.environ.setdefault("PHOTON_P2P_RETRIES", "6")
        os.environ.setdefault("PHOTON_P2P_BACKOFF_S", "0.1")
        os.environ.setdefault("PHOTON_P2P_TIMEOUT_S", "3")
        os.environ.setdefault("PHOTON_ROLLCALL_WINDOW_S", "1.5")
        os.environ.setdefault("PHOTON_COORD_MAX_MISSING_HEARTBEATS", "360")
    if mode.get("degrade"):
        os.environ["PHOTON_DESCENT_DEGRADE"] = "1"
    if mode.get("fault_plan"):
        os.environ["PHOTON_FAULT_PLAN"] = json.dumps(mode["fault_plan"])
    import jax
    jax.config.update("jax_platforms", "cpu")
    if nproc > 1:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    import numpy as np

    if nproc > 1:
        from photon_ml_tpu.parallel.multihost import initialize_multihost
        initialize_multihost(coordinator, num_processes=nproc, process_id=pid)

    run_path = None
    if mode.get("telemetry_dir"):
        import photon_ml_tpu.obs as obs
        run_path = obs.configure(
            mode["telemetry_dir"], run_id=mode.get("run_id")
        )

    import jax.numpy as jnp
    from photon_ml_tpu.config import OptimizationConfig, OptimizerConfig
    from photon_ml_tpu.config import RegularizationContext
    from photon_ml_tpu.game import bucket_entities, group_by_entity
    from photon_ml_tpu.game.coordinate import RandomEffectCoordinate
    from photon_ml_tpu.game.data import DenseFeatures, GameBatch
    from photon_ml_tpu.game.descent import CoordinateDescent
    from photon_ml_tpu.parallel import data_mesh
    from photon_ml_tpu.types import (
        RegularizationType, TaskType, VarianceComputationType,
    )

    # the in-memory multi-process schedule REPLICATES the data (only
    # bucket ownership is split), so every arm sees the identical
    # problem and the bitwise contract spans process counts
    rng = np.random.default_rng(42)
    E = 12
    sizes = np.maximum((60.0 / (1 + np.arange(E)) ** 1.1).astype(int), 3)
    ids = np.repeat(np.arange(E), sizes).astype(np.int64)
    ids = ids[rng.permutation(len(ids))]
    n = len(ids)
    X = rng.normal(size=(n, 3)).astype(np.float32)
    W_true = (rng.normal(size=(E, 3)) * 0.5).astype(np.float32)
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(
        -np.sum(W_true[ids] * X, axis=1)))).astype(np.float32)
    batch = GameBatch(
        labels=jnp.asarray(y),
        offsets=jnp.zeros(n, jnp.float32),
        weights=jnp.ones(n, jnp.float32),
        features={"r": DenseFeatures(X=jnp.asarray(X))},
        id_tags={"eid": jnp.asarray(ids, jnp.int32)},
    )
    grouping = group_by_entity(ids, num_entities=E)
    coord = RandomEffectCoordinate(
        coordinate_id="per_entity",
        batch=batch,
        feature_shard_id="r",
        random_effect_type="eid",
        config=OptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=6, tolerance=1e-9),
            regularization=RegularizationContext(RegularizationType.L2),
            regularization_weight=1.0,
        ),
        grouping=grouping,
        buckets=bucket_entities(grouping),
        task_type=TaskType.LOGISTIC_REGRESSION,
        num_entities=E,
        variance_computation=VarianceComputationType.SIMPLE,
        mesh=data_mesh() if nproc > 1 else None,
    )
    cd = CoordinateDescent(
        coordinates={"per_entity": coord}, batch=batch,
        task_type=TaskType.LOGISTIC_REGRESSION,
    )
    res = cd.run(
        ["per_entity"],
        int(mode.get("iterations", 3)),
        checkpoint_dir=mode.get("checkpoint_dir"),
        checkpoint_fingerprint=mode.get("fingerprint"),
        resume_fingerprints=mode.get("resume_fingerprints", []),
    )
    if run_path is not None:
        obs.shutdown()
    from photon_ml_tpu.obs.metrics import REGISTRY
    snap = REGISTRY.snapshot()
    counters = {
        k: v.get("value", 0.0)
        for k, v in snap.get("counters", {}).items()
        if k.startswith(("p2p.", "fleet."))
    }
    sub = res.model.models["per_entity"]
    print("RESULT " + json.dumps({
        "pid": pid,
        "W": np.asarray(sub.coefficients, np.float64).tolist(),
        "V": np.asarray(sub.variances, np.float64).tolist(),
        "iterations_recorded": len(res.trackers["per_entity"]),
        "counters": counters,
        "run_path": run_path,
    }), flush=True)
    sys.stdout.flush()
    os._exit(0)
    """
)


@pytest.mark.slow
@pytest.mark.chaos
def test_descent_peer_kill_degrades_in_place_bitwise(tmp_path):
    """The ISSUE-14 tentpole drill: kill one of 2 processes mid-descent
    (at its owner-segment combine send). The survivor must degrade IN
    PLACE — ``run()`` returns normally with no process restart — and
    the final model must be BITWISE equal to a clean run on the
    survivor count resumed from the same iteration state (the anchor
    checkpoint), which also exercises the descent resume-fingerprint-
    collection satellite."""
    import shutil

    anchor = tmp_path / "anchor"
    chaos_ckpt = tmp_path / "chaos"
    clean_ckpt = tmp_path / "clean"
    teldir = tmp_path / "tel"

    # anchor: clean 2-proc run of ONE iteration -> iteration-1 state
    anchor_mode = {
        "iterations": 1, "checkpoint_dir": str(anchor),
        "fingerprint": "descent-p2", "degrade": True,
    }
    _run_chaos_workers(
        2, {0: anchor_mode, 1: anchor_mode}, worker=_DESCENT_WORKER
    )
    assert (anchor / "ckpt.npz").exists()
    shutil.copytree(anchor, chaos_ckpt)
    shutil.copytree(anchor, clean_ckpt)

    # chaos arm: resume at iteration 1 on 2 procs; process 1 dies at
    # its FIRST owner-segment combine send of the resumed run
    plan = [{"op": "kill", "link": [1, 0], "seq": 1,
             "tag": "re_combine/wv"}]
    chaos_mode = {
        "iterations": 3, "checkpoint_dir": str(chaos_ckpt),
        "fingerprint": "descent-p2", "degrade": True,
        "fault_plan": plan, "telemetry_dir": str(teldir),
        "run_id": "D1",
    }
    chaos = _run_chaos_workers(
        2, {0: chaos_mode, 1: chaos_mode}, allow_kill=(1,),
        worker=_DESCENT_WORKER,
    )
    assert set(chaos) == {0}
    surv = chaos[0]
    # degraded IN PLACE: run() returned normally with one tracker per
    # post-resume iteration (1 and 2; iteration 0 lives in the anchor
    # run), and the recovery counters fired exactly once
    assert surv["iterations_recorded"] == 2
    assert surv["counters"].get("fleet.peer_lost") == 1.0
    assert surv["counters"].get("fleet.degraded_descents") == 1.0
    assert "fleet.recoveries" not in surv["counters"]  # no re-entry

    # clean arm: 1-proc full-data run resumed from the SAME iteration
    # state, accepting the pre-loss layout's fingerprint (satellite)
    clean_mode = {
        "iterations": 3, "checkpoint_dir": str(clean_ckpt),
        "fingerprint": "descent-p1",
        "resume_fingerprints": ["descent-p2"],
    }
    clean = _run_chaos_workers(1, {0: clean_mode}, worker=_DESCENT_WORKER)
    np.testing.assert_array_equal(
        np.asarray(surv["W"]), np.asarray(clean[0]["W"])
    )
    np.testing.assert_array_equal(
        np.asarray(surv["V"]), np.asarray(clean[0]["V"])
    )

    # the survivor's shard carries the in-memory degrade narrative and
    # the new exact gate tier sees it
    from photon_ml_tpu.obs.report import (
        fleet_run_paths,
        format_fleet,
        gate_metrics_from_fleet,
        summarize_fleet,
    )

    fs = summarize_fleet(fleet_run_paths(str(teldir)))
    rec = fs["recovery"]
    assert [pl["peer"] for pl in rec["peer_lost"]] == [1]
    assert len(rec["degraded_descents"]) == 1
    assert rec["degraded_descents"][0]["survivors"] == [0]
    assert rec["degraded_descents"][0]["lost"] == [1]
    assert not rec["recoveries"]  # in place, not checkpoint re-entry
    text = format_fleet(fs)
    assert "degraded IN PLACE" in text
    gm = gate_metrics_from_fleet(fs)
    assert gm["fleet/degraded_descents"] == 1.0
    assert gm["fleet/rejoins"] == 0.0


@pytest.mark.slow
@pytest.mark.chaos
def test_rejoin_after_kill_bitwise_with_four_processes(tmp_path):
    """The elastic-rejoin drill: 4 processes, process 3 dies at its
    visit-2 offsets send and re-execs 2 s later (fault op ``rejoin``).
    The survivors degrade 4->3, then at the first post-degrade visit
    boundary (inside the PHOTON_REJOIN_WINDOW_S linger, so no
    degraded-data visit ever commits) admit the rejoiner back 3->4 and
    resume from the pre-kill checkpoint — the final model is BITWISE
    equal to an uninterrupted 4-process run."""
    ckpt = tmp_path / "ckpt"
    clean_ckpt = tmp_path / "ckpt-clean"
    teldir = tmp_path / "tel"
    mesh_cache = str(tmp_path / "mesh.json")

    plan = [{"op": "rejoin", "link": [3, 0], "seq": 3, "tag": "offsets",
             "delay_s": 2.0}]
    mode = {
        "iterations": 3, "checkpoint_dir": str(ckpt),
        "fault_plan": plan, "telemetry_dir": str(teldir),
        "run_id": "RJ1", "rejoin": True, "mesh_cache": mesh_cache,
    }
    res = _run_chaos_workers(
        4, {p: mode for p in range(4)}, allow_kill=(3,)
    )
    # every survivor finished AND the relaunched process 3 printed its
    # own RESULT through the inherited pipe
    assert set(res) == {0, 1, 2, 3}, sorted(res)
    for p in (0, 1, 2):
        assert res[p]["counters"].get("fleet.peer_lost") == 1.0, res[p]
        assert res[p]["counters"].get("fleet.recoveries") == 1.0
        assert res[p]["counters"].get("fleet.rejoins") == 1.0
    assert res[3]["counters"].get("fleet.rejoins") == 1.0

    # clean arm: uninterrupted 4-process run over the same data
    clean_mode = {"iterations": 3, "checkpoint_dir": str(clean_ckpt)}
    clean = _run_chaos_workers(4, {p: clean_mode for p in range(4)})
    for p in range(4):
        np.testing.assert_array_equal(
            np.asarray(res[p]["W"]), np.asarray(clean[p]["W"]),
            err_msg=f"pid={p}",
        )
        np.testing.assert_array_equal(
            np.asarray(res[p]["V"]), np.asarray(clean[p]["V"]),
            err_msg=f"pid={p}",
        )

    # fleet narrative: degrade AND rejoin, and the exact tiers see both
    from photon_ml_tpu.obs.report import (
        fleet_run_paths,
        format_fleet,
        gate_metrics_from_fleet,
        summarize_fleet,
    )

    fs = summarize_fleet(fleet_run_paths(str(teldir), run_id="RJ1"))
    rec = fs["recovery"]
    # each survivor emitted exactly one peer_lost; WHICH peer it blames
    # is schedule-dependent under CPU contention (the mesh-teardown
    # cascade can close a live neighbor's socket before that survivor
    # observes the real loss) — the roll-call truth is pinned by the
    # recovery records instead
    assert sorted(pl["process"] for pl in rec["peer_lost"]) == [0, 1, 2]
    assert len(rec["recoveries"]) == 3
    assert all(rv["lost"] == [3] for rv in rec["recoveries"])
    assert all(
        sorted(rv["survivors"]) == [0, 1, 2] for rv in rec["recoveries"]
    )
    rejoins = rec["rejoins"]
    assert {r["role"] for r in rejoins} == {"survivor", "rejoiner"}
    surv_rejoins = [r for r in rejoins if r["role"] == "survivor"]
    assert all(r["rejoined"] == [3] for r in surv_rejoins)
    assert all(sorted(r["group"]) == [0, 1, 2, 3] for r in rejoins)
    text = format_fleet(fs)
    assert "rejoin:" in text
    gm = gate_metrics_from_fleet(fs)
    assert gm["fleet/rejoins"] == float(len(rejoins))


@pytest.mark.slow
@pytest.mark.chaos
def test_rejoin_races_degrade_roll_call(tmp_path):
    """The roll-call race satellite: the rejoiner re-execs almost
    immediately (delay 0.2 s) while a delay spec staggers the
    survivors' discovery of the loss — so the rejoiner's listener is
    up DURING the degrade roll call, which dials its recorded port.
    The rejoiner must ignore the non-invite hello (a mesh build it was
    not named in), the degrade must converge without it, and a later
    boundary must admit it — final model still bitwise equal to the
    uninterrupted run."""
    ckpt = tmp_path / "ckpt"
    clean_ckpt = tmp_path / "ckpt-clean"
    mesh_cache = str(tmp_path / "mesh.json")

    plan = [
        {"op": "rejoin", "link": [3, 0], "seq": 3, "tag": "offsets",
         "delay_s": 0.2},
        # stagger the survivors: p0's visit-2 offsets send to p1 stalls,
        # so p1 enters the roll call late while p3's listener comes up
        {"op": "delay", "link": [0, 1], "seq": 3, "tag": "offsets",
         "delay_s": 1.5},
    ]
    mode = {
        "iterations": 3, "checkpoint_dir": str(ckpt),
        "fault_plan": plan, "rejoin": True, "mesh_cache": mesh_cache,
    }
    res = _run_chaos_workers(
        4, {p: mode for p in range(4)}, allow_kill=(3,)
    )
    assert set(res) == {0, 1, 2, 3}, sorted(res)
    for p in (0, 1, 2):
        assert res[p]["counters"].get("fleet.rejoins") == 1.0, res[p]
    clean_mode = {"iterations": 3, "checkpoint_dir": str(clean_ckpt)}
    clean = _run_chaos_workers(4, {p: clean_mode for p in range(4)})
    for p in range(4):
        np.testing.assert_array_equal(
            np.asarray(res[p]["W"]), np.asarray(clean[p]["W"]),
            err_msg=f"pid={p}",
        )


# -- owner-segment combine + telemetry-driven re-planning (ISSUE 12) ---------

_COMBINE_WORKER = textwrap.dedent(
    """
    import hashlib, json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    coordinator, pid, nproc = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    os.environ["PHOTON_RE_SHARD"] = "1" if nproc > 1 else "0"
    import jax
    jax.config.update("jax_platforms", "cpu")
    if nproc > 1:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    import numpy as np

    if nproc > 1:
        from photon_ml_tpu.parallel.multihost import initialize_multihost
        initialize_multihost(coordinator, num_processes=nproc, process_id=pid)

    import jax.numpy as jnp
    from photon_ml_tpu.config import (
        GameTrainingConfig, OptimizationConfig, OptimizerConfig,
        RandomEffectCoordinateConfig, RegularizationContext,
    )
    from photon_ml_tpu.config import OptimizerConfig as _OC
    from photon_ml_tpu.game import bucket_entities, group_by_entity
    from photon_ml_tpu.game.data import DenseFeatures
    from photon_ml_tpu.game.random_effect import train_random_effects
    from photon_ml_tpu.game.streaming import StreamedGameData, StreamedGameTrainer
    from photon_ml_tpu.obs.metrics import REGISTRY
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.parallel import data_mesh
    from photon_ml_tpu.types import (
        RegularizationType, TaskType, VarianceComputationType,
    )

    # Zipf-skewed entities, warm start + MAP prior: the acceptance
    # criterion covers coefficients, variances AND priors per arm
    rng = np.random.default_rng(42)
    E = 24
    sizes = np.maximum((80.0 / (1 + np.arange(E)) ** 1.1).astype(int), 3)
    ids = np.repeat(np.arange(E), sizes).astype(np.int64)
    ids = ids[rng.permutation(len(ids))]
    n = len(ids)
    X = rng.normal(size=(n, 3)).astype(np.float32)
    W_true = (rng.normal(size=(E, 3)) * 0.5).astype(np.float32)
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(
        -np.sum(W_true[ids] * X, axis=1)))).astype(np.float32)
    W0 = (rng.normal(size=(E, 3)) * 0.1).astype(np.float32)
    V0 = (0.5 + rng.uniform(size=(E, 3))).astype(np.float32)

    mem_kwargs = dict(
        features=DenseFeatures(X=jnp.asarray(X)),
        labels=y,
        offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32),
        buckets=bucket_entities(group_by_entity(ids, num_entities=E)),
        num_entities=E,
        loss=loss_for_task(TaskType.LOGISTIC_REGRESSION),
        config=_OC(max_iterations=6, tolerance=1e-9),
        l2_weight=1.0,
        initial_coefficients=jnp.asarray(W0),
        variance_computation=VarianceComputationType.SIMPLE,
        prior_coefficients=jnp.asarray(W0),
        prior_variances=jnp.asarray(V0),
    )
    mesh = data_mesh() if nproc > 1 else None

    def counter(name):
        return float(REGISTRY.snapshot().get("counters", {})
                     .get(name, {}).get("value", 0.0))

    def sha(a):
        return hashlib.sha256(
            np.ascontiguousarray(np.asarray(a)).tobytes()
        ).hexdigest()

    out = {"pid": pid}
    for arm in ("allreduce", "segments"):
        os.environ["PHOTON_RE_COMBINE"] = arm
        b0 = counter("re_combine.bytes_sent")
        mem = train_random_effects(mesh=mesh, **mem_kwargs)
        out[arm] = {
            "W": sha(jax.device_get(mem.coefficients)),
            "V": sha(jax.device_get(mem.variances)),
            "loss": sha(mem.loss_values),
            "it": sha(mem.iterations),
            "conv": sha(mem.converged),
            "bytes": counter("re_combine.bytes_sent") - b0,
        }

    # streamed leg UNDER the segments env (the knob must not perturb the
    # streamed path, which has no owned-result combine) — full values so
    # the cross-arm assertion is assert_array_equal, not hash equality
    opt = OptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=8, tolerance=1e-9),
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    cfg = GameTrainingConfig(
        task_type=TaskType.LOGISTIC_REGRESSION,
        coordinate_update_sequence=("per_entity",),
        coordinate_descent_iterations=2,
        fixed_effect_coordinates={},
        random_effect_coordinates={
            "per_entity": RandomEffectCoordinateConfig(
                random_effect_type="eid", feature_shard_id="r",
                optimization=opt,
            )
        },
        variance_computation=VarianceComputationType.SIMPLE,
    )
    if nproc > 1:
        bounds = np.linspace(0, n, nproc + 1).astype(int)
        lo, hi = bounds[pid], bounds[pid + 1]
    else:
        lo, hi = 0, n
    data = StreamedGameData(
        labels=y[lo:hi], features={"r": X[lo:hi]},
        id_tags={"eid": ids[lo:hi]},
    )
    trainer = StreamedGameTrainer(cfg, chunk_rows=1 << 16, multihost=nproc > 1)
    model, info = trainer.fit(data)
    out["stream_W"] = np.asarray(
        model.models["per_entity"].coefficients, np.float64
    ).tolist()
    out["stream_V"] = np.asarray(
        model.models["per_entity"].variances, np.float64
    ).tolist()

    # satellite probe: the batched segment gather reproduces the
    # per-array process_allgather BYTE-identically on a genuinely
    # non-fully-addressable (cross-process sharded) array
    if nproc > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax.experimental import multihost_utils as mhu
        from photon_ml_tpu.game.random_effect import _gather_unaddressable

        gmesh = data_mesh()
        rows = 4 * gmesh.devices.size
        local = (np.arange(rows, dtype=np.float32) + 100.0 * pid)
        arr = mhu.host_local_array_to_global_array(
            np.asarray(
                local[pid * (rows // nproc):(pid + 1) * (rows // nproc)]
            ),
            gmesh, P("data"),
        )
        assert not arr.is_fully_addressable
        ref = np.asarray(mhu.process_allgather(arr, tiled=True))
        got = _gather_unaddressable([arr])[0]
        assert got.dtype == ref.dtype and got.shape == ref.shape
        assert got.tobytes() == ref.tobytes()
        out["gather_probe_ok"] = True

    print("RESULT " + json.dumps(out))
    """
)


def _run_combine_workers(nproc: int) -> dict:
    coordinator = f"127.0.0.1:{_free_port()}"
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
    }
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _COMBINE_WORKER, coordinator,
             str(pid), str(nproc)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for pid in range(nproc)
    ]
    results = {}
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, f"worker failed:\n{out}\n{err[-4000:]}"
        for line in out.splitlines():
            if line.startswith("RESULT "):
                r = json.loads(line[len("RESULT "):])
                results[r["pid"]] = r
    assert set(results) == set(range(nproc))
    return results


@pytest.mark.slow
def test_owner_segment_combine_bitwise_and_cheaper():
    """PHOTON_RE_COMBINE=segments on 2 AND 4 processes: the in-memory
    owned-bucket solve — coefficients, SIMPLE variances, incremental MAP
    priors, per-entity diagnostics — is BITWISE identical to the
    allreduce arm AND to the single-process reference, on every process;
    the per-process ``re_combine.bytes_sent`` counter is STRICTLY lower
    on the segments arm; the streamed solve under the segments env is
    untouched; and the batched diagnostics gather reproduces
    ``process_allgather`` byte-for-byte on a cross-process sharded
    array."""
    ref = _run_combine_workers(1)[0]
    for nproc in (2, 4):
        got = _run_combine_workers(nproc)
        for pid, r in got.items():
            tag = f"nproc={nproc} pid={pid}"
            for field in ("W", "V", "loss", "it", "conv"):
                # across arms, across processes, and vs the 1-process run
                assert r["segments"][field] == r["allreduce"][field], (
                    tag, field,
                )
                assert r["segments"][field] == ref["allreduce"][field], (
                    tag, field,
                )
            assert r["gather_probe_ok"] is True, tag
            np.testing.assert_array_equal(
                np.asarray(r["stream_W"]), np.asarray(ref["stream_W"]),
                err_msg=tag,
            )
            np.testing.assert_array_equal(
                np.asarray(r["stream_V"]), np.asarray(ref["stream_V"]),
                err_msg=tag,
            )
        # the whole point: strictly fewer combine bytes on the wire.
        # Fleet AGGREGATE at this toy E (the framed codec's fixed
        # header ≈ 400 B rivals a near-full owner's dense payload at
        # E=24); the per-process reduction at real shapes is asserted
        # by the MULTICHIP_r08 capture (74.9% mean at 4 shards)
        seg_total = sum(r["segments"]["bytes"] for r in got.values())
        allred_total = sum(r["allreduce"]["bytes"] for r in got.values())
        assert 0 < seg_total < allred_total, (nproc, seg_total, allred_total)


_REPLAN_WORKER = textwrap.dedent(
    """
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    coordinator, pid, nproc, mode = (
        sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
    )
    os.environ["PHOTON_RE_SHARD"] = "1"
    if mode == "replan":
        # telemetry-triggered re-planning, driven by an injected
        # synthetic straggler: process 1 sleeps per solve visit, so its
        # measured wall (real telemetry, not a faked gauge) trips the
        # threshold and entities migrate at the iteration boundary
        os.environ["PHOTON_RE_REPLAN_IMBALANCE"] = "1.2"
        os.environ["PHOTON_RE_STRAGGLER"] = "1:0.3"
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    import numpy as np
    from photon_ml_tpu.parallel.multihost import initialize_multihost
    initialize_multihost(coordinator, num_processes=nproc, process_id=pid)

    from photon_ml_tpu.config import (
        GameTrainingConfig, OptimizationConfig, OptimizerConfig,
        RandomEffectCoordinateConfig, RegularizationContext,
    )
    from photon_ml_tpu.game.streaming import StreamedGameData, StreamedGameTrainer
    from photon_ml_tpu.obs.metrics import REGISTRY
    from photon_ml_tpu.types import (
        RegularizationType, TaskType, VarianceComputationType,
    )

    rng = np.random.default_rng(43)
    E = 24
    sizes = np.maximum((80.0 / (1 + np.arange(E)) ** 1.1).astype(int), 3)
    ids = np.repeat(np.arange(E), sizes).astype(np.int64)
    ids = ids[rng.permutation(len(ids))]
    n = len(ids)
    X = rng.normal(size=(n, 3)).astype(np.float32)
    W_true = (rng.normal(size=(E, 3)) * 0.5).astype(np.float32)
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(
        -np.sum(W_true[ids] * X, axis=1)))).astype(np.float32)

    opt = OptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=8, tolerance=1e-9),
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    cfg = GameTrainingConfig(
        task_type=TaskType.LOGISTIC_REGRESSION,
        coordinate_update_sequence=("per_entity",),
        coordinate_descent_iterations=3,
        fixed_effect_coordinates={},
        random_effect_coordinates={
            "per_entity": RandomEffectCoordinateConfig(
                random_effect_type="eid", feature_shard_id="r",
                optimization=opt,
            )
        },
        variance_computation=VarianceComputationType.SIMPLE,
    )
    # validation rides along so the re-shard rebuild of the validation
    # routing (the migration's subtlest consumer) is exercised too
    vrng = np.random.default_rng(7)
    n_val = 60
    val_ids = vrng.integers(0, E, size=n_val).astype(np.int64)
    val_ids[::15] = -1
    val_X = vrng.normal(size=(n_val, 3)).astype(np.float32)
    val_y = (vrng.uniform(size=n_val) < 0.5).astype(np.float32)
    bounds = np.linspace(0, n, nproc + 1).astype(int)
    lo, hi = bounds[pid], bounds[pid + 1]
    vbounds = np.linspace(0, n_val, nproc + 1).astype(int)
    vlo, vhi = vbounds[pid], vbounds[pid + 1]
    data = StreamedGameData(
        labels=y[lo:hi], features={"r": X[lo:hi]},
        id_tags={"eid": ids[lo:hi]},
    )
    validation = StreamedGameData(
        labels=val_y[vlo:vhi], features={"r": val_X[vlo:vhi]},
        id_tags={"eid": val_ids[vlo:vhi]},
    )
    trainer = StreamedGameTrainer(
        cfg, chunk_rows=1 << 16, multihost=True,
        evaluators=("AUC", "MULTI_AUC(eid)"),
    )
    model, info = trainer.fit(data, validation=validation)
    snap = REGISTRY.snapshot()

    def counter(name):
        return float(snap.get("counters", {}).get(name, {}).get("value", 0.0))

    print("RESULT " + json.dumps({
        "pid": pid,
        "mode": mode,
        "W": np.asarray(
            model.models["per_entity"].coefficients, np.float64
        ).tolist(),
        "V": np.asarray(
            model.models["per_entity"].variances, np.float64
        ).tolist(),
        "val_metrics": [
            {k: v.metrics for k, v in h.items()}
            for h in trainer.validation_history
        ],
        "replan_checks": counter("re_replan.checks"),
        "replans": counter("re_replan.count"),
        "migrations": counter("re_replan.migrations"),
    }))
    """
)


def _run_replan_workers(nproc: int, mode: str) -> dict:
    coordinator = f"127.0.0.1:{_free_port()}"
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
    }
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _REPLAN_WORKER, coordinator,
             str(pid), str(nproc), mode],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for pid in range(nproc)
    ]
    results = {}
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, f"worker failed:\n{out}\n{err[-4000:]}"
        for line in out.splitlines():
            if line.startswith("RESULT "):
                r = json.loads(line[len("RESULT "):])
                results[r["pid"]] = r
    assert set(results) == set(range(nproc))
    return results


@pytest.mark.slow
def test_replan_migrates_on_straggler_and_stays_bitwise():
    """The telemetry-driven re-planner on an injected synthetic
    straggler (2-proc gloo): process 1 sleeps 0.3 s per solve visit, the
    measured-wall imbalance trips PHOTON_RE_REPLAN_IMBALANCE, entities
    migrate at the iteration boundary — and the final model (and the
    per-visit validation metrics) are BITWISE/equal to the run without
    the straggler or the re-planner, because migration only moves
    ownership, never math."""
    base = _run_replan_workers(2, "off")
    replan = _run_replan_workers(2, "replan")
    for pid in (0, 1):
        tag = f"pid={pid}"
        r, b = replan[pid], base[pid]
        assert r["replan_checks"] >= 1, (tag, r)
        assert r["replans"] >= 1, (tag, r)
        assert r["migrations"] > 0, (tag, r)
        # migration moved entities but not math: the model is bitwise
        # the unmigrated run's
        np.testing.assert_array_equal(
            np.asarray(r["W"]), np.asarray(b["W"]), err_msg=tag
        )
        np.testing.assert_array_equal(
            np.asarray(r["V"]), np.asarray(b["V"]), err_msg=tag
        )
        assert len(r["val_metrics"]) == len(b["val_metrics"])
        for got_h, ref_h in zip(r["val_metrics"], b["val_metrics"]):
            for coord, m_ref in ref_h.items():
                m_got = got_h[coord]
                np.testing.assert_allclose(
                    m_got["MULTI_AUC(eid)"], m_ref["MULTI_AUC(eid)"],
                    rtol=1e-6, err_msg=tag,
                )
                np.testing.assert_allclose(
                    m_got["AUC"], m_ref["AUC"], atol=2e-4, err_msg=tag,
                )
    # the baseline arm must not have re-planned (no knob, no straggler)
    for pid in (0, 1):
        assert base[pid]["migrations"] == 0.0
