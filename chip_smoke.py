#!/usr/bin/env python3
"""Chip smoke: the standing proof that the system still starts on a TPU.

One process drives the main path once through the entry points a user
calls, over every device JAX reports, at the full width of the models the
repo ships (depth and row counts cut, weights and data from a seed):

- ``game``: ``cli.train.main`` -> ``cli.score.main`` ->
  ``publish_game_model`` -> ``cli.serve.run`` on config E's widths (fixed
  d=64 + intercept, 20,000 users x 8, 4,000 items x 8), read from Avro;
- ``sparse``: ``cli.train_glm.main`` on a LIBSVM file at config A2's width
  (d=131,072, 32 nonzeros a row) through the tile-COO Pallas kernels,
  against the same solve on the untiled XLA ``SparseBatch`` path;
- ``dense``: the fused one-pass kernels (``fused_value_grad``,
  ``fused_hvp``) at the headline shape (n=2^20, d=512, bf16 and f32) under
  L-BFGS and TRON, against ``make_objective(..., fused=False)``;
- ``distributed``: ``DistributedTrainer`` over ``data_mesh()`` — the fused
  kernel inside ``shard_map`` and one tile-COO per shard — against the
  one-device solve.

It refuses to run without a TPU (no CPU fallback, x64 stays off), prints
the device first, the full summary (per-leg facts, compile seconds,
persistent-cache hits and misses) as one ``chip_smoke: summary: {...}``
line and to ``chip_smoke.json`` in its output directory, and LAST one JSON
object with exactly ``ok`` and ``device`` — the verdict the chip check
parses. It exits 0 only if every leg passed. Leg functions take
their sizes as arguments, so ``tests/test_chip_smoke.py`` runs them tiny
on the CPU mesh; the device check lives in ``main()`` only.

    python chip_smoke.py [--out DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import traceback

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")

# Full-width sizes of the chip run. Widths are the bench configs' own
# (bench.py config E, A2, headline); row counts are cut so that the whole
# smoke, compilation included, stays well inside its 1200 s limit.
FULL_SIZES = {
    "game": dict(
        n_train=1 << 16, n_val=1 << 13, d_fixed=64,
        effects={"userId": (20000, 8), "itemId": (4000, 8)},
        requests=2000, rate_hz=500.0,
    ),
    "sparse": dict(n=1 << 16, d=1 << 17, k=32, iters=5),
    "dense": dict(n=1 << 20, d=512, lbfgs_iters=5, tron_iters=3),
    "distributed": dict(
        n_dense=1 << 18, d_dense=512, n_sparse=1 << 16, d_sparse=1 << 17,
        k=32, iters=3,
    ),
}


class SmokeFailure(AssertionError):
    """A leg's own check failed (as opposed to the program raising)."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _rel(a, b) -> float:
    """max|a - b| over max|b|: one number for scalars and vectors alike."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))), 1e-30))


# ---------------------------------------------------------------- jax events


class _JaxEvents:
    """Compile seconds and persistent-cache traffic from ``jax.monitoring``
    (listeners cannot be removed, so one instance lives per process)."""

    def __init__(self):
        from jax import monitoring

        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, name: str, secs: float, **kw) -> None:
        if "backend_compile" in name:
            self.compile_s += secs


_EVENTS: _JaxEvents | None = None


def _events() -> _JaxEvents:
    global _EVENTS
    if _EVENTS is None:
        _EVENTS = _JaxEvents()
    return _EVENTS


def _fresh_dir(path: str) -> str:
    """A leg's own directory, emptied: a checkpoint or a published snapshot
    left by an earlier smoke would be resumed instead of the run made."""
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def _counter(name: str) -> float:
    from photon_ml_tpu.obs.metrics import REGISTRY

    c = REGISTRY.snapshot(name)["counters"].get(name)
    return float(c["value"]) if c else 0.0


def _device_memory_peaks() -> list[int | None]:
    """``peak_bytes_in_use`` per device; None where the backend reports no
    memory stats (the CPU backend)."""
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats()
        peaks.append(None if not stats else int(stats["peak_bytes_in_use"]))
    return peaks


# ---------------------------------------------------------------- GAME leg


def _write_game_avro(path, data, lo, hi, d_fixed, effects) -> None:
    """Rows [lo, hi) of one generating model as TrainingExampleAvro-style
    records: a global bag plus one bag and one id tag per random effect."""
    from photon_ml_tpu.io import TRAINING_EXAMPLE_SCHEMA, write_avro_file

    schema = json.loads(json.dumps(TRAINING_EXAMPLE_SCHEMA))
    for name in effects:
        schema["fields"].insert(
            5,
            {
                "name": f"{name}Features",
                "type": {"type": "array", "items": "NameTermValueAvro"},
                "default": [],
            },
        )
    terms = [
        str(j)
        for j in range(max([d_fixed] + [d for _, d in effects.values()]))
    ]

    def bag(name, row):
        return [
            {"name": name, "term": terms[j], "value": float(v)}
            for j, v in enumerate(row)
        ]

    def records():
        for i in range(lo, hi):
            rec = {
                "uid": f"s{i}",
                "response": float(data.y[i]),
                "offset": None,
                "weight": None,
                # the generator's last global column is the intercept; the
                # reader adds its own (has_intercept), so it is not written
                "features": bag("g", data.X[i, :d_fixed]),
                "metadataMap": {
                    name: f"{name}_{data.entity_ids[name][i]}"
                    for name in effects
                },
            }
            for name in effects:
                rec[f"{name}Features"] = bag(name, data.entity_X[name][i])
            yield rec

    write_avro_file(path, schema, records())


def _game_config(effects):
    """Config E's three coordinates (bench.py ``_game_setup``): L-BFGS on
    the fixed effect, Newton-Cholesky per entity, two outer iterations."""
    from photon_ml_tpu.config import (
        FeatureShardConfig,
        FixedEffectCoordinateConfig,
        GameTrainingConfig,
        OptimizationConfig,
        OptimizerConfig,
        RandomEffectCoordinateConfig,
        RegularizationContext,
    )
    from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType

    l2 = RegularizationContext(RegularizationType.L2)
    opt_re = OptimizationConfig(
        optimizer=OptimizerConfig(
            optimizer_type=OptimizerType.NEWTON_CHOLESKY,
            max_iterations=20, tolerance=1e-7,
        ),
        regularization=l2, regularization_weight=1.0,
    )
    return GameTrainingConfig(
        task_type=TaskType.LOGISTIC_REGRESSION,
        coordinate_update_sequence=("fixed",) + tuple(f"per_{e}" for e in effects),
        coordinate_descent_iterations=2,
        fixed_effect_coordinates={
            "fixed": FixedEffectCoordinateConfig(
                feature_shard_id="global",
                optimization=OptimizationConfig(
                    optimizer=OptimizerConfig(max_iterations=20, tolerance=1e-7),
                    regularization=l2, regularization_weight=1.0,
                ),
            )
        },
        random_effect_coordinates={
            f"per_{e}": RandomEffectCoordinateConfig(
                random_effect_type=e, feature_shard_id=f"per_{e}",
                optimization=opt_re,
            )
            for e in effects
        },
        feature_shards={
            "global": FeatureShardConfig(
                feature_bags=("features",), has_intercept=True
            ),
            **{
                f"per_{e}": FeatureShardConfig(
                    feature_bags=(f"{e}Features",), has_intercept=False
                )
                for e in effects
            },
        },
        evaluators=("AUC", "LOGISTIC_LOSS"),
    )


def leg_game(
    out_dir: str, *, n_train: int, n_val: int, d_fixed: int, effects: dict,
    requests: int, rate_hz: float, seed: int = 0, auc_slack: float = 0.02,
    serve_atol: float = 0.0,
) -> dict:
    """Train -> score -> publish -> serve through the CLI entry points.

    ``serve_atol`` bounds |served - batch| scores; 0.0 is the README's
    bitwise claim."""
    import jax

    from photon_ml_tpu.cli import score as score_cli
    from photon_ml_tpu.cli import serve as serve_cli
    from photon_ml_tpu.cli import train as train_cli
    from photon_ml_tpu.data.index_map import IndexMap
    from photon_ml_tpu.data.synthetic import synthetic_game_data
    from photon_ml_tpu.evaluation.evaluators import auc_roc
    from photon_ml_tpu.game.data import make_game_batch
    from photon_ml_tpu.io.model_io import (
        load_game_model,
        load_published_model,
        publish_game_model,
    )
    from photon_ml_tpu.obs.metrics import REGISTRY
    from photon_ml_tpu.transformers import GameTransformer

    _fresh_dir(out_dir)
    facts: dict = {}
    n = n_train + n_val
    t0 = time.perf_counter()
    data = synthetic_game_data(
        np.random.default_rng(seed), n, d_fixed=d_fixed, effects=effects
    )
    train_path = os.path.join(out_dir, "train.avro")
    val_path = os.path.join(out_dir, "val.avro")
    _write_game_avro(train_path, data, 0, n_train, d_fixed, effects)
    _write_game_avro(val_path, data, n_train, n, d_fixed, effects)
    cfg_path = os.path.join(out_dir, "game_config.json")
    with open(cfg_path, "w") as f:
        json.dump(_game_config(effects).to_dict(), f, indent=2)
    facts["setup_s"] = round(time.perf_counter() - t0, 2)

    # the generating model's own validation AUC: the bar the fit is held to
    margin = data.X @ data.w_fixed
    for name in effects:
        margin = margin + np.sum(
            data.w_entity[name][data.entity_ids[name]] * data.entity_X[name],
            axis=1,
        )
    auc_true = float(auc_roc(margin[n_train:], data.y[n_train:]))

    train_out = os.path.join(out_dir, "train")
    telemetry = os.path.join(out_dir, "telemetry")
    errors_before = _counter("devcost.capture_errors")
    t0 = time.perf_counter()
    train_cli.main([
        "--config", cfg_path, "--train-data", train_path,
        "--validation-data", val_path, "--output-dir", train_out,
        "--telemetry-dir", telemetry, "--diagnostics",
    ])
    facts["train_s"] = round(time.perf_counter() - t0, 2)

    with open(os.path.join(train_out, "diagnostics.json")) as f:
        grid = json.load(f)["grid"][0]
    fixed_losses = [
        v["final_loss"] for v in grid["coordinates"]["fixed"]["per_iteration"]
    ]
    val_loss = [
        step[list(step)[-1]]["LOGISTIC_LOSS"]
        for step in grid["validation_history"]
    ]
    auc_model = float(grid["evaluation"]["AUC"])
    facts.update(
        fixed_loss_per_outer_iteration=fixed_losses,
        validation_logistic_loss_per_outer_iteration=val_loss,
        validation_auc=auc_model, generating_model_auc=auc_true,
    )
    _check(
        len(fixed_losses) == 2 and all(
            v is not None and np.isfinite(v) for v in fixed_losses + val_loss
        ),
        f"non-finite or missing losses: {fixed_losses} {val_loss}",
    )
    _check(
        fixed_losses[1] < fixed_losses[0] and val_loss[1] < val_loss[0],
        f"loss did not fall between outer iterations: training "
        f"{fixed_losses}, validation {val_loss}",
    )
    _check(
        abs(auc_model - auc_true) <= auc_slack,
        f"validation AUC {auc_model:.4f} not within {auc_slack} of the "
        f"generating model's {auc_true:.4f}",
    )

    # several devices: the fixed effect's rows must span all of them, and
    # every device must have held data
    n_dev = len(jax.devices())
    if n_dev > 1:
        spanned = REGISTRY.snapshot("mesh.")["gauges"].get("mesh.batch_devices")
        facts["batch_devices"] = spanned
        _check(
            spanned == n_dev,
            f"training batch spans {spanned} devices, {n_dev} visible",
        )
    peaks = _device_memory_peaks()
    facts["peak_bytes_in_use"] = peaks
    if jax.default_backend() != "cpu":  # the CPU backend has no memory stats
        _check(
            all(p is not None and p > 0 for p in peaks),
            f"a device reports no memory in use: {peaks}",
        )

    # batch scoring driver on the validation file
    score_out = os.path.join(out_dir, "scores")
    t0 = time.perf_counter()
    score_cli.main([
        "--model-dir", train_out, "--data", val_path,
        "--output-dir", score_out, "--evaluators", "AUC",
        "--config", cfg_path,
    ])
    facts["score_s"] = round(time.perf_counter() - t0, 2)
    with open(os.path.join(score_out, "metrics.json")) as f:
        auc_scored = float(json.load(f)["AUC"])
    facts["scored_auc"] = auc_scored
    _check(
        abs(auc_scored - auc_model) <= 1e-3,
        f"scoring driver AUC {auc_scored} != training-time {auc_model}",
    )

    # publish (columns and entities in the trainer's dense order, so the
    # server needs no maps), then serve an open-loop Zipf trace
    published = os.path.join(out_dir, "published")
    imap_dir = os.path.join(train_out, "index-maps")
    with open(os.path.join(train_out, "entity-maps.json")) as f:
        entity_maps = json.load(f)
    publish_game_model(
        load_game_model(
            os.path.join(train_out, "best"),
            index_maps={
                fn[:-4]: IndexMap.load(os.path.join(imap_dir, fn))
                for fn in os.listdir(imap_dir) if fn.endswith(".npz")
            },
            entity_ids={f"per_{e}": entity_maps[e] for e in effects},
        ),
        published,
    )
    t0 = time.perf_counter()
    served = serve_cli.run(
        published, requests=requests, rate_hz=rate_hz, seed=seed
    )
    facts["serve_s"] = round(time.perf_counter() - t0, 2)
    facts["serve"] = {k: v for k, v in served.items() if k != "scores"}
    _check(
        len(served["scores"]) == requests
        and all(np.isfinite(v) for v in served["scores"].values()),
        "served scores missing or non-finite",
    )
    _check(
        0.0 < served["hot_hit_rate"] < 1.0,
        f"hot_hit_rate {served['hot_hit_rate']} not strictly inside (0, 1)",
    )

    # served scores against the batch transformer on the same rows
    model, _ = load_published_model(published)
    reqs = serve_cli._synthetic_requests(model, requests, 1.0, seed)
    sample = reqs[:: max(requests // 256, 1)]
    batch = make_game_batch(
        np.zeros(len(sample), np.float32),
        {
            sid: np.stack([r.features[sid] for r in sample])
            for sid in sample[0].features
        },
        id_tags={
            tag: np.asarray([r.id_tags[tag] for r in sample])
            for tag in sample[0].id_tags
        },
    )
    ref = np.asarray(GameTransformer(model).transform(batch), np.float32)
    got = np.asarray([served["scores"][r.rid] for r in sample], np.float32)
    diff = float(np.max(np.abs(got - ref)))
    facts.update(
        served_sample=len(sample), served_bitwise=bool(np.array_equal(got, ref)),
        served_max_abs_diff=diff,
    )
    _check(
        diff <= serve_atol,
        f"served scores differ from GameTransformer by {diff:.3g} "
        f"(allowed {serve_atol})",
    )

    facts["devcost_capture_errors"] = (
        _counter("devcost.capture_errors") - errors_before
    )
    _check(
        facts["devcost_capture_errors"] == 0,
        f"{facts['devcost_capture_errors']} device-cost captures failed silently",
    )
    return facts


# ---------------------------------------------------------------- sparse leg


def _write_libsvm(path, rng, n, d, k) -> None:
    """``k - 1`` uniform random columns of ``d - 1`` plus the intercept the
    reader appends: ``d`` columns and ``k`` nonzeros a row in the batch
    (bench.py ``_make_sparse_problem``'s distribution)."""
    d_raw, k_raw = d - 1, k - 1
    idx = rng.integers(0, d_raw, size=(n, k_raw))
    idx[0, 0] = d_raw - 1  # the reader sizes the space by the largest id
    val = rng.normal(size=(n, k_raw)).astype(np.float32)
    w_true = (rng.normal(size=d_raw) * 0.3).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-np.sum(val * w_true[idx], axis=1)))
    y = rng.uniform(size=n) < p
    with open(path, "w") as f:
        for i in range(n):
            f.write(
                ("+1 " if y[i] else "-1 ")
                + " ".join(f"{c + 1}:{v:.6g}" for c, v in zip(idx[i], val[i]))
                + "\n"
            )


def leg_sparse(
    out_dir: str, *, n: int, d: int, k: int, iters: int, seed: int = 1,
    rel_tol: float = 1e-4,
) -> dict:
    """``cli.train_glm.main`` on a wide LIBSVM file: the layout decision
    must tile it, the tile-COO kernels must run compiled, and the final
    loss must agree with the same solve on the untiled XLA path. Then each
    storage rung agrees with the XLA products within its documented
    tolerance."""
    import jax
    import jax.numpy as jnp

    import photon_ml_tpu.ops.sparse_tiled as st
    from photon_ml_tpu.cli import train_glm as train_glm_cli
    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.data.libsvm import read_libsvm
    from photon_ml_tpu.io.model_io import load_glm
    from photon_ml_tpu.ops.glm import make_objective
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.supervised.training import train_glm
    from photon_ml_tpu.types import TaskType

    _fresh_dir(out_dir)
    facts: dict = {}
    task = TaskType.LOGISTIC_REGRESSION
    t0 = time.perf_counter()
    path = os.path.join(out_dir, "train.libsvm")
    _write_libsvm(path, np.random.default_rng(seed), n, d, k)
    facts["setup_s"] = round(time.perf_counter() - t0, 2)

    packs_before = _counter("devcost.tile_layout.packs")
    glm_out = os.path.join(out_dir, "glm")
    t0 = time.perf_counter()
    train_glm_cli.main([
        "--task", task.value, "--train-data", path, "--format", "libsvm",
        "--weights", "1.0", "--max-iterations", str(iters),
        "--tolerance", "0", "--output-dir", glm_out,
        "--telemetry-dir", os.path.join(out_dir, "telemetry"),
    ])
    facts["train_s"] = round(time.perf_counter() - t0, 2)
    facts["tile_layout_packs"] = _counter("devcost.tile_layout.packs") - packs_before
    facts["interpret"] = st._interpret()
    _check(facts["tile_layout_packs"] >= 1, "the driver did not tile the batch")
    _check(
        facts["interpret"] == (jax.default_backend() == "cpu"),
        "the tile-COO kernel ran in interpreter mode off the CPU backend",
    )

    # the same solve on the untiled XLA gather/scatter path
    batch, intercept = read_libsvm(path)
    _check(
        batch.num_features == d and batch.indices.shape[1] == k,
        f"batch is {batch.num_features} x {batch.indices.shape[1]}, "
        f"wanted {d} x {k}",
    )
    ref = train_glm(
        batch, task,
        optimizer_config=OptimizerConfig(max_iterations=iters, tolerance=0.0),
        regularization_weights=[1.0], intercept_index=intercept,
    )
    obj = make_objective(
        batch, loss_for_task(task), l2_weight=1.0, intercept_index=intercept
    )
    w_cli = jnp.asarray(
        load_glm(
            os.path.join(glm_out, "best", "model.avro"), num_features=d
        ).coefficients.means,
        jnp.float32,
    )
    loss_cli = float(obj.value(w_cli))
    loss_ref = float(obj.value(ref.best_model.coefficients.means))
    loss_zero = float(obj.value(jnp.zeros((d,), jnp.float32)))
    facts.update(
        final_loss_tiled=loss_cli, final_loss_xla=loss_ref,
        initial_loss=loss_zero, rel_diff=_rel(loss_cli, loss_ref),
    )
    _check(
        np.isfinite(loss_cli) and loss_cli < loss_zero,
        f"tiled solve did not reduce the loss: {loss_zero} -> {loss_cli}",
    )
    _check(
        facts["rel_diff"] <= rel_tol,
        f"tiled final loss {loss_cli} vs XLA {loss_ref}: rel "
        f"{facts['rel_diff']:.3g} > {rel_tol}",
    )

    # storage rungs at the same width: products against the XLA path,
    # tolerances as documented in tests/test_kernel_dtype.py
    rng = np.random.default_rng(seed + 1)
    w = jnp.asarray(rng.normal(size=d).astype(np.float32))
    r = jnp.asarray(rng.normal(size=n).astype(np.float32))
    want = (np.asarray(batch.matvec(w)), np.asarray(batch.rmatvec(r)))
    rungs = {}
    prev = os.environ.get("PHOTON_KERNEL_DTYPE")
    try:
        for rung, tol in (("f32", 1e-5), ("int8", 6e-2)):
            os.environ["PHOTON_KERNEL_DTYPE"] = rung
            tb = st.tile_sparse_batch(batch)
            err = max(
                _rel(np.asarray(tb.matvec(w)), want[0]),
                _rel(np.asarray(tb.rmatvec(r)), want[1]),
            )
            rungs[rung] = {"max_rel_err": err, "tolerance": tol}
            _check(err <= tol, f"rung {rung}: rel err {err:.3g} > {tol}")
    finally:
        if prev is None:
            os.environ.pop("PHOTON_KERNEL_DTYPE", None)
        else:
            os.environ["PHOTON_KERNEL_DTYPE"] = prev
    facts["rungs"] = rungs
    return facts


# ---------------------------------------------------------------- dense leg


def _dense_problem(n, d, seed, dtype):
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops.batch import DenseBatch

    @jax.jit
    def make(key):
        k1, k2, k3 = jax.random.split(key, 3)
        X = jax.random.normal(k1, (n, d), jnp.float32)
        X = X.at[:, d - 1].set(1.0)
        w_true = jax.random.normal(k2, (d,), jnp.float32) * 0.5
        p = jax.nn.sigmoid(X @ w_true)
        y = (jax.random.uniform(k3, (n,)) < p).astype(jnp.float32)
        return X.astype(dtype), y

    X, y = make(jax.random.PRNGKey(seed))
    return DenseBatch(
        X=X, labels=y, offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32),
    )


def leg_dense(
    *, n: int, d: int, lbfgs_iters: int, tron_iters: int, seed: int = 0,
) -> dict:
    """The fused one-pass kernels at the headline shape, library level as
    the bench headline runs them: value, gradient and Hv against the
    unfused XLA objective on the same data, then short L-BFGS and TRON
    solves. The f32 reference runs at highest matmul precision (a TPU's
    default f32 matmul is a single bf16 pass).

    Each comparison runs twice. On bf16-representable probe vectors the
    issue's bounds hold (1e-3 bf16, 1e-5 f32): what is compared is the
    kernel's arithmetic. On generic f32 vectors the bf16 bound is 4e-3
    (2^-8): under bf16 storage both paths are documented to feed the MXU
    the vector operand in bf16, but XLA keeps the f32 vector where it can
    (excess precision) while the kernel really rounds it, to 2^-9 relative
    an element; that alone moved the gradient by 1.2e-3 and Hv by 2.6e-3
    on the chip (PR 21). A change in how either kernel treats the vector
    operand shows there."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.ops.glm import make_objective
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.optim import lbfgs_minimize
    from photon_ml_tpu.optim.tron import tron_minimize
    from photon_ml_tpu.types import TaskType

    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    rng = np.random.default_rng(seed)
    w_any = jnp.asarray((rng.normal(size=d) * 0.1).astype(np.float32))
    v_any = jnp.asarray(rng.normal(size=d).astype(np.float32))
    as_bf16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    w0 = jnp.zeros((d,), jnp.float32)
    # the objective is a pytree ARGUMENT: closed over, its (n, d) matrix
    # would be baked into the program as a constant (428 s of compile at
    # the headline shape on the first chip run)
    value_and_grad = jax.jit(lambda obj, w: obj.value_and_grad(w))
    hvp = jax.jit(lambda obj, w, v: obj.hvp(w, v))

    def compare(fused, plain, w, v):
        f_val, f_grad = value_and_grad(fused, w)
        f_hv = hvp(fused, w, v)
        with jax.default_matmul_precision("highest"):
            p_val, p_grad = value_and_grad(plain, w)
            p_hv = hvp(plain, w, v)
        return {
            "value": _rel(f_val, p_val), "grad": _rel(f_grad, p_grad),
            "hvp": _rel(f_hv, p_hv),
        }

    facts: dict = {}
    for name, dtype, tol, tol_any in (
        ("bf16", jnp.bfloat16, 1e-3, 4e-3), ("f32", jnp.float32, 1e-5, 1e-5),
    ):
        batch = _dense_problem(n, d, seed, dtype)
        kw = dict(l2_weight=1.0, intercept_index=d - 1, data_hints=(True, True))
        fused = make_objective(batch, loss, fused=True, **kw)
        plain = make_objective(batch, loss, fused=False, **kw)
        errs = compare(fused, plain, as_bf16(w_any), as_bf16(v_any))
        errs_any = compare(fused, plain, w_any, v_any)
        lb = lbfgs_minimize(
            fused, w0, OptimizerConfig(max_iterations=lbfgs_iters, tolerance=0.0)
        )
        tr = tron_minimize(
            fused, w0, OptimizerConfig(max_iterations=tron_iters, tolerance=0.0)
        )
        start = float(fused.value(w0))
        facts[name] = dict(
            rel_err=errs, tolerance=tol, rel_err_generic_vectors=errs_any,
            tolerance_generic_vectors=tol_any, initial_loss=start,
            lbfgs_loss=float(lb.value), lbfgs_iterations=int(lb.iterations),
            tron_loss=float(tr.value), tron_iterations=int(tr.iterations),
        )
        _check(
            all(e <= tol for e in errs.values()),
            f"{name}: fused vs unfused rel err {errs} > {tol}",
        )
        _check(
            all(e <= tol_any for e in errs_any.values()),
            f"{name}: fused vs unfused rel err on generic vectors "
            f"{errs_any} > {tol_any}",
        )
        for solver, res in (("lbfgs", lb), ("tron", tr)):
            _check(
                np.isfinite(float(res.value)) and float(res.value) < start
                and bool(np.all(np.isfinite(np.asarray(res.w)))),
                f"{name} {solver}: loss {start} -> {float(res.value)}",
            )
        del batch, fused, plain
    return facts


# ---------------------------------------------------------------- distributed


def leg_distributed(
    *, n_dense: int, d_dense: int, n_sparse: int, d_sparse: int, k: int,
    iters: int, seed: int = 3, rel_tol: float = 1e-4,
) -> dict:
    """``DistributedTrainer`` over every visible device: the fused kernel
    inside ``shard_map`` (dense) and one tile-COO per shard (sparse), each
    against the one-device solve of the same problem."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.ops.batch import SparseBatch
    from photon_ml_tpu.ops.glm import make_objective
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.ops.sparse_tiled import tile_sparse_batch
    from photon_ml_tpu.optim import lbfgs_minimize
    from photon_ml_tpu.parallel import DistributedTrainer, data_mesh
    from photon_ml_tpu.types import TaskType

    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    cfg = OptimizerConfig(max_iterations=iters, tolerance=0.0)
    mesh = data_mesh()
    facts: dict = {"devices": int(mesh.size)}

    def compare(name, batch, single_batch, d):
        w0 = jnp.zeros((d,), jnp.float32)
        trainer = DistributedTrainer(mesh=mesh, config=cfg, loss=loss, l2_weight=1.0)
        dist = trainer.train(batch, w0)
        single = lbfgs_minimize(
            make_objective(single_batch, loss, l2_weight=1.0), w0, cfg
        )
        rel = _rel(float(dist.value), float(single.value))
        facts[name] = dict(
            loss_mesh=float(dist.value), loss_one_device=float(single.value),
            rel_diff=rel, iterations=int(dist.iterations),
        )
        _check(
            np.isfinite(float(dist.value)) and rel <= rel_tol,
            f"{name}: mesh loss {float(dist.value)} vs one-device "
            f"{float(single.value)} (rel {rel:.3g} > {rel_tol})",
        )

    dense = _dense_problem(n_dense, d_dense, seed, jnp.float32)
    compare("dense", dense, dense, d_dense)
    del dense

    rng = np.random.default_rng(seed)
    idx = rng.integers(0, d_sparse, size=(n_sparse, k)).astype(np.int32)
    val = rng.normal(size=(n_sparse, k)).astype(np.float32)
    w_true = (rng.normal(size=d_sparse) * 0.3).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-np.sum(val * w_true[idx], axis=1)))
    sparse = SparseBatch(
        indices=jnp.asarray(idx), values=jnp.asarray(val),
        labels=jnp.asarray((rng.uniform(size=n_sparse) < p).astype(np.float32)),
        offsets=jnp.zeros((n_sparse,), jnp.float32),
        weights=jnp.ones((n_sparse,), jnp.float32), num_features=d_sparse,
    )
    compare("sparse", sparse, tile_sparse_batch(sparse), d_sparse)
    return facts


# ---------------------------------------------------------------- runner


def run_legs(out_dir: str, sizes: dict) -> dict:
    """Run every leg in this one process; a failing leg is recorded and
    the rest still run, so one chip call reports everything it can."""
    legs = {
        "game": lambda: leg_game(os.path.join(out_dir, "game"), **sizes["game"]),
        "sparse": lambda: leg_sparse(os.path.join(out_dir, "sparse"), **sizes["sparse"]),
        "dense": lambda: leg_dense(**sizes["dense"]),
        "distributed": lambda: leg_distributed(**sizes["distributed"]),
    }
    ev = _events()
    results = {}
    for name, fn in legs.items():
        print(f"[chip_smoke] leg {name} ...", file=sys.stderr, flush=True)
        t0, c0 = time.perf_counter(), ev.compile_s
        try:
            facts, ok, err = fn(), True, None
        except Exception as e:  # the boundary: record, report, keep going
            traceback.print_exc()
            facts, ok, err = {}, False, f"{type(e).__name__}: {e}"
        results[name] = {
            "ok": ok,
            "seconds": round(time.perf_counter() - t0, 2),
            "compile_seconds": round(ev.compile_s - c0, 2),
            **({"error": err} if err else {}),
            **facts,
        }
        print(
            f"[chip_smoke] leg {name}: {json.dumps(results[name])}",
            file=sys.stderr, flush=True,
        )
    return results


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", default=DEFAULT_OUT, help="output directory")
    args = p.parse_args(argv)

    import jax

    platform = jax.default_backend()
    if platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, JAX reports platform={platform!r}; "
            "there is no CPU fallback (tests/test_chip_smoke.py runs the "
            "legs tiny on CPU)",
            file=sys.stderr,
        )
        return 2
    devices = jax.devices()
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(
        f"chip_smoke: platform: {device['platform']}  device_kind: "
        f"{device['kind']}  devices: {device['count']}  jax: {jax.__version__}",
        flush=True,
    )

    from photon_ml_tpu.native.build import native_available
    from photon_ml_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    ev = _events()
    t0 = time.perf_counter()
    os.makedirs(args.out, exist_ok=True)
    legs = run_legs(args.out, FULL_SIZES)
    # the inputs are regenerated from the seed; what stays is small enough
    # for a chip run to bring back
    for rel in ("game/train.avro", "game/val.avro", "sparse/train.libsvm"):
        path = os.path.join(args.out, rel)
        if os.path.exists(path):
            os.remove(path)
    summary = {
        "ok": all(leg["ok"] for leg in legs.values()),
        "device": device,
        "jax": jax.__version__,
        "seconds": round(time.perf_counter() - t0, 2),
        "compile_seconds": round(ev.compile_s, 2),
        "compile_cache": {
            "dir": cache_dir, "hits": ev.cache_hits, "misses": ev.cache_misses,
        },
        "native_available": bool(native_available()),
        "legs": legs,
    }
    report(summary, args.out)
    return 0 if summary["ok"] else 1


def report(summary: dict, out_dir: str) -> None:
    """Write the full summary to ``<out_dir>/chip_smoke.json`` and to
    stdout, then the verdict as the LAST stdout line: one JSON object with
    exactly ``ok`` and ``device`` (``platform``, ``kind``, ``count``) —
    the line the chip check parses, so nothing else may ride on it."""
    detail = json.dumps(summary)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        f.write(detail + "\n")
    print(f"chip_smoke: summary: {detail}", flush=True)
    d = summary["device"]
    verdict = {
        "ok": bool(summary["ok"]),
        "device": {
            "platform": str(d["platform"]), "kind": str(d["kind"]),
            "count": int(d["count"]),
        },
    }
    print(json.dumps(verdict), flush=True)


if __name__ == "__main__":
    sys.exit(main())
