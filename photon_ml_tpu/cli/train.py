"""GAME training driver.

Reference parity: ``photon-client::ml.cli.game.training.GameTrainingDriver``
(SURVEY.md §2.3, §3.1). Stages: read data → feature/entity maps → (optional)
validation read against frozen maps → warm start → estimator grid fit →
(optional) Bayesian hyperparameter loop → model selection → write models +
index/entity maps + metrics.

Usage:
    python -m photon_ml_tpu.cli.train \\
        --config config.json --train-data data/train \\
        [--validation-data data/val] --output-dir out/
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os

from photon_ml_tpu.cli.common import load_training_config
from photon_ml_tpu.config import GameTrainingConfig
from photon_ml_tpu.estimators import GameEstimator, GameResult
from photon_ml_tpu.game.models import GameModel
from photon_ml_tpu.io.data_reader import AvroDataReader, GameDataset
from photon_ml_tpu.io.model_io import load_game_model, save_game_model
from photon_ml_tpu.obs import span
from photon_ml_tpu.types import ModelOutputMode
from photon_ml_tpu.utils import PhotonLogger, profile_trace, timed
from photon_ml_tpu.utils.compile_cache import configure_compile_cache


def run(
    config: GameTrainingConfig,
    train_data: list[str],
    output_dir: str,
    validation_data: list[str] | None = None,
    index_map_dir: str | None = None,
    logger: PhotonLogger | None = None,
    mesh=None,
    profile_dir: str | None = None,
    diagnostics: bool = False,
    streaming_chunk_rows: int | None = None,
    multihost: bool = False,
) -> "GameResult | GameModel":
    """Returns the in-memory grid's best ``GameResult``, or — when
    ``streaming_chunk_rows`` selects the out-of-core branch — the trained
    ``GameModel`` (the streamed path has no configuration grid to select
    over). Auto-selection of streaming happens only in the CLI ``main``
    (where nobody consumes the return value); library callers choose the
    branch — and therefore the return type — explicitly."""
    logger = logger or PhotonLogger(output_dir)
    if streaming_chunk_rows is not None:
        return _run_streamed_game(
            config, train_data, output_dir,
            validation_data=validation_data,
            chunk_rows=streaming_chunk_rows,
            logger=logger,
            multihost=multihost,
            profile_dir=profile_dir,
        )
    id_tags = _game_id_tags(config)
    reader = AvroDataReader(config.feature_shards or None)

    # prepareFeatureMaps parity: load prebuilt index stores when given
    # (FeatureIndexingDriver output), else build from the data
    prebuilt = None
    if index_map_dir:
        from photon_ml_tpu.data.index_map import IndexMap

        prebuilt = {
            fn[:-4]: IndexMap.load(os.path.join(index_map_dir, fn))
            for fn in os.listdir(index_map_dir)
            if fn.endswith(".npz")
        }
        logger.info(f"loaded index maps: { {s: m.size for s, m in prebuilt.items()} }")

    # Warm start: re-use the saved run's entity maps so the saved model's
    # dense entity rows stay valid; new entities get appended ids.
    warm_tag_maps = (
        _load_entity_maps(config.model_input_dir) if config.model_input_dir else None
    )
    with timed(logger, "read training data"), span("ingest/train-data"):
        train = reader.read(
            train_data,
            id_tags=id_tags,
            index_maps=prebuilt,
            entity_maps=warm_tag_maps,
            extend_entities=warm_tag_maps is not None,
            mesh=mesh,
        )
        logger.info(
            f"train: {train.batch.num_real_rows} rows, shards "
            f"{ {s: m.size for s, m in train.index_maps.items()} }"
        )

    val: GameDataset | None = None
    if validation_data:
        with timed(logger, "read validation data"), span(
            "ingest/validation-data"
        ):
            val = reader.read(
                validation_data,
                id_tags=id_tags,
                index_maps=train.index_maps,
                entity_maps=train.entity_maps,
            )

    initial_model = None
    if config.model_input_dir:
        with timed(logger, "load warm-start model"):
            entity_ids = None
            if warm_tag_maps:
                # entity-maps.json is keyed by id tag; the loader wants
                # coordinate id → (entity string → dense id)
                entity_ids = {
                    cid: warm_tag_maps[c.random_effect_type]
                    for cid, c in config.random_effect_coordinates.items()
                    if c.random_effect_type in warm_tag_maps
                }
            initial_model = load_game_model(
                config.model_input_dir,
                index_maps=train.index_maps,
                entity_ids=entity_ids,
            )
            initial_model = _pad_random_effects(initial_model, train, config)

    estimator = GameEstimator(
        config,
        mesh=mesh,
        intercept_indices=train.intercept_indices,
        logger=logger,
    )
    with timed(logger, "estimator grid fit"), profile_trace(
        profile_dir, "grid-fit"
    ), span("train/grid-fit"):
        results = estimator.fit(
            train.batch,
            None if val is None else val.batch,
            initial_model=initial_model,
            checkpoint_dir=os.path.join(output_dir, "checkpoints"),
        )

    if config.hyperparameter_tuning_iters > 0:
        if val is None:
            raise ValueError("hyperparameter tuning requires validation data")
        from photon_ml_tpu.hyperparameter.tuning import tune_game_hyperparameters

        with timed(logger, "hyperparameter tuning"):
            results = list(results) + tune_game_hyperparameters(
                estimator,
                train.batch,
                val.batch,
                results,
                config.hyperparameter_tuning_iters,
            )

    best = estimator.select_best(results)
    logger.info(f"selected configuration: { {c: o.regularization_weight for c, o in best.configuration.items()} }")

    # every process computes; exactly ONE writes the shared outputs —
    # concurrent writers to the same shared-storage paths corrupt files
    from photon_ml_tpu.parallel.multihost import is_output_process, sync_processes

    if is_output_process():
        with timed(logger, "write models"):
            entity_names = train.entity_names()
            by_cid = {
                cid: entity_names[cfg.random_effect_type]
                for cid, cfg in config.random_effect_coordinates.items()
            }
            save_game_model(
                best.model,
                os.path.join(output_dir, "best"),
                index_maps=train.index_maps,
                entity_names=by_cid,
            )
            if config.output_mode is ModelOutputMode.ALL:
                for i, r in enumerate(results):
                    save_game_model(
                        r.model,
                        os.path.join(output_dir, "models", f"{i:04d}"),
                        index_maps=train.index_maps,
                        entity_names=by_cid,
                    )
            _save_maps(output_dir, train)

        metrics = {
            "results": [
                {
                    "configuration": {
                        cid: opt.to_dict() for cid, opt in r.configuration.items()
                    },
                    "metrics": dict(r.evaluation.metrics) if r.evaluation else None,
                }
                for r in results
            ],
            # identity, not ==: GameResult holds device arrays (ambiguous __eq__)
            "best_index": next(i for i, r in enumerate(results) if r is best),
        }
        with open(os.path.join(output_dir, "metrics.json"), "w") as f:
            json.dump(metrics, f, indent=2)
        if diagnostics:
            from photon_ml_tpu.diagnostics import game_diagnostics, write_report

            with timed(logger, "write diagnostics"):
                write_report(
                    game_diagnostics(
                        results, config=config, index_maps=train.index_maps
                    ),
                    output_dir,
                )
    sync_processes("train-outputs-written")
    return best



def _game_id_tags(config: GameTrainingConfig) -> tuple[str, ...]:
    """Id-tag columns the datums must carry: every random-effect type PLUS
    every grouped evaluator's group-by tag — the reference's Multi*
    evaluators group on ANY datum id tag, not only coordinate entity
    types (SURVEY §2.2 evaluators row), so a validation-only tag must be
    extracted (and its entity map saved) too."""
    from photon_ml_tpu.evaluation import make_evaluator

    tags = [
        c.random_effect_type
        for c in config.random_effect_coordinates.values()
    ]
    for spec in config.evaluators:
        gb = make_evaluator(spec).group_by
        if gb is not None:
            tags.append(gb)
    return tuple(dict.fromkeys(tags))


def _streamed_unsupported(config: GameTrainingConfig) -> list[str]:
    """Config features the out-of-core branch rejects (used both to fail
    fast on an EXPLICIT --streaming-chunk-rows and to veto AUTO-selection
    — auto-streaming must never turn a runnable in-memory job into a
    ValueError). Round 5 closed the last entries (FULL variance now
    chunk-accumulates the d×d Hessian; incremental MAP priors fold into
    the streamed objectives like L2), so nothing is rejected today; the
    hook stays for future combinations."""
    return []


def _config_with_optimizations(
    config: GameTrainingConfig, configuration: dict
) -> GameTrainingConfig:
    """The training config with each coordinate's optimization replaced by
    the grid/tuning entry's (the streamed twin of the estimator's
    per-configuration coordinate rebuild)."""
    fixed = {
        cid: dataclasses.replace(
            c, optimization=configuration.get(cid, c.optimization)
        )
        for cid, c in config.fixed_effect_coordinates.items()
    }
    rand = {
        cid: dataclasses.replace(
            c, optimization=configuration.get(cid, c.optimization)
        )
        for cid, c in config.random_effect_coordinates.items()
    }
    return dataclasses.replace(
        config,
        fixed_effect_coordinates=fixed,
        random_effect_coordinates=rand,
    )


def _should_auto_stream(
    train_data: list[str], config: GameTrainingConfig, logger,
    has_validation: bool = True,
) -> bool:
    """Auto-select the out-of-core path when the raw input bytes already
    exceed the queried HBM budget of every visible device together
    (per-device ``device_hbm_budget_bytes`` — ``memory_stats`` on an
    accelerator, the 8 GB default on the CPU backend only — times the
    global device count: ``main`` trains over a mesh of all of them,
    multihost or not). Avro is more compact
    than the decoded f32 columns, so raw bytes > budget means the
    in-memory read is guaranteed to blow HBM; smaller inputs keep the
    in-memory fast path. Sizes EXACTLY the file set the readers will read
    (``list_avro_files`` policy), so the gate and the ingest can never
    disagree on what the dataset is. Configs the streamed branch rejects
    are never auto-streamed — a warning is logged instead."""
    import jax

    from photon_ml_tpu.ops.streaming import device_hbm_budget_bytes

    try:
        total = sum(os.path.getsize(f) for f in _expand_part_files(train_data))
    except (FileNotFoundError, OSError):
        return False  # let the reader raise its usual error
    budget = device_hbm_budget_bytes() * max(len(jax.devices()), 1)
    if total <= budget:
        return False
    unsupported = _streamed_unsupported(config)
    if not has_validation and (
        config.hyperparameter_tuning_iters > 0
        or config.regularization_weight_grid
    ):
        # the streamed grid/tuning loop selects by validation metric; the
        # in-memory path tolerates the absence (select_best falls back)
        unsupported = unsupported + [
            "regularization grids / hyperparameter tuning without "
            "--validation-data"
        ]
    if unsupported:
        logger.info(
            f"input bytes {total:.3g} exceed the cluster HBM budget "
            f"{budget:.3g} but the configuration uses "
            f"{', '.join(unsupported)}, which the streamed path does not "
            f"support — keeping the in-memory path (expect device OOM if "
            f"the estimate is right)"
        )
        return False
    logger.info(
        f"input bytes {total:.3g} exceed the cluster HBM budget "
        f"{budget:.3g}: auto-selecting the out-of-core streamed path "
        f"(pass --streaming-chunk-rows to control the chunk size, or "
        f"--no-auto-streaming to force in-memory)"
    )
    return True


def _run_streamed_game(
    config: GameTrainingConfig,
    train_data: list[str],
    output_dir: str,
    validation_data: list[str] | None,
    chunk_rows: int,
    logger: PhotonLogger,
    multihost: bool,
    profile_dir: str | None,
):
    """Out-of-core GAME branch: SURVEY.md §3.1's call stack with host-RAM
    data residency (the road to the 1B-row north star — VERDICT r2 missing
    #1). Stats pass over ALL files on every host (identical dictionaries);
    fill pass over THIS host's file slice; streamed coordinate descent with
    per-visit checkpoints; process 0 writes outputs."""
    from photon_ml_tpu.game.streaming import StreamedGameTrainer
    from photon_ml_tpu.parallel.multihost import (
        host_shard_of_paths,
        is_output_process,
        sync_processes,
    )

    unsupported = _streamed_unsupported(config)
    if unsupported:
        raise ValueError(
            "--streaming-chunk-rows does not support: " + ", ".join(unsupported)
        )

    id_tags = _game_id_tags(config)
    reader = AvroDataReader(config.feature_shards or None)
    train_paths = _expand_part_files(train_data)
    # warm start: seed the entity dictionaries with the saved run's maps so
    # the saved model's dense entity rows stay valid (new entities append)
    warm_tag_maps = (
        _load_entity_maps(config.model_input_dir) if config.model_input_dir else None
    )
    with timed(logger, "streaming stats pass (all files)"), span(
        "ingest/stats-pass", files=len(train_paths)
    ):
        index_maps, max_nnz, entity_maps, n_global = (
            reader.streaming_game_stats(
                train_paths, id_tags, entity_maps=warm_tag_maps
            )
        )
    logger.info(
        f"streamed GAME: {n_global} global rows, shards "
        f"{ {s: m.size for s, m in index_maps.items()} }, entities "
        f"{ {t: len(m) for t, m in entity_maps.items()} }"
    )
    local_paths = train_paths
    if multihost:
        local_paths = host_shard_of_paths(train_paths)
        logger.info(f"this host fills {len(local_paths)}/{len(train_paths)} files")

    with timed(logger, "fill pass (this host's files)"), span(
        "ingest/fill-pass", files=len(local_paths)
    ):
        # allow_empty under multihost: with fewer part files than
        # processes a host's slice is empty, but it MUST still build a
        # 0-row dataset and join every collective in the trainer —
        # returning early would deadlock the other hosts
        data = reader.read_streamed_game(
            local_paths, id_tags, index_maps, entity_maps, max_nnz=max_nnz,
            allow_empty=multihost,
        )

    vdata = None
    if validation_data:
        val_paths = _expand_part_files(validation_data)
        local_val = host_shard_of_paths(val_paths) if multihost else val_paths
        with timed(logger, "fill validation (this host's files)"), span(
            "ingest/fill-validation", files=len(local_val)
        ):
            vdata = reader.read_streamed_game(
                local_val, id_tags, index_maps, entity_maps,
                max_nnz=max_nnz, unseen_entity_ok=True,
                allow_empty=multihost,
            )

    initial_model = None
    if config.model_input_dir:
        with timed(logger, "load warm-start model"):
            entity_ids = None
            if warm_tag_maps:
                entity_ids = {
                    cid: warm_tag_maps[c.random_effect_type]
                    for cid, c in config.random_effect_coordinates.items()
                    if c.random_effect_type in warm_tag_maps
                }
            initial_model = load_game_model(
                config.model_input_dir,
                index_maps=index_maps,
                entity_ids=entity_ids,
            )
            # new entities (absent from the saved run) cold-start from
            # zero rows, like the in-memory warm-start path
            import jax.numpy as jnp

            from photon_ml_tpu.game.models import RandomEffectModel

            for cid, c in config.random_effect_coordinates.items():
                sub = initial_model.models.get(cid)
                if not isinstance(sub, RandomEffectModel):
                    continue
                e_new = len(entity_maps[c.random_effect_type])
                if sub.num_entities < e_new:
                    pad = e_new - sub.num_entities
                    W = jnp.concatenate(
                        [sub.coefficients,
                         jnp.zeros((pad, sub.coefficients.shape[1]),
                                   sub.coefficients.dtype)]
                    )
                    initial_model = initial_model.updated(
                        cid, dataclasses.replace(
                            sub, coefficients=W, variances=None
                        )
                    )

    intercepts = {sid: m.intercept_index for sid, m in index_maps.items()}
    num_entities = {t: len(m) for t, m in entity_maps.items()}
    from photon_ml_tpu.estimators import build_configuration_grid
    from photon_ml_tpu.evaluation import make_evaluator
    from photon_ml_tpu.evaluation.evaluators import DEFAULT_EVALUATOR_BY_TASK

    grid = build_configuration_grid(config)
    multi_entry = len(grid) > 1 or config.hyperparameter_tuning_iters > 0
    if multi_entry and vdata is None:
        raise ValueError(
            "regularization grids / hyperparameter tuning on the streamed "
            "path select by validation metric — pass --validation-data"
        )
    # same evaluator fallback as the estimator: an empty evaluators tuple
    # means the task's default metric, not "no validation"
    specs = tuple(config.evaluators) or (
        DEFAULT_EVALUATOR_BY_TASK[config.task_type],
    )
    primary_ev = make_evaluator(specs[0])

    # only the CURRENT BEST entry's model/trainer stay alive — a grid over
    # the out-of-core path must not accumulate per-entry models in the
    # host RAM the dataset already needs
    best: dict | None = None
    summaries: list[dict] = []

    def fit_entry(configuration, tag):
        """One full streamed descent under this grid entry's per-coordinate
        optimization configs; per-entry checkpoint directory so the
        fingerprint guard never thrashes between entries. Returns the
        entry's validation primary (None without validation data)."""
        nonlocal best
        cfg_e = _config_with_optimizations(config, configuration)
        ck_dir = (
            os.path.join(output_dir, "checkpoints", tag)
            if multi_entry else os.path.join(output_dir, "checkpoints")
        )
        if any(
            c.random_projection_dim is not None
            for c in config.random_effect_coordinates.values()
        ):
            # projected descent state does not round-trip the
            # original-space checkpoint; the trainer rejects the combo
            logger.info(
                "random-projected coordinates: checkpoint/resume disabled "
                "for the streamed descent"
            )
            ck_dir = None
        trainer = StreamedGameTrainer(
            cfg_e,
            chunk_rows=chunk_rows,
            intercept_indices=intercepts,
            logger=logger.info,
            multihost=multihost,
            checkpoint_dir=ck_dir,
            evaluators=specs if vdata is not None else (),
            num_entities=num_entities,
        )
        with span(
            "train/grid-entry", tag=tag,
            weights={
                cid: float(o.regularization_weight)
                for cid, o in configuration.items()
            },
        ):
            m, inf = trainer.fit(
                data, validation=vdata, initial_model=initial_model
            )
        primary = None
        if trainer.validation_history:
            (_, last_res), = trainer.validation_history[-1].items()
            primary = last_res.primary
        summaries.append({"configuration": configuration, "primary": primary})
        entry = {
            "model": m, "info": inf, "trainer": trainer,
            "configuration": configuration, "primary": primary,
            "index": len(summaries) - 1,
        }
        if best is None or (
            primary is not None
            and (
                best["primary"] is None
                or primary_ev.better(primary, best["primary"])
            )
        ):
            best = entry  # the previous best's model/trainer drop here
        return primary

    with timed(logger, "streamed coordinate descent"), profile_trace(
        profile_dir, "streamed-game"
    ), span("train/streamed-descent", grid_entries=len(grid)):
        for i, configuration in enumerate(grid):
            fit_entry(configuration, f"grid-{i:04d}")
        if config.hyperparameter_tuning_iters > 0:
            from photon_ml_tpu.hyperparameter.tuning import gp_tune_weights

            cids = list(config.coordinate_update_sequence)
            prior = [
                (
                    {
                        cid: s["configuration"][cid].regularization_weight
                        for cid in cids
                    },
                    s["primary"],
                )
                for s in summaries
                if s["primary"] is not None
            ]

            def evaluate(weights, it):
                configuration = {
                    cid: dataclasses.replace(
                        config.coordinate_config(cid).optimization,
                        regularization_weight=weights[cid],
                    )
                    for cid in cids
                }
                return fit_entry(configuration, f"tune-{it:04d}")

            with timed(logger, "streamed hyperparameter tuning"):
                gp_tune_weights(
                    cids, prior, config.hyperparameter_tuning_iters,
                    evaluate, primary_ev.larger_is_better,
                )

    if multi_entry:
        logger.info(
            "selected streamed configuration: "
            f"{ {c: o.regularization_weight for c, o in best['configuration'].items()} } "
            f"(primary {best['primary']})"
        )
    model, info, trainer = best["model"], best["info"], best["trainer"]

    if is_output_process():
        with timed(logger, "write models"):
            entity_names: dict[str, list[str]] = {}
            for tag, m in entity_maps.items():
                names = [""] * len(m)
                for s, i in m.items():
                    names[i] = s
                entity_names[tag] = names
            by_cid = {
                cid: entity_names[cfg.random_effect_type]
                for cid, cfg in config.random_effect_coordinates.items()
            }
            save_game_model(
                model,
                os.path.join(output_dir, "best"),
                index_maps=index_maps,
                entity_names=by_cid,
            )
            for sid, imap in index_maps.items():
                imap.save(os.path.join(output_dir, "index-maps", sid))
            with open(os.path.join(output_dir, "entity-maps.json"), "w") as f:
                json.dump(entity_maps, f)
        metrics_path = os.path.join(output_dir, "metrics.json")
        # MERGE with any previous run's metrics: a resumed run only
        # revisits the remaining coordinates and restarts its validation
        # history at the resume point — the pre-resume diagnostics live
        # only in the file written before the interruption
        old: dict = {}
        if trainer.resumed_from is not None and os.path.exists(metrics_path):
            # merge only on a genuine resume; a from-scratch rerun (fresh
            # training, or a rejected-fingerprint retrain) REPLACES
            try:
                with open(metrics_path) as f:
                    old = json.load(f)
            except (OSError, json.JSONDecodeError):
                old = {}
        if info or not old:
            coordinates = dict(old.get("coordinates", {}))
            coordinates.update(
                {
                    cid: {
                        "final_loss": ci.final_loss,
                        "iterations": ci.iterations,
                        "converged": ci.converged,
                    }
                    for cid, ci in info.items()
                }
            )
            metrics = {
                "streaming_chunk_rows": chunk_rows,
                "coordinates": coordinates,
                "validation_history": list(old.get("validation_history", []))
                + [
                    {cid: dict(res.metrics) for cid, res in entry.items()}
                    for entry in trainer.validation_history
                ],
            }
            if multi_entry:
                metrics["results"] = [
                    {
                        "configuration": {
                            cid: opt.to_dict()
                            for cid, opt in s["configuration"].items()
                        },
                        "primary": s["primary"],
                    }
                    for s in summaries
                ]
                metrics["best_index"] = best["index"]
            with open(metrics_path, "w") as f:
                json.dump(metrics, f, indent=2)
        else:
            # resume landed past the final iteration (the job had already
            # completed): no visits ran, so the existing metrics.json holds
            # the real run's diagnostics — don't overwrite it with emptiness
            logger.info(
                "checkpoint shows training already complete; keeping the "
                "existing metrics.json"
            )
    sync_processes("streamed-game-outputs-written")
    return model


def _expand_part_files(paths: list[str]) -> list[str]:
    """Directories become their sorted ``*.avro`` part files (the shared
    ``list_avro_files`` policy — the same file set every reader sees), so
    per-host path sharding distributes FILES, not whole directories."""
    from photon_ml_tpu.io.avro import list_avro_files

    return [f for p in paths for f in list_avro_files(p)]


def _pad_random_effects(model, train: GameDataset, config: GameTrainingConfig):
    """Grow each warm-start random-effect matrix to the current entity count
    (new entities start from zero rows — the reference also cold-starts
    entities absent from the loaded model)."""
    import jax.numpy as jnp

    from photon_ml_tpu.game.models import RandomEffectModel

    for cid, c in config.random_effect_coordinates.items():
        sub = model.models.get(cid)
        if not isinstance(sub, RandomEffectModel):
            continue
        e_new = len(train.entity_maps[c.random_effect_type])
        if sub.num_entities < e_new:
            pad = e_new - sub.num_entities
            W = jnp.concatenate(
                [sub.coefficients, jnp.zeros((pad, sub.coefficients.shape[1]),
                                             sub.coefficients.dtype)]
            )
            V = sub.variances
            if V is not None:
                V = jnp.concatenate([V, jnp.zeros((pad, V.shape[1]), V.dtype)])
            import dataclasses

            model = model.updated(
                cid, dataclasses.replace(sub, coefficients=W, variances=V)
            )
    return model


def _save_maps(output_dir: str, ds: GameDataset) -> None:
    """Persist the ingest dictionaries next to the model so scoring and
    warm starts line columns/entities up (the reference ships PalDB stores
    and entity-id RDDs the same way)."""
    for sid, imap in ds.index_maps.items():
        imap.save(os.path.join(output_dir, "index-maps", sid))
    with open(os.path.join(output_dir, "entity-maps.json"), "w") as f:
        json.dump(ds.entity_maps, f)


def _load_entity_maps(model_dir: str) -> dict | None:
    # entity maps live one level above the model dir when written by run()
    for candidate in (
        os.path.join(model_dir, "entity-maps.json"),
        os.path.join(os.path.dirname(model_dir.rstrip("/")), "entity-maps.json"),
    ):
        if os.path.exists(candidate):
            with open(candidate) as f:
                raw = json.load(f)
            return raw
    return None


def main(argv: list[str] | None = None) -> None:
    configure_compile_cache()
    p = argparse.ArgumentParser(description="GAME training driver")
    p.add_argument("--config", required=True, help="GameTrainingConfig JSON file")
    p.add_argument("--train-data", required=True, nargs="+")
    p.add_argument(
        "--train-date-range", nargs=2, metavar=("START", "END"), default=None,
        help="expand each --train-data base path into its daily "
             "subdirectories for the inclusive YYYY-MM-DD range "
             "(base/daily/YYYY/MM/DD or base/YYYY-MM-DD layouts)",
    )
    p.add_argument("--validation-data", nargs="*", default=None)
    p.add_argument(
        "--validation-date-range", nargs=2, metavar=("START", "END"), default=None,
        help="like --train-date-range, for --validation-data",
    )
    p.add_argument("--index-maps", default=None, help="FeatureIndexingDriver output dir")
    p.add_argument(
        "--multihost", action="store_true",
        help="join the jax.distributed runtime (coordinator from "
             "JAX_COORDINATOR_ADDRESS / TPU-pod autodetection; run the SAME "
             "command on every host) and train over the global device mesh; "
             "with --streaming-chunk-rows, ingest is PER-HOST sharded (each "
             "host fills only its slice of the part files)",
    )
    p.add_argument(
        "--streaming-chunk-rows", type=int, default=None,
        help="out-of-core mode: keep the dataset in host RAM (row-"
             "partitioned across hosts under --multihost) and stream it "
             "through the device in uniform chunks of this many rows; "
             "auto-enabled when the input exceeds the cluster HBM budget",
    )
    p.add_argument(
        "--no-auto-streaming", action="store_true",
        help="never auto-select the out-of-core path on input size; "
             "train in-memory unless --streaming-chunk-rows is given",
    )
    p.add_argument(
        "--profile-dir", default=None,
        help="capture jax.profiler device traces of the expensive phases "
             "into this directory (TensorBoard/Perfetto-loadable)",
    )
    p.add_argument(
        "--telemetry-dir", default=None,
        help="write the run's telemetry JSONL (spans, per-iteration "
             "optimizer records, metrics snapshot) into this directory; "
             "render/diff with `photon-ml-tpu report`",
    )
    p.add_argument(
        "--diagnostics", action="store_true",
        help="write diagnostics.json + a self-contained diagnostics.html "
             "(per-coordinate optimizer traces, metrics, top features)",
    )
    p.add_argument("--output-dir", required=True)
    args = p.parse_args(argv)

    config = load_training_config(args.config)
    train_data = args.train_data
    validation_data = args.validation_data
    if args.train_date_range:
        from photon_ml_tpu.io.data_reader import expand_date_range

        train_data = [
            d for base in train_data for d in expand_date_range(base, *args.train_date_range)
        ]
    if args.validation_date_range:
        from photon_ml_tpu.io.data_reader import expand_date_range

        if not validation_data:
            raise SystemExit(
                "--validation-date-range requires --validation-data base paths"
            )
        validation_data = [
            d
            for base in validation_data
            for d in expand_date_range(base, *args.validation_date_range)
        ]
    mesh = None
    if args.multihost:
        # In-memory GAME: ingest reads are replicated across hosts (the
        # feature/entity dictionaries need the global view — the reference
        # gets this from the Spark shuffle); COMPUTE is sharded over the
        # global mesh. Out-of-core GAME (--streaming-chunk-rows): ingest is
        # PER-HOST sharded — only the stats pass (dictionaries) reads all
        # files; rows live on the host that read them, and the random-
        # effect shuffle routes them to their entity owners.
        from photon_ml_tpu.parallel.multihost import (
            initialize_multihost,
            is_output_process,
        )

        info = initialize_multihost()
        # one process owns the shared log file; the rest log to stderr
        logger = PhotonLogger(args.output_dir if is_output_process() else None)
        logger.info(f"multihost runtime: {info}")
    else:
        logger = PhotonLogger(args.output_dir)
    # auto-select out-of-core when the input can't fit the device: CLI-only
    # (run()'s return type is part of the library contract; here nobody
    # consumes it)
    if (
        args.streaming_chunk_rows is None
        and not args.no_auto_streaming
        and _should_auto_stream(
            train_data, config, logger,
            has_validation=bool(validation_data),
        )
    ):
        args.streaming_chunk_rows = 1 << 20
    if args.streaming_chunk_rows is None:
        # every visible device trains: a four-chip host is one process over
        # a four-device mesh, exactly the budget _should_auto_stream sized
        # the input against. One device keeps the unsharded fused path.
        from photon_ml_tpu.parallel import data_mesh, local_device_count

        if args.multihost or local_device_count() > 1:
            mesh = data_mesh()
    # telemetry AFTER multihost init: only the output process writes (the
    # sink checks process_index), and `report` renders/diffs the JSONL
    from photon_ml_tpu import obs

    obs.configure(args.telemetry_dir)
    try:
        run(
            config,
            train_data,
            args.output_dir,
            validation_data=validation_data,
            index_map_dir=args.index_maps,
            logger=logger,
            mesh=mesh,
            profile_dir=args.profile_dir,
            diagnostics=args.diagnostics,
            streaming_chunk_rows=args.streaming_chunk_rows,
            multihost=args.multihost,
        )
    finally:
        obs.shutdown()


if __name__ == "__main__":
    main()
