"""GAME estimator: grid fit + model selection.

Reference parity: ``photon-api::ml.estimators.GameEstimator`` (SURVEY.md
§2.2, §3.1): ``fit(data, validationData, configurations)`` returns one
``(GameModel, Option[EvaluationResults], configuration)`` per optimization
configuration in the grid; the driver selects the best by the primary
validation evaluator.

TPU-first notes:
- All ingest-time work that does not depend on the optimization
  configuration — data validation, per-shard normalization statistics,
  entity grouping/bucketing (the reference's shuffle) — happens ONCE per
  ``fit`` and is shared across the whole grid.
- Each grid entry re-enters the same compiled device programs (the
  geometry — shapes, bucket capacities, mesh — is identical across the
  grid; only λ and co. change, and those are traced scalars).
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass
from typing import Callable, Mapping, Sequence

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from photon_ml_tpu.config import (
    GameTrainingConfig,
    OptimizationConfig,
    RandomEffectCoordinateConfig,
)
from photon_ml_tpu.data.validation import validate_game_batch
from photon_ml_tpu.data.summary import summarize
from photon_ml_tpu.evaluation import EvaluationResults, evaluate_all, make_evaluator
from photon_ml_tpu.game.coordinate import (
    Coordinate,
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.data import (
    EntityBuckets,
    EntityGrouping,
    GameBatch,
    bucket_entities,
    group_by_entity,
    place_game_batch,
    placeable_over,
    rows_placed_over,
)
from photon_ml_tpu.game.descent import CoordinateDescent, CoordinateDescentResult
from photon_ml_tpu.game.models import GameModel
from photon_ml_tpu.normalization import NormalizationContext
from photon_ml_tpu.sampling import down_sample
from photon_ml_tpu.types import NormalizationType, TaskType

Array = jnp.ndarray

# One grid entry: per-coordinate optimization configurations.
GameOptimizationConfiguration = Mapping[str, OptimizationConfig]


_DEFAULT_EVALUATORS = {
    TaskType.LOGISTIC_REGRESSION: ("AUC",),
    TaskType.LINEAR_REGRESSION: ("RMSE",),
    TaskType.POISSON_REGRESSION: ("POISSON_LOSS",),
    TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM: ("AUC",),
}


@dataclass(frozen=True)
class GameResult:
    """One grid entry's outcome (parity: the reference's ``GameResult``
    triple (model, evaluations, configuration))."""

    model: GameModel
    evaluation: EvaluationResults | None
    configuration: dict[str, OptimizationConfig]
    descent: CoordinateDescentResult


def build_configuration_grid(
    config: GameTrainingConfig,
) -> list[dict[str, OptimizationConfig]]:
    """Cross-product of per-coordinate regularization-weight lists
    (``config.regularization_weight_grid``); coordinates without a list keep
    their single configured weight. Parity: the reference's grid over
    ``GameOptimizationConfiguration``s."""
    import itertools

    cids = list(config.coordinate_update_sequence)
    unknown = set(config.regularization_weight_grid) - set(cids)
    if unknown:
        raise ValueError(
            f"regularization_weight_grid names unknown coordinate(s) {sorted(unknown)}; "
            f"update sequence is {cids}"
        )
    axes: list[list[OptimizationConfig]] = []
    for cid in cids:
        base = config.coordinate_config(cid).optimization
        weights = config.regularization_weight_grid.get(cid)
        if weights:
            axes.append(
                [dataclasses.replace(base, regularization_weight=float(w)) for w in weights]
            )
        else:
            axes.append([base])
    return [dict(zip(cids, combo)) for combo in itertools.product(*axes)]


# GameTrainingConfig fields that do NOT change the optimization trajectory:
# excluded from the checkpoint fingerprint so benign reruns (extending the
# iteration count — the canonical resume-and-extend workflow — changing
# evaluators, output mode, …) still resume instead of retraining from zero.
_NON_TRAJECTORY_CONFIG_FIELDS = (
    "coordinate_descent_iterations",
    "evaluators",
    "output_mode",
    "hyperparameter_tuning_iters",
    "model_input_dir",  # the warm-start model itself is hashed by value
)


def _fingerprint_base(
    config: GameTrainingConfig,
    batch: GameBatch,
    seed: int,
    initial_model: GameModel | None,
) -> dict:
    """The grid-invariant part of the checkpoint-resume fingerprint: the
    trajectory-affecting ``GameTrainingConfig`` fields, the estimator seed,
    a value hash of the warm-start model, and a cheap data signature.
    Computed once per ``fit``; each grid entry folds in only its own
    per-coordinate optimization configs. A checkpoint written under any
    different setup must not be silently resumed."""
    import hashlib

    warm = None
    if initial_model is not None:
        warm = {
            cid: hashlib.sha256(
                np.ascontiguousarray(np.asarray(sub.coefficient_means)).tobytes()
            ).hexdigest()
            for cid, sub in sorted(initial_model.models.items())
        }
    # Cheap value digest of the data: catches regenerated/changed datasets
    # that happen to keep the same geometry.
    from photon_ml_tpu.checkpoint import batch_digest

    data_digest = batch_digest(batch.labels, batch.weights)
    cfg_dict = config.to_dict()
    for key in _NON_TRAJECTORY_CONFIG_FIELDS:
        cfg_dict.pop(key, None)
    return {
        "training_config": cfg_dict,
        "seed": seed,
        "initial_model": warm,
        "data": {
            "num_rows": batch.num_rows,
            "digest": data_digest,
            "shards": {
                sid: feats.num_features for sid, feats in sorted(batch.features.items())
            },
        },
    }


def _fit_fingerprint(
    base: dict, configuration: GameOptimizationConfiguration
) -> str:
    import hashlib

    payload = dict(
        base,
        configuration={cid: oc.to_dict() for cid, oc in configuration.items()},
    )
    blob = json.dumps(payload, sort_keys=True, default=str).encode()
    return hashlib.sha256(blob).hexdigest()


class GameEstimator:
    """Fits GAME models over a grid of optimization configurations.

    ``intercept_indices`` maps feature-shard id → intercept column (or
    None); shards absent from the mapping are treated as intercept-free.
    """

    def __init__(
        self,
        config: GameTrainingConfig,
        mesh: Mesh | None = None,
        intercept_indices: Mapping[str, int | None] | None = None,
        logger: Callable[[str], None] | None = None,
        seed: int = 0,
    ):
        self.config = config
        self.mesh = mesh
        self.intercept_indices = dict(intercept_indices or {})
        self._log = logger or (lambda msg: None)
        self.seed = seed

    # -- ingest-time preparation (config-grid independent) ------------------

    def _normalization_contexts(self, batch: GameBatch) -> dict[str, NormalizationContext]:
        """Per-shard normalization from feature summaries (reference:
        ``BasicStatisticalSummary`` → ``NormalizationContext`` per shard) —
        for EVERY shard in the update sequence, random-effect shards
        included (their per-entity solves apply the shard's context inside
        the objective, like the fixed effect's)."""
        if self.config.normalization is NormalizationType.NONE:
            return {}
        contexts: dict[str, NormalizationContext] = {}
        shard_ids = {
            c.feature_shard_id for c in self.config.fixed_effect_coordinates.values()
        } | {
            c.feature_shard_id
            for c in self.config.random_effect_coordinates.values()
        }
        from photon_ml_tpu.data.summary import shard_normalization_context

        for sid in shard_ids:
            contexts[sid] = shard_normalization_context(
                summarize(batch.batch_for(sid)),
                self.config.normalization,
                sid,
                self.intercept_indices.get(sid),
                log=self._log,
            )
        return contexts

    def _entity_layouts(
        self, batch: GameBatch
    ) -> dict[str, tuple[EntityGrouping, EntityBuckets, int]]:
        """Group + bucket each random-effect coordinate's entities (the
        ingest-time replacement for the reference's group-by-entity shuffle)."""
        layouts: dict[str, tuple[EntityGrouping, EntityBuckets, int]] = {}
        for cid, cfg in self.config.random_effect_coordinates.items():
            # the real rows: one padded to fill the mesh belongs to no entity
            ids = np.asarray(batch.id_tags[cfg.random_effect_type])[
                : batch.num_real_rows
            ]
            num_entities = int(ids.max()) + 1 if len(ids) else 0
            grouping = group_by_entity(
                ids,
                num_entities=num_entities,
                active_upper_bound=cfg.active_data_upper_bound,
                seed=self.seed,
            )
            buckets = bucket_entities(
                grouping,
                cfg.sample_bucket_sizes,
                target_buckets=cfg.bucket_target_count,
                max_padded_ratio=cfg.bucket_max_padded_ratio,
            )
            layouts[cid] = (grouping, buckets, num_entities)
        return layouts

    def _build_coordinates(
        self,
        batch: GameBatch,
        configuration: GameOptimizationConfiguration,
        norm_contexts: Mapping[str, NormalizationContext],
        entity_layouts: Mapping[str, tuple[EntityGrouping, EntityBuckets, int]],
        re_coordinate_cache: dict[str, RandomEffectCoordinate] | None = None,
        prior_model: "GameModel | None" = None,
    ) -> dict[str, Coordinate]:
        """``re_coordinate_cache`` (when given) shares each random-effect
        coordinate's prepared bucket tensors across grid entries — only the
        optimization config is swapped per entry, so the staged device
        buffers are gathered once per ``fit``, not once per grid entry."""
        coordinates: dict[str, Coordinate] = {}
        task = self.config.task_type
        for cid in self.config.coordinate_update_sequence:
            opt = configuration[cid]
            coord_cfg = self.config.coordinate_config(cid)
            if isinstance(coord_cfg, RandomEffectCoordinateConfig):
                if re_coordinate_cache is not None and cid in re_coordinate_cache:
                    coordinates[cid] = re_coordinate_cache[cid].with_config(opt)
                    continue
                grouping, buckets, num_entities = entity_layouts[cid]
                projector = None
                if coord_cfg.random_projection_dim is not None:
                    from photon_ml_tpu.game.projector import RandomProjector

                    projector = RandomProjector.build(
                        batch.features[coord_cfg.feature_shard_id].num_features,
                        coord_cfg.random_projection_dim,
                        seed=self.seed,
                    )
                coord = RandomEffectCoordinate(
                    coordinate_id=cid,
                    batch=batch,
                    feature_shard_id=coord_cfg.feature_shard_id,
                    random_effect_type=coord_cfg.random_effect_type,
                    config=opt,
                    grouping=grouping,
                    buckets=buckets,
                    task_type=task,
                    num_entities=num_entities,
                    intercept_index=self.intercept_indices.get(coord_cfg.feature_shard_id),
                    normalization=norm_contexts.get(coord_cfg.feature_shard_id),
                    variance_computation=self.config.variance_computation,
                    mesh=self.mesh,
                    features_to_samples_ratio=coord_cfg.features_to_samples_ratio_upper_bound,
                    projector=projector,
                    prior_model=(
                        None if prior_model is None else prior_model.models.get(cid)
                    ),
                )
                if re_coordinate_cache is not None:
                    re_coordinate_cache[cid] = coord
                coordinates[cid] = coord
            else:
                train_rows = None
                weight_scale = None
                if opt.down_sampling_rate < 1.0:
                    rows, scale = down_sample(
                        task,
                        np.asarray(batch.labels)[: batch.num_real_rows],
                        opt.down_sampling_rate,
                        seed=self.seed,
                    )
                    train_rows = jnp.asarray(rows, jnp.int32)
                    weight_scale = None if scale is None else jnp.asarray(scale)
                coordinates[cid] = FixedEffectCoordinate(
                    coordinate_id=cid,
                    batch=batch,
                    feature_shard_id=coord_cfg.feature_shard_id,
                    config=opt,
                    task_type=task,
                    intercept_index=self.intercept_indices.get(coord_cfg.feature_shard_id),
                    normalization=norm_contexts.get(coord_cfg.feature_shard_id),
                    variance_computation=self.config.variance_computation,
                    mesh=self.mesh,
                    train_rows=train_rows,
                    train_weight_scale=weight_scale,
                    prior_model=(
                        None if prior_model is None else prior_model.models.get(cid)
                    ),
                )
        return coordinates

    # -- fit ----------------------------------------------------------------

    def _evaluator_specs(self) -> tuple[str, ...]:
        return tuple(self.config.evaluators) or _DEFAULT_EVALUATORS[self.config.task_type]

    def fit(
        self,
        batch: GameBatch,
        validation_batch: GameBatch | None = None,
        configurations: Sequence[GameOptimizationConfiguration] | None = None,
        initial_model: GameModel | None = None,
        checkpoint_dir: str | None = None,
    ) -> list[GameResult]:
        """Train one GAME model per grid configuration.

        ``configurations`` defaults to ``build_configuration_grid(self.config)``
        — the cross-product of ``regularization_weight_grid`` (a single
        configuration when no weight lists are set). ``initial_model``
        warm-starts every grid entry (reference: ``modelInputDirectory``).
        """
        cfg = self.config
        validate_game_batch(batch, cfg.task_type, cfg.data_validation, self.seed)
        if validation_batch is not None:
            validate_game_batch(
                validation_batch, cfg.task_type, cfg.data_validation, self.seed
            )

        if configurations is None:
            configurations = build_configuration_grid(cfg)

        if (
            self.mesh is not None
            and placeable_over(batch, self.mesh)
            and not rows_placed_over(batch, self.mesh)  # the reader did
        ):
            # rows over the mesh ONCE: with that the mesh alone chooses the
            # descent's fused path, and no visit pads and puts again
            batch = place_game_batch(batch, self.mesh)

        norm_contexts = self._normalization_contexts(batch)
        entity_layouts = self._entity_layouts(batch)
        specs = self._evaluator_specs()
        fingerprint_base = (
            None
            if checkpoint_dir is None
            else _fingerprint_base(cfg, batch, self.seed, initial_model)
        )

        results: list[GameResult] = []
        re_coordinate_cache: dict[str, RandomEffectCoordinate] = {}
        for i, configuration in enumerate(configurations):
            self._log(f"grid entry {i + 1}/{len(configurations)}: {configuration}")
            coordinates = self._build_coordinates(
                batch, configuration, norm_contexts, entity_layouts,
                re_coordinate_cache=re_coordinate_cache,
                prior_model=initial_model if cfg.incremental else None,
            )
            descent = CoordinateDescent(
                coordinates,
                batch,
                cfg.task_type,
                validation_batch=validation_batch,
                evaluators=specs if validation_batch is not None else (),
                logger=self._log,
                mesh=self.mesh,
            )
            cd_result = descent.run(
                cfg.coordinate_update_sequence,
                cfg.coordinate_descent_iterations,
                initial_model=initial_model,
                checkpoint_dir=(
                    None
                    if checkpoint_dir is None
                    else f"{checkpoint_dir}/config-{i:04d}"
                ),
                checkpoint_fingerprint=(
                    None
                    if fingerprint_base is None
                    else _fit_fingerprint(fingerprint_base, configuration)
                ),
            )
            if batch.padded_rows:
                cd_result = dataclasses.replace(
                    cd_result,
                    training_scores={
                        cid: s[: batch.num_real_rows]
                        for cid, s in cd_result.training_scores.items()
                    },
                )
            evaluation = None
            if validation_batch is not None:
                scores = cd_result.model.score(validation_batch)
                evaluation = evaluate_all(
                    specs,
                    scores,
                    validation_batch.labels,
                    validation_batch.weights,
                    group_ids=validation_batch.host_id_tags(),
                    mesh=self.mesh,
                )
                self._log(f"grid entry {i + 1}: validation {evaluation}")
            results.append(
                GameResult(
                    model=cd_result.model,
                    evaluation=evaluation,
                    configuration=dict(configuration),
                    descent=cd_result,
                )
            )
        return results

    def select_best(self, results: Sequence[GameResult]) -> GameResult:
        """Pick the grid entry with the best primary validation metric
        (parity: the driver's model selection). Falls back to the first
        result when nothing was evaluated."""
        specs = self._evaluator_specs()
        primary = make_evaluator(specs[0])
        best = None
        for r in results:
            if r.evaluation is None:
                continue
            if best is None or primary.better(r.evaluation.primary, best.evaluation.primary):
                best = r
        return best if best is not None else results[0]
