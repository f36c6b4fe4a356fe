"""Per-coordinate train/score units.

Reference parity: ``photon-api::ml.algorithm.{Coordinate,
FixedEffectCoordinate, RandomEffectCoordinate}`` (SURVEY.md §2.2, §3.1).
A coordinate binds one effect's data view + optimization problem and
exposes ``train(offsets, initial)`` / ``score(model)``; coordinate descent
drives them through residual offsets.

TPU-first: both coordinates train through compiled device programs keyed on
static geometry — re-entered, not recompiled, every descent iteration:
- fixed effect → the sample-sharded ``sharded_minimize`` psum path
  (HOT LOOP 1 of §3.1);
- random effect → the vmap-batched bucket solver (HOT LOOP 2).

Both hand the descent the parts of a FUSED visit (``_fused_visit_parts``),
on one device and, since PR 38, under a mesh of one host whose batch was
placed over it (``fuses_under_mesh``): the visit then runs inside
``shard_map``, rows over the mesh axis for the fixed effect, entity lanes for
the random effects, coefficients whole on every device.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Protocol

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.config import OptimizationConfig
from photon_ml_tpu.game.data import (
    DenseFeatures,
    EntityBuckets,
    EntityGrouping,
    GameBatch,
    NonzeroMajorSparseFeatures,
    SparseFeatures,
    one_process_mesh,
    rows_placed_over,
)
from photon_ml_tpu.game.random_effect import (
    RandomEffectTrainingResult,
    prepare_buckets,
    shared_order,
    train_prepared,
)
from photon_ml_tpu.game.models import FixedEffectModel, GameSubModel, RandomEffectModel
from photon_ml_tpu.game.projector import RandomProjector
from photon_ml_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_ml_tpu.normalization import (
    NormalizationContext,
    require_intercept_for_shifts,
)
from photon_ml_tpu.obs.spans import COORD_FIXED, COORD_RE, span
from photon_ml_tpu.obs.stages import (
    MESH_EXCHANGE,
    RE_OFFSETS,
    RE_SCORE,
    VISIT_FIXED,
    VISIT_RE,
    stage,
)
from photon_ml_tpu.ops.glm import auto_fused, compute_variances, make_objective
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.optim.common import OptimizationResult, select_minimize_fn
from photon_ml_tpu.parallel.distributed import sharded_minimize
from photon_ml_tpu.types import TaskType, VarianceComputationType

Array = jnp.ndarray


class Coordinate(Protocol):
    """The contract coordinate descent drives."""

    coordinate_id: str

    def train(
        self, offsets: Array, initial: GameSubModel | None
    ) -> tuple[GameSubModel, Any]: ...

    def score(self, model: GameSubModel) -> Array: ...


def _require_prior_l2(config) -> None:
    """The MAP prior's strength is λ₂·(1/variance): with a zero effective
    L2 weight the prior silently does nothing — refuse the configuration
    instead of quietly training unanchored."""
    if config.regularization.l2_weight(config.regularization_weight) <= 0.0:
        raise ValueError(
            "incremental training (prior_model) requires a positive L2 "
            "regularization weight: the prior's pull is "
            "l2_weight * (1/prior_variance)"
        )


def fuses_under_mesh(batch: GameBatch, mesh: Mesh, axis_name: str) -> bool:
    """Whether a coordinate's visit can be traced into the descent's one
    program under ``mesh``: every device of the mesh is this process's own
    (one host, fully addressable arrays) and the batch's rows were placed
    over it (``game/data.place_game_batch``: ``GameEstimator.fit`` and
    ``AvroDataReader.read`` place every batch they can, so there a mesh
    alone says so). A mesh that spans processes, or a hand-built batch left
    on one device, keeps the host loop's path."""
    return one_process_mesh(mesh) and rows_placed_over(batch, mesh, axis_name)


def _one_device_block(a):
    """The block of a sharded array that its first device holds (the array
    itself where it has no shards to show: a shape under a deviceless
    compile)."""
    shards = getattr(a, "addressable_shards", None)
    return shards[0].data if shards else a


@dataclass(frozen=True)
class FixedEffectCoordinate:
    """Distributed single-GLM solve over all samples of one feature shard.

    ``train_rows``/``train_weight_scale`` implement per-coordinate
    down-sampling (parity: the reference's ``DownSampler`` applied to the
    fixed-effect coordinate): training sees the subset with corrected
    weights; scoring always sees every sample.
    """

    coordinate_id: str
    batch: GameBatch
    feature_shard_id: str
    config: OptimizationConfig
    task_type: TaskType
    intercept_index: int | None = None
    normalization: NormalizationContext | None = None
    variance_computation: VarianceComputationType = VarianceComputationType.NONE
    mesh: Mesh | None = None
    axis_name: str = "data"
    train_rows: Array | None = None  # int32 row subset (down-sampling)
    train_weight_scale: Array | None = None  # per-subset-row weight correction
    # incremental training: the LOADED warm-start sub-model, held fixed as
    # a Gaussian MAP prior across ALL descent iterations (the per-iteration
    # ``initial`` argument evolves — anchoring the prior to it would make
    # the objective drift every pass). Parity with Photon-ML's incremental
    # learning (SURVEY.md §2.3 Model IO + warm start).
    prior_model: "FixedEffectModel | None" = None

    def _training_batch(self, offsets: Array):
        shard = self.batch.features[self.feature_shard_id]
        if self.train_rows is None:
            batch = shard.to_batch(
                self.batch.labels, offsets, self.batch.weights
            )
            opt = self._optimized_layout(batch)
            if opt is not None:
                # re-bind this visit's residual offsets onto the cached
                # layout (densify/tile depend only on indices/values)
                import dataclasses as _dc

                return _dc.replace(opt, offsets=offsets)
            return batch
        rows = self.train_rows
        w = self.batch.weights[rows]
        if self.train_weight_scale is not None:
            w = w * self.train_weight_scale
        return jax.tree.map(lambda a: a[rows], shard).to_batch(
            self.batch.labels[rows], offsets[rows], w
        )

    def _optimized_layout(self, batch):
        """The framework's FULL ingest layout decision (densify small-d
        sparse shards for MXU matmuls; tile-COO re-block genuinely
        high-dimensional ones), computed ONCE per coordinate and reused
        every descent visit (VERDICT r3 next-1b: the decision now reaches
        the GAME fixed effect, not just the legacy GLM driver). Returns
        None when the shard's layout is already the right one.
        One device only: under a mesh the rows stay as they were placed (a
        dense shard row-sharded, which the fused visit's kernels read a
        device's block of; a sparse one through ``sharded_minimize``'s own
        layout decision)."""
        if self.mesh is not None:
            return None
        cached = getattr(self, "_layout_cached", False)
        if cached is False:
            from photon_ml_tpu.ops.batch import optimize_batch_layout
            from photon_ml_tpu.ops.streaming import device_hbm_budget_bytes

            out = optimize_batch_layout(
                batch, hbm_budget_bytes=device_hbm_budget_bytes()
            )
            cached = None if out is batch else out
            object.__setattr__(self, "_layout_cached", cached)
        return cached

    def __post_init__(self):
        require_intercept_for_shifts(self.normalization)

    def train(
        self, offsets: Array, initial: GameSubModel | None = None
    ) -> tuple[FixedEffectModel, OptimizationResult]:
        train_batch = self._training_batch(offsets)
        d = train_batch.num_features
        prior = None
        if self.prior_model is not None:
            from photon_ml_tpu.ops.glm import GaussianPrior

            _require_prior_l2(self.config)
            prior = GaussianPrior.from_coefficients(
                self.prior_model.model.coefficients.means,
                self.prior_model.model.coefficients.variances,
                self.normalization,
            )
        if initial is not None:
            w0 = jnp.asarray(initial.model.coefficients.means, jnp.float32)
            if self.normalization is not None:
                w0 = self.normalization.model_from_original_space(w0)
        else:
            w0 = jnp.zeros((d,), jnp.float32)

        opt = self.config
        loss = loss_for_task(self.task_type)
        l1 = opt.regularization.l1_weight(opt.regularization_weight)
        l2 = opt.regularization.l2_weight(opt.regularization_weight)
        minimize_fn, extra = select_minimize_fn(opt.optimizer, l1)

        if self.mesh is not None:
            result = sharded_minimize(
                minimize_fn,
                train_batch,
                w0,
                opt.optimizer,
                self.mesh,
                loss,
                l2_weight=l2,
                norm=self.normalization,
                intercept_index=self.intercept_index,
                axis_name=self.axis_name,
                prior=prior,
                **extra,
            )
        else:
            obj = make_objective(
                train_batch,
                loss,
                l2_weight=l2,
                norm=self.normalization,
                intercept_index=self.intercept_index,
                prior=prior,
            )
            result = minimize_fn(obj, w0, opt.optimizer, **extra)

        w = result.w
        variances = None
        if self.variance_computation is not VarianceComputationType.NONE:
            obj = make_objective(
                train_batch,
                loss,
                l2_weight=l2,
                norm=self.normalization,
                intercept_index=self.intercept_index,
                prior=prior,
            )
            variances = compute_variances(obj, w, self.variance_computation)
        if self.normalization is not None:
            w, _ = self.normalization.model_to_original_space(w)
            if variances is not None:
                variances = self.normalization.factors**2 * variances
        model = FixedEffectModel(
            model=GeneralizedLinearModel(Coefficients(w, variances), self.task_type),
            feature_shard_id=self.feature_shard_id,
        )
        return model, result

    def score(self, model: FixedEffectModel) -> Array:
        opt = getattr(self, "_layout_cached", False)
        if opt not in (False, None):
            # scoring = margins over the same shard: ride the optimized
            # layout (MXU matmul when densified, tile-COO kernel when tiled)
            return opt.matvec(model.model.coefficients.means)
        return model.score(self.batch)

    def _reset_compiled_state(self) -> None:
        """Drop every cached compiled program / staged device tensor so
        the next visit rebuilds them from the (host-side) batch. The
        in-place descent degrade calls this after shrinking the process
        group: the cached executables/layouts were built for the old
        topology. Frozen dataclass, so the caches live in ``__dict__``
        via ``object.__setattr__`` — popping them re-arms the lazy
        builders."""
        for key in ("_visit_base", "_visit_fn", "_layout_cached"):
            self.__dict__.pop(key, None)

    def _degrade_blocker(self) -> str | None:
        """Why this coordinate CANNOT survive an in-place group shrink,
        or None when it can. Under a mesh the fixed effect's programs (the
        host loop's ``sharded_minimize`` and the fused visit's ``shard_map``
        alike) are compiled over the full device mesh — a dead process's
        devices cannot leave a live mesh in-process, so the only honest
        answer is the restart-from-checkpoint abort."""
        if self.mesh is not None:
            return (
                f"fixed-effect coordinate {self.coordinate_id!r} solves "
                "over the full device mesh"
            )
        return None

    def _fused_visit_parts(self):
        """(make_static, apply, postprocess, advance) for fused execution,
        or None when this coordinate needs host-side staging per visit.

        ``make_static(initial)`` builds the non-flowing jit arguments;
        ``apply(static, total, own_score)`` runs the visit INSIDE a trace
        and returns (aux, new_score, new_total); ``postprocess(aux)``
        rebuilds (sub-model, tracker) on host; ``advance(aux, static)`` is
        the PURE in-trace twin of postprocess→make_static, wiring one
        visit's result into the next visit's static inputs so multiple
        outer iterations can chain inside one program. ``visit`` composes
        these for a single-coordinate launch; ``descent._build_fused_outer``
        chains every coordinate's ``apply`` into ONE program per outer
        iteration — and, through ``advance``, one program per CHUNK of
        outer iterations.

        Under a mesh the parts are the same and ``apply`` runs inside
        ``shard_map``: a device solves over its own block of rows and the
        objective's partial sums meet in its ``psum``
        (``fuses_under_mesh`` says when)."""
        if self.train_rows is not None:
            # down-sampling changes row sets per config: the unfused path
            return None
        if self.mesh is not None and not (
            fuses_under_mesh(self.batch, self.mesh, self.axis_name)
            and isinstance(self.batch.features[self.feature_shard_id], DenseFeatures)
        ):
            return None
        base = self.__dict__.get("_visit_base")
        if base is None:
            # materialize the layout cache + the offset-free base batch
            # OUTSIDE the trace (densify/tile are host-side transforms); the
            # jit rebinds per-visit offsets onto this pytree ARGUMENT (a
            # closure would bake the feature arrays into the executable)
            with span(COORD_FIXED):
                base = self._training_batch(jnp.zeros_like(self.batch.offsets))
                object.__setattr__(self, "_visit_base", base)
                object.__setattr__(self, "_visit_fn", self._build_visit_fn(base))
        fn = self.__dict__["_visit_fn"]

        def make_static(initial):
            w0 = (
                jnp.asarray(initial.model.coefficients.means, jnp.float32)
                if initial is not None
                else jnp.zeros((base.num_features,), jnp.float32)
            )
            return (base, w0)

        def apply(static, total, own_score):
            b, w0 = static
            w, variances, tracker, new_score, new_total = fn(
                b, total, own_score, w0
            )
            return (w, variances, tracker), new_score, new_total

        def postprocess(aux, build_model=True):
            w, variances, tracker = aux
            if not build_model:
                return None, tracker
            model = FixedEffectModel(
                model=GeneralizedLinearModel(
                    Coefficients(w, variances), self.task_type
                ),
                feature_shard_id=self.feature_shard_id,
            )
            return model, tracker

        def advance(aux, static):
            # in-trace twin of postprocess→make_static: the next visit
            # warm-starts from this visit's coefficients
            b, _ = static
            return (b, aux[0])

        return make_static, apply, postprocess, advance

    def visit(
        self, total: Array, own_score: Array | None,
        initial: GameSubModel | None = None,
    ) -> tuple[FixedEffectModel, OptimizationResult, Array, Array]:
        """One descent visit as ONE compiled program: residual offsets →
        solve → score → new running total. Returns (sub-model, tracker,
        new own score, new total). On dispatch-latency-dominated platforms
        (remote-attached chips) the unfused visit's 4-6 small program
        launches were the wall-clock floor of every GAME config (VERDICT
        r3 weak #3); the fused form launches once. ``own_score=None``
        means this coordinate has not scored yet (cold start)."""
        parts = self._fused_visit_parts()
        if parts is None:
            offsets = total - own_score if own_score is not None else total
            sub_model, tracker = self.train(offsets, initial)
            new_score = self.score(sub_model)
            return sub_model, tracker, new_score, offsets + new_score
        make_static, apply, postprocess, _advance = parts
        if own_score is None:
            own_score = jnp.zeros_like(total)
        aux, new_score, new_total = apply(
            make_static(initial), total, own_score
        )
        model, tracker = postprocess(aux)
        return model, tracker, new_score, new_total

    def _build_visit_fn(self, base):
        """The jitted visit body (built once per coordinate; closes over
        the config, prior, and cached layout). Whether the objective takes
        the one-pass kernels is decided here, on the concrete ``base``
        batch: inside the trace X is a tracer, of which ``auto_fused``
        knows neither the device nor how the array is stored."""
        opt = self.config
        loss = loss_for_task(self.task_type)
        l1 = opt.regularization.l1_weight(opt.regularization_weight)
        l2 = opt.regularization.l2_weight(opt.regularization_weight)
        minimize_fn, extra = select_minimize_fn(opt.optimizer, l1)
        mesh, axis = self.mesh, self.axis_name
        # under a mesh a device's kernels see its own block of rows
        fused = auto_fused(
            base if mesh is None else jax.tree.map(_one_device_block, base)
        )
        prior = None
        if self.prior_model is not None:
            from photon_ml_tpu.ops.glm import GaussianPrior

            _require_prior_l2(self.config)
            prior = GaussianPrior.from_coefficients(
                self.prior_model.model.coefficients.means,
                self.prior_model.model.coefficients.variances,
                self.normalization,
            )
        norm = self.normalization

        def run(base_batch, total, own_score, w0):
            import dataclasses as _dc

            with stage(VISIT_FIXED):
                offsets = total - own_score
                train_batch = _dc.replace(base_batch, offsets=offsets)
                if norm is not None:
                    w0_n = norm.model_from_original_space(w0)
                else:
                    w0_n = w0
                obj = make_objective(
                    train_batch, loss, l2_weight=l2, norm=norm,
                    intercept_index=self.intercept_index, prior=prior,
                    fused=fused, axis_name=None if mesh is None else axis,
                )
                result = minimize_fn(obj, w0_n, opt.optimizer, **extra)
                w = result.w
                variances = compute_variances(
                    obj, w, self.variance_computation
                )
                if norm is not None:
                    w, _ = norm.model_to_original_space(w)
                    if variances is not None:
                        variances = norm.factors**2 * variances
                new_score = train_batch.matvec(w)
                return w, variances, result, new_score, offsets + new_score

        if mesh is None:
            return jax.jit(run)
        # the partitioning written down: rows over the axis, coefficients
        # and the tracker whole on every device
        rows = P(axis)
        return jax.jit(jax.shard_map(
            run, mesh=mesh, in_specs=(rows, rows, rows, P()),
            out_specs=(P(), P(), P(), rows, rows), check_vma=False,
        ))


@dataclass(frozen=True)
class RandomEffectCoordinate:
    """Per-entity batched solves over one feature shard + entity column.

    The grouping/bucketing (the reference's shuffle + partitioner) is done
    once at construction; ``train`` re-enters the compiled bucket kernels
    with fresh residual offsets each descent iteration.
    """

    coordinate_id: str
    batch: GameBatch
    feature_shard_id: str
    random_effect_type: str
    config: OptimizationConfig
    grouping: EntityGrouping
    buckets: EntityBuckets
    task_type: TaskType
    num_entities: int
    intercept_index: int | None = None
    normalization: NormalizationContext | None = None
    variance_computation: VarianceComputationType = VarianceComputationType.NONE
    mesh: Mesh | None = None
    axis_name: str = "data"
    # per-entity subspace projection (numFeaturesToSamplesRatioUpperBound)
    features_to_samples_ratio: float | None = None
    # shared random projection (ProjectionMatrix); trained coefficients are
    # mapped back to the original space, so the model/scores are unchanged
    projector: "RandomProjector | None" = None
    # incremental training: the LOADED warm-start sub-model, held fixed as
    # per-entity Gaussian MAP priors across all descent iterations (see
    # FixedEffectCoordinate.prior_model)
    prior_model: "RandomEffectModel | None" = None

    def __post_init__(self):
        if self.normalization is not None and self.projector is not None:
            raise NotImplementedError(
                "normalization is not supported together with random "
                "projection (the projected columns have no per-feature stats)"
            )
        if (
            self.normalization is not None
            and self.features_to_samples_ratio is not None
        ):
            raise NotImplementedError(
                "normalization is not supported together with per-entity "
                "subspace projection (the per-entity column maps would need "
                "per-entity normalization slices)"
            )
        require_intercept_for_shifts(self.normalization)

    def _features(self):
        feats = self.batch.features[self.feature_shard_id]
        if self.projector is not None:
            if not isinstance(feats, DenseFeatures):
                raise ValueError("random projection requires dense features")
            # cache the projected shard: it is static across descent
            # visits, and the fused visit path reads it every visit
            cached = self.__dict__.get("_features_cache")
            if cached is None:
                cached = DenseFeatures(
                    X=self.projector.project_features(feats.X)
                )
                object.__setattr__(self, "_features_cache", cached)
            return cached
        return feats

    @property
    def _train_num_features(self) -> int:
        """Feature width of the training subspace, WITHOUT materializing the
        projection (``_features()`` would re-run the full-shard projection
        matmul every descent iteration just to read a shape)."""
        if self.projector is not None:
            return self.projector.projected_dim
        return self.batch.features[self.feature_shard_id].num_features

    @property
    def _prepared(self):
        """Bucket tensors staged to device ONCE (cached on the instance);
        each descent iteration only gathers fresh offsets on device."""
        cached = self.__dict__.get("_prepared_cache")
        if cached is None:
            with span(COORD_RE):
                cached = prepare_buckets(
                    self._features(),
                    np.asarray(self.batch.labels),
                    np.asarray(self.batch.weights),
                    self.buckets,
                    self.mesh,
                    self.axis_name,
                    features_to_samples_ratio=self.features_to_samples_ratio,
                    intercept_index=None if self.projector is not None else self.intercept_index,
                )
            object.__setattr__(self, "_prepared_cache", cached)
        return cached

    def with_config(self, config: OptimizationConfig) -> "RandomEffectCoordinate":
        """A copy bound to a different optimization config that SHARES the
        prepared bucket tensors (they depend only on data/geometry, not on
        the optimization config) — so a grid of λ values re-enters the same
        staged device buffers instead of re-gathering per grid entry."""
        import dataclasses

        new = dataclasses.replace(self, config=config)
        cached = self.__dict__.get("_prepared_cache")
        if cached is not None:
            object.__setattr__(new, "_prepared_cache", cached)
        return new

    def train(
        self, offsets: Array, initial: GameSubModel | None = None
    ) -> tuple[RandomEffectModel, RandomEffectTrainingResult]:
        opt = self.config
        loss = loss_for_task(self.task_type)
        l1 = opt.regularization.l1_weight(opt.regularization_weight)
        l2 = opt.regularization.l2_weight(opt.regularization_weight)
        W0 = None
        prior_W = prior_V = None
        if initial is not None:
            W0 = initial.coefficients
            if W0.shape[0] != self.num_entities:
                raise ValueError(
                    f"warm-start entity count {W0.shape[0]} != {self.num_entities}"
                )
            if self.projector is not None:
                # approximate: P has no exact inverse; P is near-orthogonal
                # (JL), so projecting the original-space warm start is the
                # standard choice
                W0 = W0 @ self.projector.matrix
        if self.prior_model is not None:
            _require_prior_l2(self.config)
            prior_W = self.prior_model.coefficients
            prior_V = self.prior_model.variances
            if prior_W.shape[0] != self.num_entities:
                raise ValueError(
                    f"prior entity count {prior_W.shape[0]} != {self.num_entities}"
                )
            if self.projector is not None:
                prior_W = prior_W @ self.projector.matrix
                # diagonal variances do not survive a dense projection;
                # fall back to unit precision in the projected space
                prior_V = None
        result = train_prepared(
            self._prepared,
            jnp.asarray(offsets),
            self._train_num_features,
            self.num_entities,
            loss,
            opt.optimizer,
            l2_weight=l2,
            l1_weight=l1,
            intercept_index=None if self.projector is not None else self.intercept_index,
            initial_coefficients=W0,
            variance_computation=self.variance_computation,
            mesh=self.mesh,
            axis_name=self.axis_name,
            norm=self.normalization,
            prior_coefficients=prior_W,
            prior_variances=prior_V,
            fusion_units=self._staged_fusion_units(),
        )
        coefficients = result.coefficients
        variances = result.variances
        if self.projector is not None:
            # back to original space, score-exactly: (XP)w_p = X(P w_p)
            coefficients = self.projector.coefficients_to_original(coefficients)
            variances = None  # diagonal variances don't survive a dense map
        model = RandomEffectModel(
            coefficients=coefficients,
            variances=variances,
            random_effect_type=self.random_effect_type,
            feature_shard_id=self.feature_shard_id,
            task_type=self.task_type,
        )
        return model, result

    def score(self, model: RandomEffectModel) -> Array:
        return model.score(self.batch)

    def _reset_compiled_state(self) -> None:
        """Degrade-in-place hook: drop the prepared bucket tensors, the
        staged fusion units and the cached visit program. The next
        ``train``/``visit`` re-prepares over the CURRENT (survivor)
        group — ``prepare_buckets`` re-plans ownership with the degraded
        ``effective_process_*`` shape, so each survivor stages exactly
        the buckets it now owns."""
        for key in (
            "_prepared_cache", "_fusion_units_cache", "_visit_fn",
            "_features_cache", "_score_features_cache",
            "_mesh_bucket_args_cache",
        ):
            self.__dict__.pop(key, None)

    def _degrade_blocker(self) -> str | None:
        """Why this coordinate cannot survive an in-place group shrink
        (None = it can). Owned-bucket prep (``PHOTON_RE_SHARD=1`` under
        a mesh) degrades cleanly: buckets are staged whole per process
        and the combine is a host collective over the survivor mesh.
        The LANE-SHARDED prep spans the full device mesh, in the host
        loop's bucket steps and in the fused visit's ``shard_map`` alike —
        a mesh cannot shrink in-process, so it keeps the abort."""
        if self.mesh is None:
            return None
        prepared = self.__dict__.get("_prepared_cache")
        if prepared is not None:
            owned = any(pb.owner is not None for pb in prepared)
        else:
            from photon_ml_tpu.parallel.placement import re_shard_enabled

            owned = re_shard_enabled()
        if owned:
            return None
        return (
            f"random-effect coordinate {self.coordinate_id!r} is "
            "lane-sharded over the full device mesh (enable "
            "PHOTON_RE_SHARD=1 owned-bucket placement — with the "
            "PHOTON_RE_COMBINE=segments host-collective combine — for "
            "a degradable in-memory solve)"
        )

    def _staged_fusion_units(self):
        """Fused launch units for this coordinate's (cached) prepared
        buckets, staged ONCE: the eager visit loop calls ``train`` per
        descent visit, and rebuilding the fused concatenation each time
        would copy every static bucket tensor per visit. ``None`` when
        fusion doesn't apply (knob off, lane-sharded mesh, single
        bucket). Under entity-sharded owned-bucket mode
        (``PHOTON_RE_SHARD=1``) a mesh no longer disables fusion: lanes
        are fully addressable per owned bucket, and placement is
        fusion-group-atomic, so every fusable set is co-owned."""
        from photon_ml_tpu.game.random_effect import (
            _fusion_units,
            _parent_units,
            fuse_buckets,
        )

        # gate on the PREPARED STATE, not a re-read of the knob: the
        # buckets were either staged owned (owner set, fully addressable
        # — fusable) or lane-sharded (concatenation would break the mesh
        # lane padding), and a knob flip after staging must not change
        # which schedule the cached tensors support
        lane_sharded = self.mesh is not None and not any(
            pb.owner is not None for pb in self._prepared
        )
        if lane_sharded or len(self._prepared) < 2:
            return None
        # a PHOTON_RE_SPLIT prep re-concatenates same-parent sub-buckets
        # per owner even with the fuse knob off (prepared-state gate
        # again: parent markers were staged, or not, at prep time)
        split_mode = any(pb.parent is not None for pb in self._prepared)
        fuse = fuse_buckets()
        if not fuse and not split_mode:
            return None
        cached = self.__dict__.get("_fusion_units_cache")
        units = cached[1] if cached is not None and cached[0] == fuse else None
        if units is None:
            units = (
                _fusion_units(self._prepared) if fuse
                else _parent_units(self._prepared)
            )
            object.__setattr__(self, "_fusion_units_cache", (fuse, units))
        return units

    def _fused_visit_parts(self):
        """See ``FixedEffectCoordinate._fused_visit_parts``. Under a mesh
        (``_fuses_lane_sharded`` says when) a device solves its own quarter
        of every class's lanes inside ``shard_map``: the residual is made
        whole on every device before the buckets read it, the solved lanes
        are gathered and scattered into a coefficient matrix whole on every
        device after, and each device scores its own rows."""
        from photon_ml_tpu.game.random_effect import compact_every, fuse_buckets

        if self.mesh is not None and not self._fuses_lane_sharded():
            return None
        if compact_every() > 0:
            # convergence-aware lane compaction (PHOTON_RE_COMPACT_EVERY)
            # snapshots per-lane done masks on host between chunks —
            # incompatible with tracing the whole visit into one launch;
            # fall back to the host bucket loop where compaction applies
            # (knob 0, the default, keeps the fused-visit path untouched)
            return None
        _ = self._prepared  # stage bucket tensors OUTSIDE the trace
        # the launch-fusion knob is baked into the visit trace — key the
        # cached fn on it so a toggle rebuilds instead of silently reusing
        # the old schedule (same discipline as the kernel-constant caches)
        fuse_key = bool(fuse_buckets())
        cached = self.__dict__.get("_visit_fn")
        fn = cached[1] if cached is not None and cached[0] == fuse_key else None
        if fn is None:
            fn = self._build_visit_fn()
            object.__setattr__(self, "_visit_fn", (fuse_key, fn))
        # the effect's own order (one array, every bucket's: None where the
        # file's order serves), then the buckets
        bucket_args = (shared_order(self._prepared), tuple(
            (pb.static, pb.row_idx, pb.mask, pb.ids, pb.columns)
            for pb in self._prepared
        )) if self.mesh is None else self._mesh_bucket_args()
        feats = self._features()
        if isinstance(feats, SparseFeatures):
            # scored nonzero-major inside the program; staged once
            cached = self.__dict__.get("_score_features_cache")
            if cached is None:
                with span(COORD_RE):
                    cached = NonzeroMajorSparseFeatures.of(feats)
                object.__setattr__(self, "_score_features_cache", cached)
            feats = cached
        ids = self.batch.id_tags[self.random_effect_type]

        def make_static(initial):
            if initial is not None:
                W0 = initial.coefficients
                if W0.shape[0] != self.num_entities:
                    raise ValueError(
                        f"warm-start entity count {W0.shape[0]} != "
                        f"{self.num_entities}"
                    )
                if self.projector is not None:
                    W0 = W0 @ self.projector.matrix
            else:
                W0 = jnp.zeros(
                    (self.num_entities, self._train_num_features), jnp.float32
                )
            return (W0, bucket_args, feats, ids)

        def apply(static, total, own_score):
            W0, b_args, f_s, i_s = static
            W, V, diag, new_score, new_total = fn(
                total, own_score, W0, b_args, f_s, i_s
            )
            return (W, V, diag), new_score, new_total

        def postprocess(aux, build_model=True):
            W, V, diag = aux
            tracker = RandomEffectTrainingResult(
                coefficients=W,
                variances=V,
                diag_refs=tuple(
                    (pb.entity_ids, f_k, it_k, reason_k)
                    for pb, (f_k, it_k, reason_k) in zip(self._prepared, diag)
                ),
                num_entities=self.num_entities,
            )
            if not build_model:
                return None, tracker
            model = RandomEffectModel(
                coefficients=(
                    self.projector.coefficients_to_original(W)
                    if self.projector is not None else W
                ),
                variances=None if self.projector is not None else V,
                random_effect_type=self.random_effect_type,
                feature_shard_id=self.feature_shard_id,
                task_type=self.task_type,
            )
            return model, tracker

        def advance(aux, static):
            # in-trace twin of postprocess→make_static: the next visit
            # warm-starts from this visit's coefficients. With a random
            # projector the host loop round-trips original→projected space
            # between visits (an approximate JL map) — replicate it so the
            # chunked path is numerically the host path, not a better one.
            W = aux[0]
            if self.projector is not None:
                W = self.projector.coefficients_to_original(W) @ self.projector.matrix
            _, b_args, f_s, i_s = static
            return (W, b_args, f_s, i_s)

        return make_static, apply, postprocess, advance

    def _fuses_lane_sharded(self) -> bool:
        """Whether this coordinate's visit is traced into the descent's one
        program under its mesh: ``fuses_under_mesh``, a dense shard solved
        at its full width, and buckets lane-sharded by ``prepare_buckets``.
        Owned-bucket placement (``PHOTON_RE_SHARD``, with its splits), a
        sparse or projected shard, per-entity column maps and the
        geometry-fusion knob keep the host loop's path under a mesh."""
        from photon_ml_tpu.game.random_effect import fuse_buckets
        from photon_ml_tpu.parallel.placement import re_shard_enabled

        if not (
            fuses_under_mesh(self.batch, self.mesh, self.axis_name)
            and isinstance(self.batch.features[self.feature_shard_id], DenseFeatures)
            and self.projector is None
            and self.features_to_samples_ratio is None
            and not fuse_buckets()
        ):
            return False
        prepared = self.__dict__.get("_prepared_cache")
        if prepared is None and re_shard_enabled():
            return False  # before staging anything the visit would not use
        return all(
            pb.owner is None and pb.columns is None and pb.hash_S is None
            for pb in self._prepared
        )

    def _mesh_bucket_args(self):
        """The buckets as the mesh visit takes them: the effect's own order
        (a device's segment of it over the mesh axis, or None), then the
        staged tensors beside each class's entity ids by LANE, ``(k_pad,)``
        over the mesh axis like the lanes themselves, a padded lane bearing
        ``num_entities`` (one past the last row of the coefficient matrix:
        read as a clamp, dropped by a scatter)."""
        cached = self.__dict__.get("_mesh_bucket_args_cache")
        if cached is None:
            lanes = NamedSharding(self.mesh, P(self.axis_name))
            cached = (shared_order(self._prepared), tuple(
                (
                    pb.static, pb.row_idx, pb.mask,
                    jax.device_put(
                        np.concatenate([
                            np.asarray(pb.entity_ids, np.int32),
                            np.full(
                                pb.mask.shape[0] - pb.num_real,
                                self.num_entities, np.int32,
                            ),
                        ]),
                        lanes,
                    ),
                    None,
                )
                for pb in self._prepared
            ))
            object.__setattr__(self, "_mesh_bucket_args_cache", cached)
        return cached

    def visit(
        self, total: Array, own_score: Array | None,
        initial: GameSubModel | None = None,
    ) -> tuple[RandomEffectModel, RandomEffectTrainingResult, Array, Array]:
        """One descent visit as ONE compiled program (offsets → every
        bucket solve → score → new total), the RE twin of
        ``FixedEffectCoordinate.visit`` — the whole bucket ladder traces
        into a single launch instead of one per bucket (VERDICT r3 weak
        #3: E's per-visit dispatch count, not math, was the floor)."""
        parts = self._fused_visit_parts()
        if parts is None:
            offsets = total - own_score if own_score is not None else total
            sub_model, tracker = self.train(offsets, initial)
            new_score = self.score(sub_model)
            return sub_model, tracker, new_score, offsets + new_score
        make_static, apply, postprocess, _advance = parts
        if own_score is None:
            own_score = jnp.zeros_like(total)
        aux, new_score, new_total = apply(
            make_static(initial), total, own_score
        )
        model, tracker = postprocess(aux)
        return model, tracker, new_score, new_total

    def _build_visit_fn(self):
        from photon_ml_tpu.game.random_effect import _train_prepared_core

        opt = self.config
        loss = loss_for_task(self.task_type)
        l1 = opt.regularization.l1_weight(opt.regularization_weight)
        l2 = opt.regularization.l2_weight(opt.regularization_weight)
        prior_W = prior_V = None
        if self.prior_model is not None:
            _require_prior_l2(self.config)
            prior_W = self.prior_model.coefficients
            prior_V = self.prior_model.variances
            if prior_W.shape[0] != self.num_entities:
                raise ValueError(
                    f"prior entity count {prior_W.shape[0]} != {self.num_entities}"
                )
            if self.projector is not None:
                prior_W = prior_W @ self.projector.matrix
                prior_V = None
        prepared = self._prepared
        mesh, axis = self.mesh, self.axis_name

        def run(total, own_score, W0, bucket_args, feats, ids):
            import dataclasses as _dc

            with stage(VISIT_RE):
                # rebind the device tensors through jit ARGUMENTS (closing over
                # them would bake every bucket tensor and the feature shard
                # into the executable as trace constants — the closure-capture
                # accumulation bench.py isolates per-config subprocesses for);
                # the host-side metadata (entity_ids, num_real) rides the
                # closure, unused in the trace
                order, per_bucket = bucket_args
                if mesh is not None and order is not None:
                    # a device holds its own segment of the order, and a
                    # lane's start counts from the segment's
                    base = jax.lax.axis_index(axis) * order.shape[0]
                    per_bucket = [
                        (s, ri - base, mk, bi, co) for s, ri, mk, bi, co in per_bucket
                    ]
                prep = [
                    _dc.replace(
                        pb, static=s, row_idx=ri, mask=mk, ids=bi, columns=co,
                        order=order,
                    )
                    for pb, (s, ri, mk, bi, co) in zip(prepared, per_bucket)
                ]
                with stage(RE_OFFSETS):
                    offsets = residual = total - own_score
                if mesh is not None:
                    # a device's lanes read rows wherever they lie: the
                    # residual whole on every device, in row order. Every
                    # lane a device holds is solved (its padded lanes bear
                    # an id no scatter takes)
                    with stage(MESH_EXCHANGE):
                        offsets = jax.lax.all_gather(residual, axis, tiled=True)
                    prep = [
                        _dc.replace(pb, num_real=pb.mask.shape[0]) for pb in prep
                    ]
                W, V, diag = _train_prepared_core(
                    prep,
                    offsets,
                    self._train_num_features,
                    self.num_entities,
                    loss,
                    opt.optimizer,
                    l2_weight=l2,
                    l1_weight=l1,
                    intercept_index=(
                        None if self.projector is not None else self.intercept_index
                    ),
                    initial_coefficients=W0,
                    variance_computation=self.variance_computation,
                    norm=self.normalization,
                    prior_coefficients=prior_W,
                    prior_variances=prior_V,
                )
                if mesh is not None:
                    # the matrix whole on every device: the devices' solved
                    # lanes, all of them, scattered by entity id. Copies
                    # only, so a row is the bits its owner solved
                    with stage(MESH_EXCHANGE):
                        lane_ids = jnp.concatenate([pb.ids for pb in prep])
                        every_id = jax.lax.all_gather(lane_ids, axis, tiled=True)

                        def whole(M):
                            own = M.at[lane_ids].get(mode="fill", fill_value=0.0)
                            every = jax.lax.all_gather(own, axis, tiled=True)
                            return M.at[every_id].set(every, mode="drop")

                        W = whole(W)
                        V = None if V is None else whole(V)
                # scoring in the TRAINING subspace: (XP)w_p == X(P w_p), so the
                # projected-space score equals the original-space model's
                from photon_ml_tpu.game.random_effect import random_effect_scores

                with stage(RE_SCORE):
                    in_range = (ids >= 0) & (ids < self.num_entities)
                    safe_ids = jnp.where(in_range, ids, 0)
                    raw = random_effect_scores(feats, safe_ids, W)
                    new_score = jnp.where(in_range, raw, 0.0)
                return W, V, diag, new_score, residual + new_score

        if mesh is None:
            return jax.jit(run)
        # the partitioning written down: rows and lanes over the axis, the
        # coefficient matrix whole on every device
        over = P(axis)
        sharded = jax.shard_map(
            run, mesh=mesh, in_specs=(over, over, P(), over, over, over),
            out_specs=(P(), P(), over, over, over), check_vma=False,
        )

        @jax.jit
        def run_mesh(total, own_score, W0, bucket_args, feats, ids):
            W, V, diag, new_score, new_total = sharded(
                total, own_score, W0, bucket_args, feats, ids
            )
            # a class's diagnostics come back a lane each, padded lanes last
            diag = [
                tuple(a[: pb.num_real] for a in d) for pb, d in zip(prepared, diag)
            ]
            return W, V, diag, new_score, new_total

        return run_mesh
