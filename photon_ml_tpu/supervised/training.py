"""Single-GLM training: regularization sweep with warm start, validation,
model selection, optional coefficient variances.

Reference parity: ``photon-client::ml.ModelTraining.trainGeneralizedLinearModel``
+ the legacy ``Driver`` pipeline (SURVEY.md §3.2): for each λ in ascending
order, train (warm-starting from the previous λ's model), validate, select
best; optionally compute coefficient variances from the Hessian.

TPU-first: each λ's solve is one compiled device program (the optimizer
while-loop); the sweep is a short host loop that re-enters the same compiled
executable (shapes don't change with λ, and λ is a traced array, so there is
exactly ONE compilation for the whole sweep).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Mapping, Sequence

import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.config import OptimizerConfig, RegularizationContext
from photon_ml_tpu.evaluation import EvaluationResults, evaluate_all, make_evaluator
from photon_ml_tpu.models import Coefficients, GeneralizedLinearModel
from photon_ml_tpu.normalization import (
    NormalizationContext,
    require_intercept_for_shifts,
)
from photon_ml_tpu.obs import emit_event, enabled, span
from photon_ml_tpu.obs.spans import GLM_LAMBDA, GLM_TRAIN, spanned
from photon_ml_tpu.ops.batch import Batch
from photon_ml_tpu.ops.glm import GLMObjective, compute_variances, make_objective
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.optim.common import OptimizationResult, select_minimize_fn
from photon_ml_tpu.types import OptimizerType, TaskType, VarianceComputationType

Array = jnp.ndarray


@dataclass(frozen=True)
class GLMTrainingResult:
    """Per-λ models + diagnostics, and the selected best model."""

    models: Mapping[float, GeneralizedLinearModel]
    trackers: Mapping[float, OptimizationResult]
    validation: Mapping[float, EvaluationResults]
    best_weight: float | None

    @property
    def best_model(self) -> GeneralizedLinearModel:
        if self.best_weight is None:
            # no validation data: last λ (reference picks by validation;
            # without it the sweep's final — most regularized — model)
            return self.models[list(self.models)[-1]]
        return self.models[self.best_weight]


@spanned(GLM_TRAIN)
def train_glm(
    batch: Batch,
    task: TaskType,
    optimizer_config: OptimizerConfig | None = None,
    regularization: RegularizationContext | None = None,
    regularization_weights: Sequence[float] = (0.0,),
    normalization: NormalizationContext | None = None,
    intercept_index: int | None = None,
    validation_batch: Batch | None = None,
    evaluators: Sequence[str] = (),
    validation_group_ids: Mapping[str, np.ndarray] | None = None,
    variance_computation: VarianceComputationType = VarianceComputationType.NONE,
    initial_model: GeneralizedLinearModel | None = None,
    axis_name: str | None = None,
    incremental: bool = False,
) -> GLMTrainingResult:
    """Train one GLM per regularization weight (ascending, warm-started),
    validate each, and select the best by the first evaluator.

    ``incremental=True`` turns ``initial_model`` from a plain warm start
    into an informative Gaussian prior (MAP update): the regularizer pulls
    toward the prior model's means with strength 1/variance per coordinate
    (unit precision when the prior model carries no variances — train it
    with ``variance_computation`` to get per-coordinate strengths).

    When ``axis_name`` is set the caller is responsible for invoking this
    inside ``shard_map`` (the distributed layer wraps it); the code is
    identical either way.
    """
    optimizer_config = optimizer_config or OptimizerConfig()
    if regularization is None:
        # default: nonzero weights imply plain L2 (asking for λ>0 with type
        # NONE would silently train unregularized — an easy trap)
        from photon_ml_tpu.types import RegularizationType

        has_weights = any(w > 0 for w in regularization_weights)
        regularization = RegularizationContext(
            RegularizationType.L2 if has_weights else RegularizationType.NONE
        )
    elif regularization.regularization_type.value == "NONE" and any(
        w > 0 for w in regularization_weights
    ):
        raise ValueError(
            "regularization_weights > 0 with RegularizationType.NONE would be "
            "silently ignored; pass an L1/L2/ELASTIC_NET context or drop the weights"
        )
    loss = loss_for_task(task)
    d = batch.num_features
    dtype = batch.labels.dtype

    require_intercept_for_shifts(normalization)

    # The optimizer works in NORMALIZED coefficient space; models are kept in
    # ORIGINAL space (the reference un-applies factors on the final model).
    prior = None
    if initial_model is not None:
        w = jnp.asarray(initial_model.coefficients.means, dtype)
        if normalization is not None:
            w = normalization.model_from_original_space(w)
        if incremental:
            from photon_ml_tpu.ops.glm import GaussianPrior

            if not any(regularization.l2_weight(lam) > 0
                       for lam in regularization_weights):
                raise ValueError(
                    "incremental=True needs at least one sweep weight with a "
                    "positive L2 component: the prior's pull is "
                    "l2_weight * (1/prior_variance)"
                )
            prior = GaussianPrior.from_coefficients(
                initial_model.coefficients.means,
                initial_model.coefficients.variances,
                normalization,
            )
    else:
        if incremental:
            raise ValueError("incremental=True requires initial_model (the prior)")
        w = jnp.zeros((d,), dtype)

    specs = list(evaluators)
    if validation_batch is not None and not specs:
        from photon_ml_tpu.evaluation.evaluators import DEFAULT_EVALUATOR_BY_TASK

        specs = [DEFAULT_EVALUATOR_BY_TASK[task]]
    primary = make_evaluator(specs[0]) if specs else None

    models: dict[float, GeneralizedLinearModel] = {}
    trackers: dict[float, OptimizationResult] = {}
    validation: dict[float, EvaluationResults] = {}
    best_weight: float | None = None
    best_value = float("nan")

    # ascending λ with warm start (reference sweeps the same way)
    for lam in sorted(regularization_weights):
        l1 = regularization.l1_weight(lam)
        l2 = regularization.l2_weight(lam)
        with span(GLM_LAMBDA, weight=float(lam)):
            obj = make_objective(
                batch,
                loss,
                l2_weight=l2,
                norm=normalization,
                intercept_index=intercept_index,
                axis_name=axis_name,
                prior=prior,
            )
            minimize_fn, extra = select_minimize_fn(optimizer_config, l1)
            result = minimize_fn(obj, w, optimizer_config, **extra)
        w = result.w  # warm start the next λ (normalized space)
        if enabled():
            # device solvers return lazily; pull the record only when a
            # sink is live (a host sync per λ is fine, but not for free)
            emit_event(
                "optim_result", weight=float(lam), **result.telemetry_record()
            )

        variances = compute_variances(obj, result.w, variance_computation)
        w_model = result.w
        if normalization is not None:
            w_model, _ = normalization.model_to_original_space(result.w)
            if variances is not None:
                # linear map u = f⊙w ⇒ var scales by f² (diagonal approx.)
                variances = normalization.factors**2 * variances
        model = GeneralizedLinearModel(Coefficients(w_model, variances), task)
        models[lam] = model
        trackers[lam] = result

        if validation_batch is not None and specs:
            # evaluators consume RAW scores (margins + offsets), matching the
            # reference: loss evaluators re-apply the pointwise loss to the
            # margin; AUC is rank-invariant; RMSE on a linear task sees the
            # prediction (identity link). Feeding inverse-link predictions
            # here would evaluate e.g. the Poisson loss at exp(exp(m)).
            scores = model.score(validation_batch)
            res = evaluate_all(
                specs,
                scores,
                validation_batch.labels,
                validation_batch.weights,
                group_ids=validation_group_ids,
            )
            validation[lam] = res
            if primary is not None and (
                best_weight is None or primary.better(res.primary, best_value)
            ):
                best_weight, best_value = lam, res.primary

    return GLMTrainingResult(
        models=models, trackers=trackers, validation=validation, best_weight=best_weight
    )


class _StreamedSweepCheckpoint:
    """Resumable state for the streamed λ sweep: an atomic npz with the
    completed λs' coefficient vectors (rewritten only when a λ finishes)
    plus a separate small per-iteration file holding the in-progress λ's
    latest iterate. Both carry a fingerprint of the sweep setup (task,
    geometry, optimizer config, regularization, data digest), so a changed
    setup retrains instead of silently resuming; corrupt/foreign files are
    ignored, never fatal — a resume feature must not be able to brick runs.

    Multi-host: process 0 alone reads/writes the files (per-host data
    shards give other processes different digests, and shared storage must
    have exactly one writer); ``sync_across_processes`` broadcasts its
    state so every process branches identically.
    """

    def __init__(self, directory, task, chunks, num_features, opt_config, reg,
                 normalization=None, prior=None):
        import hashlib
        import os

        self.directory = directory
        self.done_path = os.path.join(directory, "sweep-done.npz")
        self.partial_path = os.path.join(directory, "sweep-partial.npz")
        first_labels = np.ascontiguousarray(chunks[0]["labels"]) if chunks else np.zeros(0)
        total_rows = sum(len(c["labels"]) for c in chunks)
        # normalization reshapes the optimization trajectory AND the saved
        # coefficient space — resuming under different factors/shifts must
        # be rejected like any other setup change
        norm_token = (
            None
            if normalization is None
            else hashlib.sha256(
                np.ascontiguousarray(
                    np.asarray(normalization.factors, np.float32)
                ).tobytes()
                + np.ascontiguousarray(
                    np.asarray(normalization.shifts, np.float32)
                ).tobytes()
                + repr(normalization.intercept_index).encode()
            ).hexdigest()
        )
        # NOTE: the λ list is deliberately NOT fingerprinted — completed
        # models are keyed by λ, so extending the sweep (the canonical
        # resume-and-extend workflow) reuses what finished and trains the
        # rest. The optimizer config IS fingerprinted: a λ "completed"
        # under a smaller iteration budget is not the model a bigger
        # budget's rerun asks for.
        self.fingerprint = hashlib.sha256(
            repr(
                (
                    task.value,
                    num_features,
                    total_rows,
                    len(chunks),
                    opt_config.optimizer_type.value,
                    opt_config.max_iterations,
                    opt_config.max_cg_iterations,
                    opt_config.history_length,
                    opt_config.max_line_search_steps,
                    opt_config.tolerance,
                    reg.regularization_type.value if reg is not None else None,
                    reg.alpha if reg is not None else None,
                    norm_token,
                    # an incremental prior reshapes the objective itself —
                    # resuming a plain sweep into a MAP sweep (or vice
                    # versa, or under a different prior) must retrain
                    None
                    if prior is None
                    else hashlib.sha256(
                        np.ascontiguousarray(
                            np.asarray(prior.means, np.float32)
                        ).tobytes()
                        + (
                            b""
                            if prior.variances is None
                            else np.ascontiguousarray(
                                np.asarray(prior.variances, np.float32)
                            ).tobytes()
                        )
                    ).hexdigest(),
                )
            ).encode()
            + first_labels.tobytes()
        ).hexdigest()
        self._completed: dict[str, np.ndarray] = {}
        self._partial: tuple[float, np.ndarray] | None = None
        import jax

        if jax.process_index() == 0:
            # only process 0 touches the files; in multi-host runs the
            # caller broadcasts this state via sync_across_processes()
            done = self._load(self.done_path)
            if done is not None:
                z, _ = done
                self._completed = {
                    k[len("done__"):]: z[k] for k in z.files if k.startswith("done__")
                }
            partial = self._load(self.partial_path)
            if partial is not None:
                z, meta = partial
                if "w" in z.files and meta.get("lam") is not None:
                    self._partial = (float(meta["lam"]), z["w"])

    def sync_across_processes(self) -> None:
        """Multi-host: replace every process's view of the checkpoint with
        PROCESS 0's (only process 0 reads/writes the files; per-host data
        shards would otherwise give each process a different fingerprint
        and desynchronize the λ-loop branches, deadlocking the gradient
        collectives). Two broadcast phases: sizes first, then arrays."""
        import jax

        if jax.process_count() <= 1:
            return
        from jax.experimental import multihost_utils as mhu

        d = None
        for v in self._completed.values():
            d = len(v)
            break
        if d is None and self._partial is not None:
            d = len(self._partial[1])
        counts = mhu.broadcast_one_to_all(
            np.asarray(
                [len(self._completed), 1 if self._partial is not None else 0,
                 d if d is not None else 0],
                np.int64,
            )
        )
        k, has_partial, d = int(counts[0]), int(counts[1]), int(counts[2])
        if k == 0 and not has_partial:
            self._completed, self._partial = {}, None
            return
        # every array broadcast in ONE canonical dtype — the stored
        # coefficient dtype varies (f32 from the solver, f64 from resume)
        # and a dtype mismatch between source and placeholder aborts gloo
        if jax.process_index() == 0:
            lams = np.asarray([float(key) for key in self._completed], np.float64)
            W = (
                np.stack(
                    [self._completed[key] for key in self._completed]
                ).astype(np.float64)
                if k
                else np.zeros((0, d))
            )
            plam = np.asarray(
                [self._partial[0] if self._partial is not None else 0.0],
                np.float64,
            )
            pw = (
                np.asarray(self._partial[1], np.float64)
                if self._partial is not None
                else np.zeros(d)
            )
        else:
            lams = np.zeros(k, np.float64)
            W = np.zeros((k, d))
            plam = np.zeros(1)
            pw = np.zeros(d)
        lams, W, plam, pw = mhu.broadcast_one_to_all((lams, W, plam, pw))
        self._completed = {
            repr(float(lams[i])): np.asarray(W[i]) for i in range(k)
        }
        self._partial = (
            (float(plam[0]), np.asarray(pw)) if has_partial else None
        )

    def _load(self, path):
        """(npz, meta) when ``path`` is a valid checkpoint matching this
        sweep's fingerprint; None otherwise (corrupt files included)."""
        import json as _json
        import os

        if not os.path.exists(path):
            return None
        try:
            z = np.load(path, allow_pickle=False)
            meta = _json.loads(bytes(z["__meta__"]).decode())
        except Exception:
            return None  # truncated/foreign file: retrain, don't crash
        if meta.get("fingerprint") != self.fingerprint:
            return None
        return z, meta

    def completed_model(self, lam: float) -> np.ndarray | None:
        got = self._completed.get(repr(float(lam)))
        return None if got is None else np.asarray(got, np.float64)

    def partial_iterate(self, lam: float) -> np.ndarray | None:
        if self._partial is not None and self._partial[0] == float(lam):
            return np.asarray(self._partial[1], np.float64)
        return None

    def save_partial(self, lam: float, w: np.ndarray) -> None:
        # small file, rewritten per accepted iteration — the completed
        # models are immutable and must not be re-serialized that often
        self._partial = (float(lam), np.asarray(w))
        self._write(
            self.partial_path, {"w": self._partial[1]}, {"lam": self._partial[0]}
        )

    def save_completed(self, lam: float, w: np.ndarray) -> None:
        import os

        self._completed[repr(float(lam))] = np.asarray(w)
        self._partial = None
        self._write(
            self.done_path,
            {f"done__{k}": v for k, v in self._completed.items()},
            {},
        )
        try:
            os.remove(self.partial_path)
        except OSError:
            pass

    def _write(self, path: str, arrays: dict, extra_meta: dict) -> None:
        import json as _json
        import os

        from photon_ml_tpu.parallel.multihost import is_output_process

        if not is_output_process():
            return  # multi-host: exactly one writer
        os.makedirs(self.directory, exist_ok=True)
        meta = {"fingerprint": self.fingerprint, **extra_meta}
        arrays = dict(arrays)
        arrays["__meta__"] = np.frombuffer(
            _json.dumps(meta).encode(), dtype=np.uint8
        )
        tmp = path + f".tmp-{os.getpid()}.npz"
        np.savez(tmp, **arrays)
        os.replace(tmp, path)


def train_glm_streamed(
    chunks: Sequence[dict],
    task: TaskType,
    num_features: int,
    optimizer_config: OptimizerConfig | None = None,
    regularization: RegularizationContext | None = None,
    regularization_weights: Sequence[float] = (0.0,),
    intercept_index: int | None = None,
    validation_chunks: Sequence[dict] | None = None,
    evaluators: Sequence[str] = (),
    initial_model: GeneralizedLinearModel | None = None,
    incremental: bool = False,
    cross_process: bool = False,
    checkpoint_dir: str | None = None,
    normalization: NormalizationContext | None = None,
    variance_computation: VarianceComputationType = VarianceComputationType.NONE,
) -> GLMTrainingResult:
    """Out-of-core twin of ``train_glm``: the same ascending-λ warm-started
    sweep, driven by host L-BFGS over a ``StreamingGLMObjective`` (one
    streamed pass per value+gradient evaluation — the reference's Spark
    aggregation pattern; SURVEY.md §7 "Streaming 1B rows").

    ``normalization`` applies inside every streamed objective evaluation
    (factor-folding — zero extra HBM traffic) and is un-applied on the
    saved models, exactly like the in-memory sweep; build the context from
    ``data.summary.summarize_chunks`` over the SAME chunks.
    ``variance_computation`` SIMPLE costs one extra streamed
    Hessian-diagonal pass per λ at its solution; FULL costs one extra
    streamed pass accumulating the d×d Hessian chunk-wise (host-inverted,
    bounded at ``StreamingGLMObjective.FULL_HESSIAN_MAX_D``).
    ``incremental=True`` turns ``initial_model`` into a Gaussian MAP prior
    (means + 1/variance precisions), folded into the streamed objective
    exactly like L2 — the same contract as the in-memory sweep.

    ``chunks`` are uniform host chunk dicts (``photon_ml_tpu.ops.streaming``
    builders or ``AvroDataReader.iter_batch_chunks``). Validation scores
    stream chunk-by-chunk; padded rows carry weight 0, which every
    evaluator treats as absent. The streamed optimizers are host-driven
    L-BFGS and TRON (selected by ``optimizer_config.optimizer_type``);
    a positive L1 weight routes through host OWL-QN, exactly like the
    in-memory path (L1 with TRON is rejected, as in the reference).

    ``checkpoint_dir`` makes the sweep resumable: completed λs' models and
    the in-progress λ's latest iterate are checkpointed (atomic npz with an
    embedded fingerprint of the sweep setup + a data digest); a rerun loads
    completed models and restarts the interrupted λ from its saved iterate
    with a fresh L-BFGS history. Multi-host safe: process 0 owns the files
    and its checkpoint view is broadcast to every process, so all λ-loop
    branches are taken identically and the gradient collectives stay
    matched.
    """
    from photon_ml_tpu.ops.streaming import StreamingGLMObjective, stream_scores
    from photon_ml_tpu.optim.common import select_minimize_fn
    from photon_ml_tpu.types import RegularizationType

    optimizer_config = optimizer_config or OptimizerConfig()
    has_weights = any(w > 0 for w in regularization_weights)
    if regularization is None:
        # same default as train_glm: nonzero weights imply plain L2
        regularization = RegularizationContext(
            RegularizationType.L2 if has_weights else RegularizationType.NONE
        )
    # fail fast on unsupported combinations BEFORE any data work: the
    # selection rule (and its rejections) is shared with the in-memory path
    select_minimize_fn(
        optimizer_config, regularization.l1_weight(1.0), host=True
    )
    if regularization.regularization_type is RegularizationType.NONE and has_weights:
        raise ValueError(
            "regularization_weights > 0 with RegularizationType.NONE would be "
            "silently ignored; pass an L2 context or drop the weights"
        )
    if variance_computation is VarianceComputationType.FULL:
        from photon_ml_tpu.ops.streaming import StreamingGLMObjective as _S

        if num_features > _S.FULL_HESSIAN_MAX_D:
            # fail BEFORE the first λ's full streamed solve, not after it
            raise ValueError(
                f"streamed FULL variance supports d <= {_S.FULL_HESSIAN_MAX_D} "
                f"(got {num_features}); use SIMPLE at this width"
            )
    require_intercept_for_shifts(normalization)
    loss = loss_for_task(task)
    # the optimizer works in NORMALIZED coefficient space (models are saved
    # in original space, same contract as the in-memory sweep)
    prior = None
    if initial_model is not None:
        w0 = jnp.asarray(initial_model.coefficients.means, jnp.float32)
        if normalization is not None:
            w0 = normalization.model_from_original_space(w0)
        w = np.asarray(w0, np.float32)
        if incremental:
            # same contract as the in-memory sweep: the loaded model
            # becomes a Gaussian MAP prior, which needs a positive L2
            # component somewhere in the sweep to have any pull
            from photon_ml_tpu.ops.glm import GaussianPrior

            if not any(
                regularization.l2_weight(lam) > 0
                for lam in regularization_weights
            ):
                raise ValueError(
                    "incremental=True needs at least one sweep weight with a "
                    "positive L2 component: the prior's pull is "
                    "l2_weight * (1/prior_variance)"
                )
            prior = GaussianPrior.from_coefficients(
                initial_model.coefficients.means,
                initial_model.coefficients.variances,
                normalization,
            )
    else:
        if incremental:
            raise ValueError("incremental=True requires initial_model (the prior)")
        w = np.zeros((num_features,), np.float32)

    specs = list(evaluators)
    if validation_chunks is not None and not specs:
        specs = {
            TaskType.LOGISTIC_REGRESSION: ["AUC"],
            TaskType.LINEAR_REGRESSION: ["RMSE"],
            TaskType.POISSON_REGRESSION: ["POISSON_LOSS"],
            TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM: ["AUC"],
        }[task]
    primary = make_evaluator(specs[0]) if specs else None

    val_labels = val_weights = val_offsets = None
    if validation_chunks is not None:
        val_labels = np.concatenate([c["labels"] for c in validation_chunks])
        val_weights = np.concatenate([c["weights"] for c in validation_chunks])
        val_offsets = np.concatenate([c["offsets"] for c in validation_chunks])

    models: dict[float, GeneralizedLinearModel] = {}
    trackers: dict[float, OptimizationResult] = {}
    validation: dict[float, EvaluationResults] = {}
    best_weight: float | None = None
    best_value = float("nan")

    ckpt = (
        _StreamedSweepCheckpoint(
            checkpoint_dir, task, chunks, num_features, optimizer_config,
            regularization, normalization=normalization, prior=prior,
        )
        if checkpoint_dir is not None
        else None
    )
    if ckpt is not None and cross_process:
        # multi-host: all processes adopt process 0's checkpoint view, so
        # every λ-loop branch (load vs train vs resume-from-iterate) is
        # taken identically and the gradient collectives stay matched
        ckpt.sync_across_processes()

    # ONE objective for the whole sweep: its per-chunk kernels are built
    # λ-free (λ applied outside the jit), so mutating l2_weight between λs
    # re-enters the same compiled programs — no recompilation across the grid
    sobj = StreamingGLMObjective(
        chunks, loss, num_features=num_features, l2_weight=0.0,
        intercept_index=intercept_index, cross_process=cross_process,
        norm=normalization,
        prior_mean=None if prior is None else prior.means,
        prior_precision=None if prior is None else prior.precisions,
        # FULL needs the raw per-chunk indices for its densified Hessian
        # pass; the auto tile-COO layout drops them
        tile_sparse=(
            False
            if variance_computation is VarianceComputationType.FULL
            else None
        ),
    )
    fe = getattr(sobj, "fe_active", False)
    if fe:
        if ckpt is not None:
            # checkpoints store FULL-space iterates with a fingerprint
            # over the unsharded chunk set; a per-range resume contract
            # (and cross-P re-partitioned resume) is future work — fail
            # loudly rather than write shard-local iterates a later
            # unsharded run would load as full vectors
            raise NotImplementedError(
                "checkpoint_dir with PHOTON_FE_SHARD=1 is not supported; "
                "disable sharding or drop the checkpoint directory"
            )
        if variance_computation is VarianceComputationType.FULL:
            # the streamed FULL pass densifies a d x d Hessian from raw
            # chunk indices; the sharded objective only holds its range
            raise NotImplementedError(
                "FULL variances with PHOTON_FE_SHARD=1 are not supported; "
                "use SIMPLE (per-range diagonal, gathered exactly)"
            )
        # the optimizer iterates on this process's range shard; model
        # assembly gathers the full vector per λ below
        w = sobj.fe_slice(w)
    for lam in sorted(regularization_weights):
        done_w = ckpt.completed_model(lam) if ckpt is not None else None
        if done_w is not None:
            w = done_w
            result = None
            sobj.l2_weight = float(regularization.l2_weight(lam))
        else:
            sobj.l2_weight = float(regularization.l2_weight(lam))
            resume_w = ckpt.partial_iterate(lam) if ckpt is not None else None
            minimize, extra = select_minimize_fn(
                optimizer_config, regularization.l1_weight(lam), host=True
            )
            result = minimize(
                sobj,
                resume_w if resume_w is not None else w,
                optimizer_config,
                iteration_callback=(
                    None if ckpt is None else lambda it, wi, f: ckpt.save_partial(lam, wi)
                ),
                **extra,
            )
            w = np.asarray(result.w)  # warm start the next λ (normalized space)
            if ckpt is not None:
                ckpt.save_completed(lam, w)

        variances = None
        if variance_computation is not VarianceComputationType.NONE:
            from photon_ml_tpu.ops.glm import compute_variances

            # one extra streamed pass at the solution (checkpoint-loaded λs
            # included — variances are not checkpointed); the shared
            # implementation consumes the streaming objective's
            # hessian_diag (SIMPLE) or its chunk-accumulated d×d hessian
            # (FULL, host-inverted, d-bounded) directly
            variances = compute_variances(
                sobj, jnp.asarray(w, jnp.float32), variance_computation
            )
            if fe and variances is not None:
                # SIMPLE variances are elementwise in the Hessian
                # diagonal, and the sharded diagonal is this range's
                # DISJOINT segment — the gather is exact
                variances = jnp.asarray(sobj.fe_gather(np.asarray(variances)))
        # under PHOTON_FE_SHARD the iterate is this process's range
        # shard; the saved model (and validation scoring) need the full
        # vector — a fixed ascending-order gather, pure data movement
        w_model = jnp.asarray(sobj.fe_gather(w) if fe else w, jnp.float32)
        if normalization is not None:
            w_model, _ = normalization.model_to_original_space(w_model)
            if variances is not None:
                variances = normalization.factors**2 * variances
        model = GeneralizedLinearModel(
            Coefficients(w_model, variances), task
        )
        models[lam] = model
        if result is not None:
            trackers[lam] = result

        if validation_chunks is not None and specs:
            n_val = len(val_labels)
            # validation chunks carry RAW features — score with the
            # ORIGINAL-space coefficients
            margins = stream_scores(
                validation_chunks, np.asarray(w_model), num_rows=n_val,
                num_features=num_features,
            )
            res = evaluate_all(
                specs,
                jnp.asarray(margins + val_offsets),
                jnp.asarray(val_labels),
                jnp.asarray(val_weights),
            )
            validation[lam] = res
            if primary is not None and (
                best_weight is None or primary.better(res.primary, best_value)
            ):
                best_weight, best_value = lam, res.primary

    return GLMTrainingResult(
        models=models, trackers=trackers, validation=validation, best_weight=best_weight
    )
