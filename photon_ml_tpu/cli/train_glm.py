"""Legacy single-GLM training driver.

Reference parity: ``photon-client::ml.Driver`` + ``ml.DriverStage`` +
``ml.ModelTraining`` (SURVEY.md §2.3, §3.2): a staged pipeline
(INIT → PROCESSED → TRAINED → VALIDATED) that trains one GLM per
regularization weight (ascending, warm-started), validates each, selects
the best, and writes per-λ models + feature summary + best model.

Input formats: LIBSVM (benchmark config A) or TrainingExampleAvro files.

Usage:
    python -m photon_ml_tpu.cli.train_glm \\
        --task LOGISTIC_REGRESSION --train-data a9a.libsvm --format libsvm \\
        --regularization L2 --weights 0.1 1 10 --output-dir out/
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np

from photon_ml_tpu.config import OptimizerConfig, RegularizationContext
from photon_ml_tpu.data.libsvm import read_libsvm
from photon_ml_tpu.data.summary import summarize
from photon_ml_tpu.data.validation import validate_arrays
from photon_ml_tpu.io.data_reader import AvroDataReader
from photon_ml_tpu.io.model_io import save_glm
from photon_ml_tpu.io.results import write_feature_summary
from photon_ml_tpu.supervised.training import train_glm
from photon_ml_tpu.types import (
    DataValidationType,
    NormalizationType,
    OptimizerType,
    RegularizationType,
    TaskType,
    VarianceComputationType,
)
from photon_ml_tpu.utils import PhotonLogger, profile_trace, timed
from photon_ml_tpu.utils.compile_cache import configure_compile_cache

STAGES = ("INIT", "PROCESSED", "TRAINED", "VALIDATED")


def _read(paths: list[str], fmt: str, index_maps=None, num_features=None):
    if fmt == "libsvm":
        if len(paths) != 1:
            raise ValueError("libsvm input takes exactly one file")
        batch, intercept_index = read_libsvm(paths[0], num_features=num_features)
        return batch, intercept_index, None
    reader = AvroDataReader()
    ds = reader.read(paths, index_maps=index_maps)
    sid = next(iter(ds.index_maps))
    return (
        ds.batch.batch_for(sid),
        ds.intercept_indices[sid],
        ds,
    )


def run(
    task: TaskType,
    train_data: list[str],
    output_dir: str,
    data_format: str = "libsvm",
    validation_data: list[str] | None = None,
    regularization: RegularizationType = RegularizationType.L2,
    weights: list[float] = (1.0,),
    optimizer: OptimizerType = OptimizerType.LBFGS,
    max_iterations: int = 100,
    tolerance: float = 1e-7,
    normalization: NormalizationType = NormalizationType.NONE,
    summarize_features: bool = False,
    variance_computation: VarianceComputationType = VarianceComputationType.NONE,
    validate: DataValidationType = DataValidationType.VALIDATE_DISABLED,
    streaming_chunk_rows: int | None = None,
    multihost: bool = False,
    logger: PhotonLogger | None = None,
    profile_dir: str | None = None,
    prior_model_path: str | None = None,
    diagnostics: bool = False,
):
    if multihost and streaming_chunk_rows is None:
        raise ValueError(
            "--multihost requires --streaming-chunk-rows (per-host sharded "
            "ingest exists on the streaming path; in-memory multihost GLM "
            "training goes through the GAME driver's --multihost)"
        )
    logger = logger or PhotonLogger(output_dir)
    stage_file = os.path.join(output_dir, "_stage")

    def advance(stage: str) -> None:
        os.makedirs(output_dir, exist_ok=True)
        with open(stage_file, "w") as f:
            f.write(stage)
        logger.info(f"stage → {stage}")

    if streaming_chunk_rows is not None:
        # reject — not silently drop — options the streaming branch can't honor
        unsupported = []
        if optimizer not in (OptimizerType.LBFGS, OptimizerType.TRON):
            unsupported.append(
                f"--optimizer {optimizer.value} (streaming offers LBFGS/TRON)"
            )
        if optimizer is OptimizerType.TRON and regularization in (
            RegularizationType.L1, RegularizationType.ELASTIC_NET
        ):
            unsupported.append(
                f"--optimizer TRON with --regularization {regularization.value} "
                f"(L1 routes through OWL-QN; use LBFGS)"
            )
        if unsupported:
            raise ValueError(
                "--streaming-chunk-rows does not support: "
                + ", ".join(unsupported)
            )
        return _run_streamed(
            task, train_data, output_dir, data_format, validation_data,
            regularization, weights, max_iterations, tolerance,
            streaming_chunk_rows, advance, logger, multihost=multihost,
            profile_dir=profile_dir, optimizer=optimizer,
            normalization=normalization,
            variance_computation=variance_computation,
            summarize_features=summarize_features,
            validate=validate,
            prior_model_path=prior_model_path,
            diagnostics=diagnostics,
        )

    advance("INIT")
    with timed(logger, "read training data"):
        batch, intercept_index, train_ds = _read(train_data, data_format)
    if validate is not DataValidationType.VALIDATE_DISABLED:
        with timed(logger, "validate data"):
            validate_arrays(
                task,
                np.asarray(batch.labels),
                np.asarray(batch.X)
                if hasattr(batch, "X")
                else np.asarray(batch.values),
                offsets=np.asarray(batch.offsets),
                weights=np.asarray(batch.weights),
                mode=validate,
            )

    norm_context = None
    if summarize_features or normalization is not NormalizationType.NONE:
        with timed(logger, "summarize features"):
            summary = summarize(batch)
            if summarize_features:
                write_feature_summary(
                    os.path.join(output_dir, "summary", "part-00000.avro"),
                    summary,
                    None if train_ds is None else next(iter(train_ds.index_maps.values())),
                )
            if normalization is not NormalizationType.NONE:
                norm_context = summary.normalization(normalization, intercept_index)
    advance("PROCESSED")

    val_batch = None
    if validation_data:
        with timed(logger, "read validation data"):
            val_batch, _, _ = _read(
                validation_data,
                data_format,
                index_maps=None if train_ds is None else train_ds.index_maps,
                # libsvm: pin the validation feature space to the training one
                num_features=(
                    batch.num_features - (1 if intercept_index is not None else 0)
                    if data_format == "libsvm"
                    else None
                ),
            )

    prior_model = None
    if prior_model_path:
        with timed(logger, "load prior model"):
            from photon_ml_tpu.io.model_io import load_glm

            prior_model = load_glm(
                prior_model_path,
                index_map=(
                    None if train_ds is None
                    else next(iter(train_ds.index_maps.values()))
                ),
                num_features=batch.num_features,
                task=task,
            )

    # layout decision AFTER validation/summary (both read raw columns):
    # densify small-d; re-block genuinely high-dimensional sparse data into
    # the tile-COO Pallas kernels (~9x over XLA gather/scatter). The
    # summary-derived normalization factors fold into the weight vector, so
    # the optimized layout composes with them unchanged.
    from photon_ml_tpu.ops.batch import optimize_batch_layout
    from photon_ml_tpu.ops.streaming import device_hbm_budget_bytes

    with timed(logger, "optimize batch layout"):
        batch = optimize_batch_layout(
            batch, hbm_budget_bytes=device_hbm_budget_bytes()
        )

    with timed(logger, "train"), profile_trace(profile_dir, "glm-sweep"):
        result = train_glm(
            batch,
            task,
            optimizer_config=OptimizerConfig(
                optimizer_type=optimizer,
                max_iterations=max_iterations,
                tolerance=tolerance,
            ),
            regularization=RegularizationContext(regularization),
            regularization_weights=list(weights),
            normalization=norm_context,
            intercept_index=intercept_index,
            validation_batch=val_batch,
            variance_computation=variance_computation,
            initial_model=prior_model,
            incremental=prior_model is not None,
        )
    advance("TRAINED")

    imap = (
        None if train_ds is None else next(iter(train_ds.index_maps.values()))
    )
    with timed(logger, "write models"):
        for lam, model in result.models.items():
            save_glm(
                model,
                os.path.join(output_dir, "models", f"lambda-{lam:g}", "model.avro"),
                index_map=imap,
                model_id=f"lambda-{lam:g}",
            )
        save_glm(
            result.best_model,
            os.path.join(output_dir, "best", "model.avro"),
            index_map=imap,
            model_id="best",
        )

    report = {
        "task": task.value,
        "weights": sorted(float(w) for w in weights),
        "best_weight": result.best_weight,
        "validation": {
            str(lam): dict(ev.metrics) for lam, ev in result.validation.items()
        },
        "trackers": {
            str(lam): {
                "iterations": int(t.iterations),
                "converged": bool(t.converged),
            }
            for lam, t in result.trackers.items()
        },
    }
    with open(os.path.join(output_dir, "report.json"), "w") as f:
        json.dump(report, f, indent=2)
    if diagnostics:
        from photon_ml_tpu.diagnostics import glm_sweep_diagnostics, write_report

        with timed(logger, "write diagnostics"):
            write_report(
                glm_sweep_diagnostics(result, index_map=imap, task=task),
                output_dir,
            )
    advance("VALIDATED")
    return result


def _expand_avro_paths(paths: list[str]) -> list[str]:
    """Directories become their sorted ``*.avro`` part files (the shared
    ``list_avro_files`` policy), so per-host path sharding distributes
    FILES, not whole directories."""
    from photon_ml_tpu.io.avro import list_avro_files

    return [f for p in paths for f in list_avro_files(p)]


def _run_streamed(
    task, train_data, output_dir, data_format, validation_data,
    regularization, weights, max_iterations, tolerance,
    chunk_rows, advance, logger, multihost: bool = False,
    profile_dir: str | None = None,
    optimizer: OptimizerType = OptimizerType.LBFGS,
    normalization: NormalizationType = NormalizationType.NONE,
    variance_computation: VarianceComputationType = VarianceComputationType.NONE,
    summarize_features: bool = False,
    validate: DataValidationType = DataValidationType.VALIDATE_DISABLED,
    prior_model_path: str | None = None,
    diagnostics: bool = False,
):
    """Out-of-core branch: data is read in uniform chunks that live in host
    RAM and stream through the device per optimizer iteration (SURVEY.md §7
    "Streaming 1B rows"). Avro input only — LIBSVM fits in memory whenever
    its text fits.

    Multi-host: the stats pass (index maps + max nnz) covers ALL files so
    every host agrees on the feature space; each host then fills chunks
    only from ITS slice of the part files, and the streaming objective sums
    partial (value, gradient) across processes per evaluation. Validation
    files are read replicated so metrics are global and identical on every
    host. Only process 0 writes outputs.
    """
    if data_format != "avro":
        raise ValueError("--streaming-chunk-rows requires --format avro")
    from photon_ml_tpu.supervised.training import train_glm_streamed
    from photon_ml_tpu.parallel.multihost import is_output_process, sync_processes

    reader = AvroDataReader()
    sid = next(iter(reader.feature_shards))
    writer = is_output_process()

    def advance_once(stage):
        if writer:
            advance(stage)

    train_paths = _expand_avro_paths(train_data)
    local_paths = train_paths
    if multihost:
        from photon_ml_tpu.parallel.multihost import host_shard_of_paths

        local_paths = host_shard_of_paths(train_paths)
        logger.info(f"this host reads {len(local_paths)}/{len(train_paths)} files")

    advance_once("INIT")
    with timed(logger, "index maps (streaming pass, all files)"):
        index_maps, max_nnz = reader.streaming_ingest_stats(train_paths)
    imap = index_maps[sid]
    with timed(logger, "chunk training data (this host's files)"):
        chunks = list(
            reader.iter_batch_chunks(
                local_paths, sid, chunk_rows, index_maps, max_nnz=max_nnz[sid]
            )
        ) if local_paths else []
    logger.info(f"{len(chunks)} training chunks of {chunk_rows} rows")

    if validate is not DataValidationType.VALIDATE_DISABLED:
        from photon_ml_tpu.data.validation import DataValidationError

        with timed(logger, "validate data (streamed, per chunk)"):
            # FULL checks every chunk; SAMPLE thins rows inside each chunk
            # (validate_arrays' own sampling, seeded per chunk) — either
            # way the whole dataset is covered chunk by chunk, the
            # streamed twin of the in-memory one-shot validation
            failure: str | None = None
            for ci, chunk in enumerate(chunks):
                try:
                    validate_arrays(
                        task,
                        chunk["labels"],
                        chunk.get("X", chunk.get("values")),
                        offsets=chunk.get("offsets"),
                        weights=chunk.get("weights"),
                        mode=validate,
                        seed=ci,
                    )
                except DataValidationError as e:
                    # chunk-addressed: on a billion-row stream the operator
                    # needs WHERE, not just what
                    failure = (
                        f"chunk {ci} (rows {ci * chunk_rows}.."
                        f"{ci * chunk_rows + len(chunk['labels'])} of this "
                        f"host's stream): {e}"
                    )
                    break
            if multihost:
                # agree across hosts BEFORE raising: a host that raised
                # alone would abandon the later collectives and hang the
                # clean hosts
                from photon_ml_tpu.parallel.multihost import (
                    allreduce_max_host,
                )

                any_failed = allreduce_max_host(
                    np.asarray([1.0 if failure is not None else 0.0])
                )
                if float(any_failed[0]) > 0 and failure is None:
                    failure = "validation failed on another host"
            if failure is not None:
                raise DataValidationError(failure)

    norm_context = None
    if summarize_features or normalization is not NormalizationType.NONE:
        from photon_ml_tpu.data.summary import summarize_chunks

        with timed(logger, "summarize features (streamed, this host's chunks)"):
            # cross_process makes the summary GLOBAL — every host builds the
            # identical normalization context from its own chunks
            summary = summarize_chunks(
                chunks, num_features=imap.size, cross_process=multihost
            )
        if summarize_features and writer:
            write_feature_summary(
                os.path.join(output_dir, "summary", "part-00000.avro"),
                summary,
                imap,
            )
        if normalization is not NormalizationType.NONE:
            norm_context = summary.normalization(
                normalization, imap.intercept_index
            )
    advance_once("PROCESSED")

    val_chunks = None
    if validation_data:
        with timed(logger, "chunk validation data"):
            val_chunks = list(
                reader.iter_batch_chunks(
                    _expand_avro_paths(validation_data), sid, chunk_rows, index_maps
                )
            )

    prior_model = None
    if prior_model_path:
        # incremental training on the streamed path: the loaded model
        # becomes warm start + Gaussian MAP prior, folded into the
        # streamed objective exactly like L2 (same contract as in-memory)
        with timed(logger, "load prior model"):
            from photon_ml_tpu.io.model_io import load_glm

            prior_model = load_glm(
                prior_model_path,
                index_map=imap,
                num_features=imap.size,
                task=task,
            )

    with timed(logger, "train (streamed)"), profile_trace(
        profile_dir, "glm-sweep-streamed"
    ):
        result = train_glm_streamed(
            chunks,
            task,
            num_features=imap.size,
            optimizer_config=OptimizerConfig(
                optimizer_type=optimizer,
                max_iterations=max_iterations,
                tolerance=tolerance,
            ),
            regularization=RegularizationContext(regularization),
            regularization_weights=list(weights),
            intercept_index=imap.intercept_index,
            validation_chunks=val_chunks,
            initial_model=prior_model,
            incremental=prior_model is not None,
            cross_process=multihost,
            checkpoint_dir=os.path.join(output_dir, "checkpoints"),
            normalization=norm_context,
            variance_computation=variance_computation,
        )
    advance_once("TRAINED")

    if writer:
        with timed(logger, "write models"):
            for lam, model in result.models.items():
                save_glm(
                    model,
                    os.path.join(output_dir, "models", f"lambda-{lam:g}", "model.avro"),
                    index_map=imap,
                    model_id=f"lambda-{lam:g}",
                )
            save_glm(
                result.best_model,
                os.path.join(output_dir, "best", "model.avro"),
                index_map=imap,
                model_id="best",
            )
        report = {
            "task": task.value,
            "streaming_chunk_rows": chunk_rows,
            "weights": sorted(float(w) for w in weights),
            "best_weight": result.best_weight,
            "validation": {
                str(lam): dict(ev.metrics) for lam, ev in result.validation.items()
            },
        }
        with open(os.path.join(output_dir, "report.json"), "w") as f:
            json.dump(report, f, indent=2)
        if diagnostics:
            # the report consumes only the training RESULT (models,
            # trackers, validation) — no raw data — so the streamed sweep
            # feeds it exactly like the in-memory one
            from photon_ml_tpu.diagnostics import glm_sweep_diagnostics, write_report

            with timed(logger, "write diagnostics"):
                write_report(
                    glm_sweep_diagnostics(result, index_map=imap, task=task),
                    output_dir,
                )
        advance("VALIDATED")
    sync_processes("train-glm-outputs-written")
    return result


def main(argv: list[str] | None = None) -> None:
    configure_compile_cache()
    p = argparse.ArgumentParser(description="Single-GLM training driver (legacy)")
    p.add_argument("--task", required=True, choices=[t.value for t in TaskType])
    p.add_argument("--train-data", required=True, nargs="+")
    p.add_argument("--validation-data", nargs="*", default=None)
    p.add_argument("--format", default="libsvm", choices=["libsvm", "avro"])
    p.add_argument(
        "--regularization", default="L2", choices=[r.value for r in RegularizationType]
    )
    p.add_argument("--weights", nargs="+", type=float, default=[1.0])
    p.add_argument("--optimizer", default="LBFGS", choices=[o.value for o in OptimizerType])
    p.add_argument("--max-iterations", type=int, default=100)
    p.add_argument("--tolerance", type=float, default=1e-7)
    p.add_argument(
        "--normalization", default="NONE", choices=[n.value for n in NormalizationType]
    )
    p.add_argument("--summarize-features", action="store_true")
    p.add_argument(
        "--variance", default="NONE", choices=[v.value for v in VarianceComputationType]
    )
    p.add_argument(
        "--validate", default="VALIDATE_DISABLED",
        choices=[v.value for v in DataValidationType],
    )
    p.add_argument(
        "--streaming-chunk-rows", type=int, default=None,
        help="out-of-core mode: stream avro data through the device in "
             "uniform chunks of this many rows (host-RAM resident)",
    )
    p.add_argument(
        "--multihost", action="store_true",
        help="join the jax.distributed runtime and shard the input part "
             "files across hosts (streaming mode only; run the SAME "
             "command on every host)",
    )
    p.add_argument(
        "--profile-dir", default=None,
        help="capture jax.profiler device traces of the training sweep",
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
             "(optimizer traces, validation metrics, top features)",
    )
    p.add_argument(
        "--prior-model", default=None,
        help="incremental training: path to a previously saved model Avro "
             "whose means/variances become an informative Gaussian prior "
             "(MAP update) and the warm-start point",
    )
    p.add_argument("--output-dir", required=True)
    args = p.parse_args(argv)
    if args.multihost:
        from photon_ml_tpu.parallel.multihost import initialize_multihost

        initialize_multihost()
    from photon_ml_tpu import obs

    obs.configure(args.telemetry_dir)
    try:
        run(
            TaskType(args.task),
            args.train_data,
            args.output_dir,
            data_format=args.format,
            validation_data=args.validation_data,
            regularization=RegularizationType(args.regularization),
            weights=args.weights,
            optimizer=OptimizerType(args.optimizer),
            max_iterations=args.max_iterations,
            tolerance=args.tolerance,
            normalization=NormalizationType(args.normalization),
            summarize_features=args.summarize_features,
            variance_computation=VarianceComputationType(args.variance),
            validate=DataValidationType(args.validate),
            prior_model_path=args.prior_model,
            diagnostics=args.diagnostics,
            streaming_chunk_rows=args.streaming_chunk_rows,
            multihost=args.multihost,
            profile_dir=args.profile_dir,
        )
    finally:
        obs.shutdown()


if __name__ == "__main__":
    main()
