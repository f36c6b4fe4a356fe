"""GAME scoring driver.

Reference parity: ``photon-client::ml.cli.game.scoring.GameScoringDriver``
(SURVEY.md §2.3, §3.3): load model + data, score via ``GameTransformer``,
write ``ScoringResultAvro``, optional evaluation.

Usage:
    python -m photon_ml_tpu.cli.score \\
        --model-dir out/ --data data/test --output-dir scores/ \\
        [--evaluators AUC LOGISTIC_LOSS] [--feature-shards config.json]
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np

from photon_ml_tpu.cli.common import load_training_config
from photon_ml_tpu.config import FeatureShardConfig
from photon_ml_tpu.data.index_map import IndexMap
from photon_ml_tpu.io.data_reader import AvroDataReader
from photon_ml_tpu.io.model_io import load_game_model
from photon_ml_tpu.io.results import write_scoring_results
from photon_ml_tpu.game.models import RandomEffectModel
from photon_ml_tpu.transformers import GameTransformer
from photon_ml_tpu.utils import PhotonLogger, profile_trace, timed
from photon_ml_tpu.utils.compile_cache import configure_compile_cache


def run(
    model_dir: str,
    data: list[str],
    output_dir: str,
    evaluators: list[str] | None = None,
    feature_shards: dict[str, FeatureShardConfig] | None = None,
    logger: PhotonLogger | None = None,
    profile_dir: str | None = None,
    multihost: bool = False,
):
    """``model_dir`` is a training output dir (contains ``best/``,
    ``index-maps/``, ``entity-maps.json``) or a bare model dir with the
    maps alongside.

    ``multihost``: scoring is per-row independent, so each host loads the
    (replicated, on-disk) model, scores ITS round-robin slice of the input
    part files, and writes its own output partition
    (``part-{process_index:05d}.avro``) — no collectives on the scoring
    path itself. Requested scalar metrics are computed GLOBALLY by
    allgathering (score, label, weight) with zero-weight padding (inert to
    every evaluator), identically on every host; grouped (Multi*)
    evaluators owner-route (score, label, entity id) rows once per id tag
    and combine per-group partials — which needs the tag's GLOBAL entity
    dictionary, i.e. a training-saved entity map. Process 0 writes
    ``metrics.json``.
    """
    import jax

    part_index = 0
    if multihost:
        from photon_ml_tpu.io.avro import list_avro_files
        from photon_ml_tpu.parallel.multihost import (
            host_shard_of_paths,
            is_output_process,
        )

        # one process owns the shared log file; the rest log to stderr
        logger = logger or PhotonLogger(
            output_dir if is_output_process() else None
        )

        files: list[str] = []
        for p_ in data:
            files.extend(list_avro_files(p_))
        data = host_shard_of_paths(files)
        part_index = jax.process_index()
        logger.info(
            f"multihost scoring: this host scores {len(data)}/{len(files)} files"
        )
    logger = logger or PhotonLogger(output_dir)

    best_dir = os.path.join(model_dir, "best")
    if os.path.isdir(best_dir):
        game_dir = best_dir
        maps_root = model_dir
    else:
        game_dir = model_dir
        maps_root = os.path.dirname(model_dir.rstrip("/"))

    with timed(logger, "load model + maps"):
        index_maps = {}
        imap_dir = os.path.join(maps_root, "index-maps")
        if os.path.isdir(imap_dir):
            for fn in os.listdir(imap_dir):
                if fn.endswith(".npz"):
                    index_maps[fn[:-4]] = IndexMap.load(os.path.join(imap_dir, fn))
        entity_maps = {}
        em_path = os.path.join(maps_root, "entity-maps.json")
        if os.path.exists(em_path):
            with open(em_path) as f:
                entity_maps = json.load(f)
        entity_ids = None
        if entity_maps:
            entity_ids = {
                cid: entity_maps[retype]
                for cid, retype in _random_effects(game_dir).items()
                if retype in entity_maps
            }
        model = load_game_model(game_dir, index_maps=index_maps, entity_ids=entity_ids)

    id_tags = tuple(
        sub.random_effect_type
        for sub in model.models.values()
        if isinstance(sub, RandomEffectModel)
    )
    if evaluators:
        # grouped (Multi*) evaluators group on ANY datum id tag, not only
        # the model's random-effect types (SURVEY §2.2 evaluators row) —
        # the reader must extract those columns too
        from photon_ml_tpu.evaluation import make_evaluator

        eval_tags = [
            make_evaluator(s).group_by
            for s in evaluators
            if make_evaluator(s).group_by is not None
        ]
        id_tags = tuple(dict.fromkeys([*id_tags, *eval_tags]))
        missing = [t for t in eval_tags if t not in entity_maps]
        if missing and (multihost or entity_maps):
            # multihost: per-host reader dictionaries would disagree.
            # single-host with OTHER frozen maps present: the reader would
            # freeze the missing tag to an empty map (every id -> the -1
            # sentinel), silently evaluating the metric over nothing. Only
            # a model dir with NO entity-maps.json at all lets the reader
            # build fresh single-host dictionaries for every tag.
            raise ValueError(
                f"grouped evaluators need the id tags in the "
                f"training-saved entity-maps.json; missing: {missing} "
                f"(declare the evaluator at training time so its tag's "
                f"entity map is extracted and saved)"
            )
    reader = AvroDataReader(feature_shards)
    ds = None
    # single-host empty input keeps its loud error; only a multihost member
    # may legitimately hold fewer part files than its peers
    if data or not multihost:
        with timed(logger, "read scoring data"):
            ds = reader.read(
                data,
                id_tags=id_tags,
                index_maps=index_maps or None,
                entity_maps={t: entity_maps[t] for t in id_tags} if entity_maps else None,
            )

    from photon_ml_tpu.obs import span

    transformer = GameTransformer(model, logger=logger)
    metrics = None
    with timed(logger, "score"), profile_trace(profile_dir, "score"), span(
        "score/pass"
    ):
        if evaluators and not multihost:
            scores, results = transformer.transform_with_evaluation(
                ds.batch, evaluators
            )
            metrics = dict(results.metrics)
        elif ds is not None:
            scores = transformer.transform(ds.batch)
        else:
            scores = np.zeros(0)
        if evaluators and multihost:
            from photon_ml_tpu.evaluation import make_evaluator

            scalar_specs = [
                s for s in evaluators if make_evaluator(s).group_by is None
            ]
            grouped_specs = [
                s for s in evaluators if make_evaluator(s).group_by is not None
            ]
            metrics = {}
            if scalar_specs:
                metrics.update(_global_metrics_multihost(
                    scalar_specs,
                    np.asarray(scores),
                    np.asarray(ds.batch.labels) if ds is not None else np.zeros(0),
                    np.asarray(ds.batch.weights) if ds is not None else np.zeros(0),
                ))
            if grouped_specs:
                metrics.update(_grouped_metrics_multihost(
                    grouped_specs,
                    np.asarray(scores),
                    np.asarray(ds.batch.labels) if ds is not None else np.zeros(0),
                    {
                        t: np.asarray(v)
                        for t, v in (ds.batch.id_tags if ds is not None else {}).items()
                    },
                ))
            logger.info(f"scoring evaluation (global): {metrics}")

    with timed(logger, "write scores"):
        if ds is not None:
            write_scoring_results(
                os.path.join(output_dir, "scores", f"part-{part_index:05d}.avro"),
                np.asarray(scores),
                uids=ds.uids,
                labels=ds.labels,
            )
        if metrics is not None:
            from photon_ml_tpu.parallel.multihost import is_output_process

            if is_output_process():
                with open(os.path.join(output_dir, "metrics.json"), "w") as f:
                    json.dump(metrics, f, indent=2)
    if multihost:
        from photon_ml_tpu.parallel.multihost import sync_processes

        sync_processes("score-outputs-written")
    return scores, metrics


def _global_metrics_multihost(
    specs: list[str], scores: np.ndarray, labels: np.ndarray, weights: np.ndarray
) -> dict:
    """Global metrics over every host's rows: allgather (score, label,
    weight) padded to the max per-host row count with weight-0 rows, which
    every evaluator treats as absent. Identical on all processes."""
    from jax.experimental import multihost_utils as mhu

    from photon_ml_tpu.evaluation import evaluate_all

    counts = mhu.process_allgather(np.asarray([len(scores)], np.int64))
    max_n = int(np.max(counts))

    def pad(a):
        out = np.zeros(max_n, np.float64)
        out[: len(a)] = np.asarray(a, np.float64)
        return out

    s, y, w = mhu.process_allgather(
        (pad(scores), pad(labels), pad(weights))
    )
    results = evaluate_all(specs, s.ravel(), y.ravel(), w.ravel())
    return dict(results.metrics)


def _grouped_metrics_multihost(
    specs: list[str],
    scores: np.ndarray,
    labels: np.ndarray,
    id_tag_values: dict[str, np.ndarray],
) -> dict:
    """Grouped (Multi*) metrics over multihost-scored rows: one
    owner-routing exchange per id tag (each row's (score, label, entity
    id) travels to the entity's owner — global dense ids from the
    training-saved entity map, unseen-entity sentinel -1 rows dropped),
    per-group partials from COMPLETE groups, one (sum, count) allreduce
    per spec. No host ever gathers a global score column (the same
    owner-side recipe as the streamed trainer's validation —
    ``evaluation.host_sharded``). Collective: every process calls with the
    same specs in the same order; a host with no input rows participates
    with empty arrays."""
    import jax

    from photon_ml_tpu.evaluation import make_evaluator
    from photon_ml_tpu.evaluation.evaluators import (
        grouped_auc_parts,
        grouped_precision_at_k_parts,
    )
    from photon_ml_tpu.parallel.multihost import (
        allreduce_sum_host,
        exchange_rows,
    )

    P_ = max(jax.process_count(), 1)
    routed: dict[str, tuple] = {}
    out: dict[str, float] = {}
    for spec in specs:
        ev = make_evaluator(spec)
        tag = ev.group_by
        if tag not in routed:
            gids = np.asarray(
                id_tag_values.get(tag, np.zeros(0, np.int64)), np.int64
            )
            keep = np.flatnonzero(gids >= 0)
            recv = exchange_rows(
                {
                    "gid": gids[keep],
                    "score": np.asarray(scores, np.float32)[keep],
                    "label": np.asarray(labels, np.float32)[keep],
                },
                (gids[keep] % P_).astype(np.int64),
            )
            routed[tag] = (recv["score"], recv["label"], recv["gid"])
        s_o, y_o, g_o = routed[tag]
        if ev.k is not None:
            part = grouped_precision_at_k_parts(s_o, y_o, g_o, ev.k)
        else:
            part = grouped_auc_parts(s_o, y_o, g_o)
        tot = allreduce_sum_host(np.asarray(part, np.float64))
        out[spec] = float(tot[0] / tot[1]) if tot[1] > 0 else float("nan")
    return out


def _random_effects(game_dir: str) -> dict:
    """cid → random_effect_type from the model's metadata (pre-load peek)."""
    with open(os.path.join(game_dir, "metadata.json")) as f:
        meta = json.load(f)
    return {
        cid: info["random_effect_type"]
        for cid, info in meta["coordinates"].items()
        if info["type"] == "random"
    }


def main(argv: list[str] | None = None) -> None:
    configure_compile_cache()
    p = argparse.ArgumentParser(description="GAME scoring driver")
    p.add_argument("--model-dir", required=True)
    p.add_argument("--data", required=True, nargs="+")
    p.add_argument("--output-dir", required=True)
    p.add_argument("--evaluators", nargs="*", default=None)
    p.add_argument(
        "--config", default=None, help="training config JSON (for feature shards)"
    )
    p.add_argument(
        "--profile-dir", default=None,
        help="capture a jax.profiler device trace of the scoring pass",
    )
    p.add_argument(
        "--telemetry-dir", default=None,
        help="write the run's telemetry JSONL into this directory; "
             "render/diff with `photon-ml-tpu report`",
    )
    p.add_argument(
        "--multihost", action="store_true",
        help="join the jax.distributed runtime; each host scores its slice "
             "of the input part files and writes its own output partition "
             "(run the SAME command on every host)",
    )
    args = p.parse_args(argv)
    if args.multihost:
        from photon_ml_tpu.parallel.multihost import initialize_multihost

        initialize_multihost()
    shards = None
    if args.config:
        shards = dict(load_training_config(args.config).feature_shards)
    from photon_ml_tpu import obs

    obs.configure(args.telemetry_dir)
    try:
        run(
            args.model_dir,
            args.data,
            args.output_dir,
            evaluators=args.evaluators,
            feature_shards=shards,
            profile_dir=args.profile_dir,
            multihost=args.multihost,
        )
    finally:
        obs.shutdown()


if __name__ == "__main__":
    main()
