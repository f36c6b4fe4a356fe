"""Traffic kind ``descent``: GAME/GLMix coordinate descent from the zero
model over one resident data set.

One unit is one ``CoordinateDescent.run`` of the traffic file's
``outer_iterations`` over its ``sequence`` (the configuration's update
sequence when the file names none), with no checkpoint directory, so no run
resumes another; it is fenced by bringing every trained coordinate's
coefficients to the host, and counts ``outer_iterations`` of work. Data,
the host-side grouping and bucketing, the coordinates' staging and the
warm-up are set-up. The data set is the configuration's, whole, whatever
the sequence: a job that trains one coordinate of a GLMix data set still
holds the data set.

The configuration file gives the sizes, so the tests run this tiny on the
CPU backend by handing in a small configuration.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

from benchmark import datagen
from benchmark.reference import glm as reference_glm
from benchmark.reference import glmix as reference_glmix
from benchmark.reference import newton as reference_newton

FIXED_SHARD = "global"


def _optimization(spec):
    from photon_ml_tpu.config import (
        OptimizationConfig,
        OptimizerConfig,
        RegularizationContext,
    )
    from photon_ml_tpu.types import OptimizerType, RegularizationType

    o = spec["optimizer"]
    return OptimizationConfig(
        optimizer=OptimizerConfig(
            optimizer_type=OptimizerType(o["type"]),
            max_iterations=int(o["max_iterations"]),
            tolerance=float(o["tolerance"]),
        ),
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=float(spec["l2"]),
    )


def setup(cell) -> SimpleNamespace:
    import jax

    from photon_ml_tpu.game import (
        CoordinateDescent,
        DenseFeatures,
        FixedEffectCoordinate,
        RandomEffectCoordinate,
        bucket_entities,
        group_by_entity,
        make_game_batch,
    )
    from photon_ml_tpu.types import TaskType

    if len(cell.devices) != 1:
        raise ValueError("the resident descent is a one-chip path")
    cfg = cell.config
    n = int(cfg["rows"])
    effects = cfg["random_effects"]
    d_fixed = int(cfg["fixed"]["width"])
    task = TaskType(cfg["task"])
    st = SimpleNamespace(cfg=cfg, cell=cell, facts={}, last=None, n=n)

    y, Xf, Xe, ids = datagen.glmix_rows(
        cell.seed, n, d_fixed, effects, int(cfg["data_seed"])
    )
    shard_of = {tag: f"per_{tag}" for tag in effects}
    batch = make_game_batch(
        y,
        {FIXED_SHARD: DenseFeatures(X=Xf),
         **{shard_of[t]: DenseFeatures(X=Xe[t]) for t in effects}},
        id_tags=ids,
    )
    jax.block_until_ready(batch)
    st.batch, st.ids, st.shard_of = batch, ids, shard_of

    sequence = list(cell.traffic.get("sequence") or cfg["update_sequence"])
    st.sequence = sequence
    st.iterations = int(cell.traffic["outer_iterations"])
    coord_tag = {f"per_{t}": t for t in effects}
    coordinates = {}
    t0 = time.perf_counter()
    for cid in sequence:
        if cid == "fixed":
            coordinates[cid] = FixedEffectCoordinate(
                coordinate_id=cid, batch=batch, feature_shard_id=FIXED_SHARD,
                config=_optimization(cfg["fixed"]), task_type=task,
                intercept_index=d_fixed,
            )
            continue
        tag = coord_tag[cid]
        spec = effects[tag]
        grouping = group_by_entity(ids[tag], num_entities=int(spec["entities"]))
        coordinates[cid] = RandomEffectCoordinate(
            coordinate_id=cid, batch=batch, feature_shard_id=shard_of[tag],
            random_effect_type=tag, config=_optimization(spec),
            grouping=grouping, buckets=bucket_entities(grouping),
            task_type=task, num_entities=int(spec["entities"]),
        )
        buckets = coordinates[cid].buckets
        st.facts[f"buckets.{cid}.classes"] = float(len(buckets.capacities))
        st.facts[f"buckets.{cid}.slots"] = float(
            sum(r.size for r in buckets.row_indices)
        )
    st.coord_tag = coord_tag
    st.descent = CoordinateDescent(coordinates, batch, task)
    st.facts["descent.group_bucket_s"] = time.perf_counter() - t0
    return st


def unit(st):
    with st.cell.annotate("descent.run"):
        res = st.descent.run(st.sequence, st.iterations)
    with st.cell.annotate("fence"):
        coefs = {
            cid: np.asarray(res.model[cid].coefficient_means)
            for cid in st.sequence
        }
    return res, coefs


def account(st, out) -> dict:
    import jax

    res, coefs = out
    st.last = out
    counted = {"work": float(st.iterations)}
    finite = all(bool(np.all(np.isfinite(c))) for c in coefs.values())
    counted["failed"] = 0.0 if finite else 1.0
    executed = useful = 0.0
    for cid in st.sequence:
        last = res.trackers[cid][-1]
        if cid == "fixed":
            counted["optim.objective_passes"] = float(
                sum(float(t.objective_passes) for t in res.trackers[cid])
            )
            continue
        # every lane of a bucket runs until its slowest lane stops
        for _, _, it_lane, _ in last.diag_refs:
            it = np.asarray(jax.device_get(it_lane), np.int64)
            if it.size:
                executed += float(it.size * it.max())
                useful += float(it.sum())
    if executed:
        counted["re_solve.executed_entity_iterations"] = executed
        counted["re_solve.useful_entity_iterations"] = useful
    return counted


def facts(st) -> dict:
    return dict(st.facts)


def shape(st) -> dict:
    return {"rows": st.n, "devices": 1}


def check(st) -> dict:
    """The program's training scores equal the reference scorer's on the
    returned model, the reference log-loss beats the null model's by the
    configuration's ratio, and the last coordinate is held to its own
    optimum: a random effect by the reference Newton solve of seeded
    entities, the fixed effect by the reference gradient."""
    import jax.numpy as jnp

    res, coefs = st.last
    g = st.cfg["guarantees"]
    batch = st.batch
    parts = {}
    for cid in st.sequence:
        if cid == "fixed":
            parts[cid] = reference_glmix.score(
                (batch.features[FIXED_SHARD].X, coefs[cid]), []
            )
        else:
            tag = st.coord_tag[cid]
            parts[cid] = reference_glmix.score(
                None,
                [(batch.features[st.shard_of[tag]].X, batch.id_tags[tag],
                  coefs[cid])],
            )
    ref_scores = sum(parts.values())
    got = sum(res.training_scores[cid] for cid in st.sequence)
    score_diff = float(jnp.max(jnp.abs(got - ref_scores)))
    loss = reference_glmix.log_loss(ref_scores, batch.labels)
    null = reference_glmix.log_loss(jnp.zeros_like(ref_scores), batch.labels)
    notes = {
        "score_max_abs_diff": score_diff, "log_loss": loss,
        "null_log_loss": null,
    }
    ok = (
        score_diff <= float(g["score_abs_tol"])
        and loss <= float(g["log_loss_ratio_max"]) * null
    )
    # what the last coordinate's last visit was solved against
    last = st.sequence[-1]
    others = ref_scores - parts[last]
    if last == "fixed":
        X = batch.features[FIXED_SHARD].X
        l2 = float(st.cfg["fixed"]["l2"])
        d_fixed = int(st.cfg["fixed"]["width"])
        ref = lambda w: reference_glm.dense_value_grad(
            X, batch.labels, w, l2, d_fixed, offsets=others
        )
        _, g_w = ref(coefs["fixed"])
        _, g_0 = ref(np.zeros_like(coefs["fixed"]))
        ratio = float(np.linalg.norm(g_w) / np.linalg.norm(g_0))
        notes["grad_ratio"] = ratio
        ok = ok and ratio <= float(g["grad_ratio_max"])
    else:
        tag = st.coord_tag[last]
        ids = st.ids[tag]
        l2 = float(st.cfg["random_effects"][tag]["l2"])
        present = np.flatnonzero(np.bincount(ids) > 0)
        rng = np.random.default_rng(st.cell.seed)
        sample = rng.choice(
            present, size=min(int(g["entities_checked"]), len(present)),
            replace=False,
        )
        # to the host once: indexing a device array by a new row set
        # compiles a gather for every entity
        X = np.asarray(batch.features[st.shard_of[tag]].X)
        y, off = np.asarray(batch.labels), np.asarray(others)
        order = np.argsort(ids, kind="stable")
        starts = np.searchsorted(ids[order], np.arange(ids.max() + 2))
        worst = 0.0
        for e in sample:
            rows = order[starts[e]:starts[e + 1]]
            w_ref = reference_newton.entity_newton(X[rows], y[rows], off[rows], l2)
            worst = max(worst, float(np.max(np.abs(coefs[last][e] - w_ref))))
        notes["entities_checked"] = int(len(sample))
        notes["entity_max_abs_diff"] = worst
        ok = ok and worst <= float(g["entity_abs_tol"])
    return {"correct": bool(ok), "notes": notes}
