"""Traffic kind ``descent_mesh``: GAME/GLMix coordinate descent from the
zero model over one data set resident on the chips of a mesh.

The unit is ``descent``'s (``runners/descent.py``): one
``CoordinateDescent.run`` of the traffic file's ``outer_iterations`` over its
``sequence``, no checkpoint directory, fenced by bringing every trained
coordinate's coefficients to the host, counting ``outer_iterations`` of
work. What differs is where the data lie. A ``data_mesh`` over the cell's
chips is handed to ``make_game_batch``, to every coordinate and to
``CoordinateDescent``, exactly as ``GameEstimator`` hands one: that mesh is
the only thing that says "four chips". The data are drawn a chip's rows on
that chip (``datagen_glmix_mesh``), and the reference scores them in blocks
where they lie (``reference/glmix_blocks``).

The program must be able to run a descent fused under a mesh
(``game/data.place_game_batch`` came with that): one that cannot fails here
at once, before any data is made.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

from benchmark import datagen_glmix_mesh
from benchmark.reference import glmix_blocks as reference_glmix
from benchmark.reference import newton as reference_newton
from benchmark.runners import descent as one_chip

FIXED_SHARD = one_chip.FIXED_SHARD
account = one_chip.account


def setup(cell) -> SimpleNamespace:
    import jax

    # first: a program without the mesh path stops here (ImportError)
    from photon_ml_tpu.game import place_game_batch  # noqa: F401
    from photon_ml_tpu.game import (
        CoordinateDescent,
        DenseFeatures,
        FixedEffectCoordinate,
        RandomEffectCoordinate,
        bucket_entities,
        group_by_entity,
        make_game_batch,
    )
    from photon_ml_tpu.parallel.mesh import data_mesh
    from photon_ml_tpu.types import TaskType

    cfg = cell.config
    n = int(cfg["rows"])
    effects = cfg["random_effects"]
    d_fixed = int(cfg["fixed"]["width"])
    task = TaskType(cfg["task"])
    mesh = data_mesh(devices=cell.devices)
    st = SimpleNamespace(cfg=cfg, cell=cell, facts={}, last=None, n=n, mesh=mesh)

    y, weights, Xf, Xe, ids = datagen_glmix_mesh.glmix_mesh_rows(
        cell.seed, n, d_fixed, effects, int(cfg["data_seed"]), mesh
    )
    shard_of = {tag: f"per_{tag}" for tag in effects}
    batch = make_game_batch(
        y,
        {FIXED_SHARD: DenseFeatures(X=Xf),
         **{shard_of[t]: DenseFeatures(X=Xe[t]) for t in effects}},
        id_tags=ids, weights=weights, mesh=mesh,
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
                config=one_chip._optimization(cfg["fixed"]), task_type=task,
                intercept_index=d_fixed, mesh=mesh,
            )
            continue
        tag = coord_tag[cid]
        spec = effects[tag]
        # the real rows: a padded row belongs to no entity's bucket
        grouping = group_by_entity(ids[tag][:n], num_entities=int(spec["entities"]))
        coordinates[cid] = RandomEffectCoordinate(
            coordinate_id=cid, batch=batch, feature_shard_id=shard_of[tag],
            random_effect_type=tag, config=one_chip._optimization(spec),
            grouping=grouping, buckets=bucket_entities(grouping),
            task_type=task, num_entities=int(spec["entities"]), mesh=mesh,
        )
        buckets = coordinates[cid].buckets
        st.facts[f"buckets.{cid}.classes"] = float(len(buckets.capacities))
        st.facts[f"buckets.{cid}.slots"] = float(
            sum(r.size for r in buckets.row_indices)
        )
    st.coord_tag = coord_tag
    st.descent = CoordinateDescent(coordinates, batch, task, mesh=mesh)
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


def facts(st) -> dict:
    return dict(st.facts)


def shape(st) -> dict:
    return {"rows": st.n, "devices": len(st.cell.devices)}


def check(st, reference_operand=None) -> dict:
    """Of what the timed path returned, at the timed size, over the real
    rows: the program's training scores equal the reference scorer's on the
    returned coefficients (in row blocks, each chip's rows where they lie);
    the reference log-loss beats the null model's by the configuration's
    ratio; seeded entities of the last random effect sit at the reference
    Newton solve's optimum, the worst of them and the median one (the fixed
    effect, where the sequence ends in it, at the reference gradient's
    zero). ``notes`` also holds the readings one precision down, every
    operand of the reference rounded to bfloat16, which a limit must refuse:
    ``reference_operand=jnp.bfloat16`` puts that control through this very
    comparison (the harness never passes it; the tests do)."""
    import jax.numpy as jnp

    res, coefs = st.last
    g = st.cfg["guarantees"]
    batch, n = st.batch, st.n
    t0, seconds = time.perf_counter(), {}

    def lap(name):
        nonlocal t0
        seconds[name], t0 = time.perf_counter() - t0, time.perf_counter()

    def terms(operand=None):
        parts = {}
        for cid in st.sequence:
            if cid == "fixed":
                parts[cid] = reference_glmix.score(
                    (batch.features[FIXED_SHARD].X, coefs[cid]), [], operand=operand
                )[:n]
            else:
                tag = st.coord_tag[cid]
                parts[cid] = reference_glmix.score(
                    None,
                    [(batch.features[st.shard_of[tag]].X, st.ids[tag], coefs[cid])],
                    operand=operand,
                )[:n]
        return parts

    parts = terms(reference_operand)
    ref_scores = sum(parts.values())
    lap("reference_scores")
    got = sum(np.asarray(res.training_scores[cid])[:n] for cid in st.sequence)
    score_diff = float(np.max(np.abs(got - ref_scores)))
    labels = np.asarray(batch.labels)[:n]
    loss = reference_glmix.log_loss(ref_scores, labels)
    null = reference_glmix.log_loss(np.zeros_like(ref_scores), labels)
    lap("scores_and_loss")
    one_down = float(np.max(np.abs(
        sum(terms(jnp.bfloat16).values()) - ref_scores
    )))
    lap("bf16_operands")
    notes = {
        "score_max_abs_diff": score_diff, "log_loss": loss,
        "null_log_loss": null, "score_max_abs_diff_bf16_operands": one_down,
        "check_seconds": seconds,
    }
    ok = (
        score_diff <= float(g["score_abs_tol"])
        and loss <= float(g["log_loss_ratio_max"]) * null
    )
    # what the last coordinate's last visit was solved against
    last = st.sequence[-1]
    others = ref_scores - parts[last]
    if last == "fixed":
        from benchmark.reference import glm as reference_glm

        X = np.asarray(batch.features[FIXED_SHARD].X)[:n]
        l2 = float(st.cfg["fixed"]["l2"])
        d_fixed = int(st.cfg["fixed"]["width"])
        ref = lambda w: reference_glm.dense_value_grad(
            X, labels, w, l2, d_fixed, offsets=others
        )
        _, g_w = ref(coefs["fixed"])
        _, g_0 = ref(np.zeros_like(coefs["fixed"]))
        ratio = float(np.linalg.norm(g_w) / np.linalg.norm(g_0))
        notes["grad_ratio"] = ratio
        ok = ok and ratio <= float(g["grad_ratio_max"])
    else:
        tag = st.coord_tag[last]
        ids = st.ids[tag][:n]
        l2 = float(st.cfg["random_effects"][tag]["l2"])
        present = np.flatnonzero(np.bincount(ids) > 0)
        rng = np.random.default_rng(st.cell.seed)
        sample = rng.choice(
            present, size=min(int(g["entities_checked"]), len(present)),
            replace=False,
        )
        # to the host once (the program's staging left a host copy there):
        # indexing a sharded device array by a new row set gathers it whole
        X = np.asarray(batch.features[st.shard_of[tag]].X)
        # rounded on the host: a device round trip would compile a program
        # for every entity's row count
        down = lambda a: np.asarray(a).astype(jnp.bfloat16).astype(np.float32)
        got, one_down = [], []
        for e in sample:
            rows = np.flatnonzero(ids == e)
            w_ref = reference_newton.entity_newton(
                X[rows], labels[rows], others[rows], l2
            )
            # the same solve one precision down: features and offsets rounded
            w_down = reference_newton.entity_newton(
                down(X[rows]), labels[rows], down(others[rows]), l2
            )
            one_down.append(float(np.max(np.abs(w_down - w_ref))))
            # what the program is held to: the control where it stands in
            w_held = w_ref if reference_operand is None else w_down
            got.append(float(np.max(np.abs(coefs[last][e] - w_held))))
        # the worst entity sits at float32's floor (a summed loss that cannot
        # see a step), where one precision down sits too; the MEDIAN entity
        # converges far below it, and one precision down does not
        notes["entity_max_abs_diff_bf16_operands"] = max(one_down)
        notes["entity_median_abs_diff_bf16_operands"] = float(np.median(one_down))
        notes["entities_checked"] = int(len(sample))
        notes["entity_max_abs_diff"] = max(got)
        notes["entity_median_abs_diff"] = float(np.median(got))
        ok = (
            ok
            and max(got) <= float(g["entity_abs_tol"])
            and np.median(got) <= float(g["entity_median_abs_tol"])
        )
        lap("entities")
    return {"correct": bool(ok), "notes": notes}
