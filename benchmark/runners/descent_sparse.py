"""Traffic kind ``descent_sparse``: GAME/GLMix coordinate descent from the
zero model over one resident data set whose random effects may be SPARSE.

As ``runners/descent.py`` (same unit, same fence, same counters and facts
under the same names, so its per-layer readers work here unchanged): one
unit is one ``CoordinateDescent.run`` of the traffic file's
``outer_iterations`` over its ``sequence`` (the configuration's update
sequence when the file names none), no checkpoint directory, fenced by
bringing every trained coordinate's coefficients to the host, counted as
``outer_iterations`` of work. A random effect whose spec says ``"kind":
"sparse"`` gets a ``SparseFeatures`` shard (``benchmark/datagen_sparse_re``);
the program trains each of its entities in the subspace of the columns the
entity's rows touch.

Refused up front: a program without ``game/projector.sparse_index_map``
(a commit before per-entity subspaces for sparse shards). Such a program
solves every lane at the shard's full width: at 16,384 columns one L-BFGS
lane is 1.4 MB and 17,312 users need 25 GB, which no chip holds, and it
would find that out only after minutes of host gathering and compilation.

The configuration file gives the sizes, so the tests run this tiny on the
CPU backend by handing in a small configuration.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

from benchmark import datagen_sparse_re, work_sparse_re
from benchmark.reference import glmix as reference_glmix
from benchmark.reference import glmix_sparse as reference_sparse
from benchmark.reference import newton as reference_newton
from benchmark.runners import descent as dense_runner

FIXED_SHARD = dense_runner.FIXED_SHARD
_optimization = dense_runner._optimization


def setup(cell) -> SimpleNamespace:
    import jax

    from photon_ml_tpu.game import (
        CoordinateDescent,
        DenseFeatures,
        FixedEffectCoordinate,
        RandomEffectCoordinate,
        SparseFeatures,
        bucket_entities,
        group_by_entity,
        make_game_batch,
        projector,
    )
    from photon_ml_tpu.types import TaskType

    if not hasattr(projector, "sparse_index_map"):
        raise RuntimeError(
            "this program has no per-entity index map for a sparse random "
            "effect (game/projector.sparse_index_map): it would solve every "
            "entity at the shard's full width, which no chip holds at this "
            "configuration's 16,384 columns (25 GB of L-BFGS lanes)"
        )
    if len(cell.devices) != 1:
        raise ValueError("the resident descent is a one-chip path")
    cfg = cell.config
    n = int(cfg["rows"])
    effects = cfg["random_effects"]
    d_fixed = int(cfg["fixed"]["width"])
    task = TaskType(cfg["task"])
    st = SimpleNamespace(cfg=cfg, cell=cell, facts={}, last=None, n=n)

    y, Xf, shards, ids = datagen_sparse_re.glmix_sparse_rows(
        cell.seed, n, d_fixed, effects, int(cfg["data_seed"])
    )
    shard_of = {tag: f"per_{tag}" for tag in effects}
    st.sparse = {t for t, s in effects.items() if s.get("kind") == "sparse"}
    features = {FIXED_SHARD: DenseFeatures(X=Xf)}
    for tag in effects:
        if tag in st.sparse:
            features[shard_of[tag]] = SparseFeatures(
                indices=shards[tag][0], values=shards[tag][1],
                num_features=int(effects[tag]["width"]),
            )
        else:
            features[shard_of[tag]] = DenseFeatures(X=shards[tag])
    batch = make_game_batch(y, features, id_tags=ids)
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

    # what one objective pass of each entity of a sparse effect has to do
    # (rows, nonzeros and support are the data's, counted here, not read
    # from the program): for the roofline share of the subspace passes
    st.pass_work = {}
    for tag in st.sparse:
        shard = batch.features[shard_of[tag]]
        idx, val = np.asarray(shard.indices), np.asarray(shard.values)
        e = int(effects[tag]["entities"])
        st.pass_work[f"per_{tag}"] = work_sparse_re.entity_passes(
            rows=np.bincount(ids[tag], minlength=e),
            nonzeros=np.bincount(
                ids[tag], weights=(val != 0).sum(axis=1), minlength=e
            ),
            support=reference_sparse.support_sizes(idx, val, ids[tag], e),
        )
    return st


def unit(st):
    with st.cell.annotate("descent.run"):
        res = st.descent.run(st.sequence, st.iterations)
    with st.cell.annotate("fence"):
        coefs = {
            cid: np.asarray(res.model[cid].coefficient_means)
            for cid in st.sequence
        }
    # Only what the accounting and the check read is kept: the harness and
    # ``st.last`` hold a unit's result through the next unit, and the model
    # and the last trackers hold the (entities, d) matrix on the device,
    # 1.1 GB the next unit's program has no room for.
    kept = SimpleNamespace(
        training_scores=res.training_scores,
        fixed_passes=float(sum(
            float(t.objective_passes) for t in res.trackers.get("fixed", ())
        )),
        # per bucket of a random effect's last visit: entity ids (host),
        # iterations and ConvergenceReason per lane (device, a few KB)
        lanes={
            cid: [(ent, it, why) for ent, _, it, why in res.trackers[cid][-1].diag_refs]
            for cid in st.sequence if cid != "fixed"
        },
    )
    return kept, coefs


def account(st, out) -> dict:
    import jax

    res, coefs = out
    st.last = out
    counted = {"work": float(st.iterations)}
    finite = all(bool(np.all(np.isfinite(c))) for c in coefs.values())
    counted["failed"] = 0.0 if finite else 1.0
    if "fixed" in st.sequence:
        counted["optim.objective_passes"] = res.fixed_passes
    executed = useful = flops = bytes_ = 0.0
    for cid, lanes in res.lanes.items():
        # every lane of a bucket runs until its slowest lane stops (a
        # sparse effect's classes stop a chunk of lanes at a time, so for
        # them this counts too many executed and the share reads low)
        for ent, it_lane, _ in lanes:
            it = np.asarray(jax.device_get(it_lane), np.int64)
            if it.size:
                executed += float(it.size * it.max())
                useful += float(it.sum())
            if cid in st.pass_work:
                # a coordinate's earlier trackers are released by the
                # descent, so every visit of the unit is counted at the
                # LAST visit's iterations: warm, it is the one with fewer
                f, b = st.pass_work[cid]
                flops += st.iterations * float(it @ f[ent])
                bytes_ += st.iterations * float(it @ b[ent])
    if executed:
        counted["re_solve.executed_entity_iterations"] = executed
        counted["re_solve.useful_entity_iterations"] = useful
    if bytes_:
        counted["sparse_re.useful_pass_flops"] = flops
        counted["sparse_re.useful_pass_bytes"] = bytes_
    return counted


def facts(st) -> dict:
    """Set-up facts; the program's own prepare-time counters of the sparse
    subspaces (``re_subspace.*``) are read when asked, since the program
    stages its buckets at the first visit, which is the warm-up's."""
    from photon_ml_tpu.obs.metrics import REGISTRY

    out = dict(st.facts)
    snap = REGISTRY.snapshot("re_subspace.")
    for name, c in snap["counters"].items():
        out[name] = float(c["value"])
    for name, t in snap["timers"].items():
        out[name + ".seconds"] = float(t["seconds"])
    return out


def shape(st) -> dict:
    return {"rows": st.n, "devices": 1}


def _part(st, cid, coefs):
    """The reference's score of one coordinate on the given coefficients."""
    batch = st.batch
    if cid == "fixed":
        return reference_glmix.score((batch.features[FIXED_SHARD].X, coefs), [])
    tag = st.coord_tag[cid]
    shard = batch.features[st.shard_of[tag]]
    if tag in st.sparse:
        import jax.numpy as jnp

        return jnp.asarray(reference_sparse.sparse_score(
            shard.indices, shard.values, st.ids[tag], coefs
        ))
    return reference_glmix.score(
        None, [(shard.X, batch.id_tags[tag], coefs)]
    )


def check(st) -> dict:
    """What the timed path produced, at the timed sizes, against the plain
    reference: the training scores on the returned coefficients; the
    log-loss against the null model's; the last coordinate held to its own
    optimum as ``runners/descent.py`` does; and every sparse random effect
    held to ITS optimum on seeded entities: coefficients exactly 0 outside
    the entity's support, the reference gradient at the returned
    coefficients against that at 0, and, for small supports, the
    coefficients against the reference Newton solve. A coordinate that is
    not the last was solved against the NEXT coordinates' scores of the
    outer iteration before; those are those of a run one iteration
    shorter, made here (the descent is deterministic)."""
    import jax.numpy as jnp

    res, coefs = st.last
    g = st.cfg["guarantees"]
    batch = st.batch
    parts = {cid: _part(st, cid, coefs[cid]) for cid in st.sequence}
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
    y = np.asarray(batch.labels)
    rng = np.random.default_rng(st.cell.seed)

    # the last coordinate's last visit was solved against these
    last = st.sequence[-1]
    if last != "fixed" and st.coord_tag[last] not in st.sparse:
        tag = st.coord_tag[last]
        ids = st.ids[tag]
        l2 = float(st.cfg["random_effects"][tag]["l2"])
        present = np.flatnonzero(np.bincount(ids) > 0)
        sample = rng.choice(
            present, size=min(int(g["entities_checked"]), len(present)),
            replace=False,
        )
        # to the host once: indexing a device array by a new row set
        # compiles a gather for every entity
        X = np.asarray(batch.features[st.shard_of[tag]].X)
        off = np.asarray(ref_scores - parts[last])
        rows_of = reference_sparse.EntityRows(ids)
        worst = 0.0
        for e in sample:
            rows = rows_of.of(e)
            w_ref = reference_newton.entity_newton(X[rows], y[rows], off[rows], l2)
            worst = max(worst, float(np.max(np.abs(coefs[last][e] - w_ref))))
        notes["entities_checked"] = int(len(sample))
        notes["entity_max_abs_diff"] = worst
        ok = ok and worst <= float(g["entity_abs_tol"])

    shorter = None
    for pos, cid in enumerate(st.sequence):
        if cid == "fixed" or st.coord_tag[cid] not in st.sparse:
            continue
        # the offsets of this coordinate's last visit: the coordinates
        # before it as returned, those after it as one iteration earlier
        seen = sum(parts[c] for c in st.sequence[:pos])
        after = st.sequence[pos + 1:]
        if after and st.iterations > 1:
            if shorter is None:
                shorter = st.descent.run(st.sequence, st.iterations - 1)
            seen = seen + sum(
                _part(st, c, np.asarray(shorter.model[c].coefficient_means))
                for c in after
            )
        verdict = _check_sparse_effect(
            st, cid, coefs[cid], np.asarray(seen), y, rng, g
        )
        notes[cid] = verdict
        ok = ok and verdict.pop("ok")
    return {"correct": bool(ok), "notes": notes}


# optim/common.ConvergenceReason, by what ended an entity's last solve
_STOPPED_BY = ("cap", "tolerance", "objective", "float32_resolution")


def _check_sparse_effect(st, cid, W, seen, y, rng, g) -> dict:
    import jax

    tag = st.coord_tag[cid]
    ids = st.ids[tag]
    shard = st.batch.features[st.shard_of[tag]]
    idx, val = np.asarray(shard.indices), np.asarray(shard.values)
    l2 = float(st.cfg["random_effects"][tag]["l2"])
    present = np.flatnonzero(np.bincount(ids) > 0)
    sample = rng.choice(
        present, size=min(int(g["sparse_entities_checked"]), len(present)),
        replace=False,
    )
    rows_of = reference_sparse.EntityRows(ids)
    outside = 0.0
    ratios, diffs, detail = [], [], []
    for e in sample:
        rows = rows_of.of(e)
        support = reference_sparse.entity_support(idx[rows], val[rows])
        w_e = W[e]
        out = np.ones(w_e.shape[0], bool)
        out[support] = False
        outside = max(outside, float(np.max(np.abs(w_e[out]), initial=0.0)))
        X = reference_sparse.entity_dense(idx[rows], val[rows], support)
        _, g_w = reference_sparse.entity_value_grad(
            X, y[rows], seen[rows], w_e[support], l2
        )
        _, g_0 = reference_sparse.entity_value_grad(
            X, y[rows], seen[rows], np.zeros(len(support)), l2
        )
        ratios.append(float(np.linalg.norm(g_w) / np.linalg.norm(g_0)))
        detail.append((ratios[-1], int(e), len(rows), len(support),
                       float(np.linalg.norm(g_0))))
        if (len(support) <= int(g["sparse_solved_support_max"])
                and len(diffs) < int(g["sparse_entities_solved"])):
            w_ref = reference_sparse.entity_solve(X, y[rows], seen[rows], l2)
            diffs.append(float(np.max(np.abs(w_e[support] - w_ref))))
    why = np.concatenate([
        np.asarray(r)
        for r in jax.device_get([w for _, _, w in st.last[0].lanes[cid]])
    ])
    shares = np.bincount(why, minlength=len(_STOPPED_BY)) / max(len(why), 1)
    verdict = {
        "entities_checked": int(len(sample)),
        "outside_support_max_abs": outside,
        "grad_ratio_median": float(np.median(ratios)),
        "grad_ratio_max": float(np.max(ratios)),
        "entities_solved": len(diffs),
        "entity_median_abs_diff": float(np.median(diffs)) if diffs else 0.0,
        "entity_max_abs_diff": float(np.max(diffs)) if diffs else 0.0,
        "stopped_by": {k: float(v) for k, v in zip(_STOPPED_BY, shares)},
        # ratio, entity, rows, support, |g(0)| of the three worst
        "worst": sorted(detail, reverse=True)[:3],
    }
    verdict["ok"] = bool(
        outside == 0.0
        and verdict["grad_ratio_median"] <= float(g["sparse_grad_ratio_median_max"])
        and verdict["grad_ratio_max"] <= float(g["sparse_grad_ratio_max"])
        and verdict["entity_median_abs_diff"]
        <= float(g["sparse_entity_median_abs_tol"])
        and verdict["entity_max_abs_diff"] <= float(g["sparse_entity_abs_tol"])
    )
    return verdict
