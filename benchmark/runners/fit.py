"""Traffic kind ``fit``: complete GLM fits of one resident data set.

One unit is one fit from ``w0 = 0`` to the configuration's stopping rule,
fenced by bringing the coefficients to the host. On one chip it goes
through ``supervised/training.train_glm`` over the layout that
``ops/batch.optimize_batch_layout`` chooses; on several chips through
``parallel/distributed.DistributedTrainer`` over ``data_mesh()``. Data,
layout and warm-up are set-up; the reference check runs after the window.

The configuration file gives the sizes, so the tests run this tiny on the
CPU backend by handing in a small configuration.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

from benchmark import datagen
from benchmark.reference import glm as reference


def setup(cell) -> SimpleNamespace:
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.types import OptimizerType, TaskType

    cfg = cell.config
    feats = cfg["features"]
    opt = cfg["optimizer"]
    st = SimpleNamespace(
        cfg=cfg, cell=cell, facts={}, last=None,
        l2=float(cfg["l2"]),
        task=TaskType(cfg["task"]),
        opt=OptimizerConfig(
            optimizer_type=OptimizerType(opt["type"]),
            max_iterations=int(opt["max_iterations"]),
            tolerance=float(opt["tolerance"]),
        ),
    )
    n = int(feats["rows"]) * int(feats.get("row_multiple", 1))
    d = int(feats["columns"])
    st.n, st.d = n, d
    if feats["kind"] == "sparse":
        _setup_sparse(st, feats, cell.seed)
    elif feats["kind"] == "dense":
        _setup_dense(st, feats, cell)
    else:
        raise ValueError(f"unknown feature kind {feats['kind']!r}")
    st.w0 = jnp.zeros((d,), jnp.float32)
    jax.block_until_ready(st.w0)
    st.fit = _fitter(st)
    return st


def _setup_sparse(st, feats, seed: int) -> None:
    """Padded-sparse rows on the device, then the program's own layout
    decision (tile-COO for a wide sparse matrix), timed apart: every sparse
    job pays that host build."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops.batch import SparseBatch, optimize_batch_layout
    from photon_ml_tpu.ops.streaming import device_hbm_budget_bytes

    if len(st.cell.devices) != 1:
        raise ValueError("the sparse fit is a one-chip path")
    k = int(feats["nonzeros_per_row"])
    idx, val, y = datagen.sparse_glm_rows(
        seed, st.n, st.d, k, float(feats["zipf_exponent"]),
        int(feats["data_seed"]),
    )
    batch = SparseBatch(
        indices=idx, values=val, labels=y,
        offsets=jnp.zeros((st.n,), jnp.float32),
        weights=jnp.ones((st.n,), jnp.float32), num_features=st.d,
    )
    jax.block_until_ready(batch)
    # the reference's own copy of the rows, on the host
    st.host_rows = (np.asarray(idx), np.asarray(val), np.asarray(y))
    t0 = time.perf_counter()
    st.batch = optimize_batch_layout(
        batch, hbm_budget_bytes=device_hbm_budget_bytes()
    )
    jax.block_until_ready(st.batch)
    st.facts["layout.build_s"] = time.perf_counter() - t0
    st.facts["layout.nonzeros"] = float(np.count_nonzero(st.host_rows[1]))
    chunks = getattr(st.batch, "chunks", None)
    if chunks is not None:
        # packed (groups, streams, 128) arrays, one a direction a chunk
        st.facts["layout.slots"] = float(sum(
            int(arrays[0].shape[0]) * int(arrays[0].shape[-1])
            for c in chunks for arrays in (c.m_arrays, c.g_arrays)
        ))
    st.mesh = None
    st.intercept = None


def _setup_dense(st, feats, cell) -> None:
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops.batch import DenseBatch
    from photon_ml_tpu.parallel import data_mesh

    mesh = data_mesh(devices=cell.devices)
    X, y = datagen.dense_glm_rows(
        st.n, st.d, jnp.dtype(feats["dtype"]), mesh,
        int(feats["generate_block_rows"]), int(feats["data_seed"]),
        float(feats["column_scale_spread"]),
    )
    sharding = y.sharding
    st.batch = DenseBatch(
        X=X, labels=y,
        offsets=jax.device_put(jnp.zeros((st.n,), jnp.float32), sharding),
        weights=jax.device_put(jnp.ones((st.n,), jnp.float32), sharding),
    )
    jax.block_until_ready(st.batch)
    st.mesh = mesh if len(cell.devices) > 1 else None
    st.intercept = st.d - 1
    st.host_rows = None


def _fitter(st):
    """The program's fit entry point for this many chips, as a callable that
    returns the ``OptimizationResult``."""
    if st.mesh is not None:
        from photon_ml_tpu.ops.losses import loss_for_task
        from photon_ml_tpu.parallel import DistributedTrainer

        trainer = DistributedTrainer(
            mesh=st.mesh, config=st.opt, loss=loss_for_task(st.task),
            l2_weight=st.l2, intercept_index=st.intercept,
        )
        return lambda: trainer.train(st.batch, st.w0)
    from photon_ml_tpu.supervised.training import train_glm

    return lambda: train_glm(
        st.batch, st.task, optimizer_config=st.opt,
        regularization_weights=[st.l2], intercept_index=st.intercept,
    ).trackers[st.l2]


def unit(st):
    with st.cell.annotate("fit"):
        res = st.fit()
    with st.cell.annotate("fence"):
        w = np.asarray(res.w)
    return res, w


def account(st, out) -> dict:
    res, w = out
    st.last = (res, w)
    return {
        "work": 1.0,
        "failed": 0.0 if np.all(np.isfinite(w)) else 1.0,
        "optim.objective_passes": float(res.objective_passes),
        "optim.iterations": float(res.iterations),
    }


def facts(st) -> dict:
    return dict(st.facts)


def shape(st) -> dict:
    """What the benchmark's bytes/FLOP functions need to know."""
    nnz = st.facts.get("layout.nonzeros")
    return {
        "rows": st.n, "columns": st.d, "nonzeros": nnz,
        "itemsize": None if st.host_rows is not None
        else int(st.batch.X.dtype.itemsize),
        "devices": len(st.cell.devices),
    }


def check(st) -> dict:
    """The reported loss equals the reference objective at the returned
    ``w``, and the reference gradient there is small against the gradient
    at zero: a fit that stopped early fails."""
    res, w = st.last
    g = st.cfg["guarantees"]
    if st.host_rows is not None:
        idx, val, y = st.host_rows
        ref = lambda v: reference.sparse_value_grad(
            idx, val, y, v, st.l2, st.intercept
        )
    else:
        ref = lambda v: reference.dense_value_grad(
            st.batch.X, st.batch.labels, v, st.l2, st.intercept,
            block_rows=int(st.cfg["features"]["generate_block_rows"]),
        )
    f_ref, g_ref = ref(w)
    _, g_zero = ref(np.zeros_like(w))
    loss_rel = abs(float(res.value) - f_ref) / abs(f_ref)
    grad_ratio = float(np.linalg.norm(g_ref) / np.linalg.norm(g_zero))
    ok = (
        loss_rel <= float(g["loss_rel_tol"])
        and grad_ratio <= float(g["grad_ratio_max"])
    )
    return {
        "correct": bool(ok),
        "notes": {
            "loss_reported": float(res.value), "loss_reference": f_ref,
            "loss_rel_diff": loss_rel, "grad_ratio": grad_ratio,
            "iterations": int(res.iterations), "reason": int(res.reason),
        },
    }
