"""Distributed-vs-single-node equivalence on the 8-device CPU mesh — the
TPU analog of the reference's local-mode Spark integration tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.config import OptimizerConfig
from photon_ml_tpu.ops.batch import dense_batch_from_numpy
from photon_ml_tpu.ops.glm import make_objective
from photon_ml_tpu.ops.losses import LOSSES
from photon_ml_tpu.optim import lbfgs_minimize, owlqn_minimize, tron_minimize
from photon_ml_tpu.parallel import DistributedTrainer, data_mesh, shard_batch
from photon_ml_tpu.types import OptimizerType


def _problem(rng, n=333, d=6):  # n deliberately not divisible by 8
    X = rng.normal(size=(n, d))
    X[:, -1] = 1.0
    w_true = rng.normal(size=d)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-X @ w_true))).astype(np.float64)
    wt = rng.uniform(0.5, 2.0, size=n)
    return X, y, wt


def test_mesh_has_8_devices():
    mesh = data_mesh()
    assert mesh.shape["data"] == 8


@pytest.mark.parametrize("opt", ["lbfgs", "tron", "owlqn"])
def test_sharded_equals_single_node(opt, rng):
    X, y, wt = _problem(rng)
    batch = dense_batch_from_numpy(X, y, weights=wt, dtype=jnp.float64)
    mesh = data_mesh()
    cfg = OptimizerConfig(
        optimizer_type=OptimizerType.TRON if opt == "tron" else OptimizerType.LBFGS,
        max_iterations=100,
        tolerance=1e-9,
    )
    l1 = 2.0 if opt == "owlqn" else 0.0
    trainer = DistributedTrainer(
        mesh=mesh, config=cfg, loss=LOSSES["logistic"], l2_weight=0.5,
        l1_weight=l1, intercept_index=5,
    )
    res_d = trainer.train(batch, jnp.zeros(6, jnp.float64))

    obj = make_objective(batch, LOSSES["logistic"], l2_weight=0.5, intercept_index=5)
    if opt == "owlqn":
        res_s = owlqn_minimize(obj, jnp.zeros(6, jnp.float64), cfg, l1)
    elif opt == "tron":
        res_s = tron_minimize(obj, jnp.zeros(6, jnp.float64), cfg)
    else:
        res_s = lbfgs_minimize(obj, jnp.zeros(6, jnp.float64), cfg)

    np.testing.assert_allclose(res_d.value, res_s.value, rtol=1e-8)
    np.testing.assert_allclose(res_d.w, res_s.w, rtol=1e-5, atol=1e-7)


def test_sharded_objective_value_grad_hvp_match(rng):
    X, y, wt = _problem(rng, n=100)
    batch = dense_batch_from_numpy(X, y, weights=wt, dtype=jnp.float64)
    mesh = data_mesh()
    sharded = shard_batch(batch, mesh)
    assert sharded.num_rows == 104  # padded to multiple of 8
    w = jnp.asarray(rng.normal(size=6))
    v = jnp.asarray(rng.normal(size=6))

    obj_local = make_objective(batch, LOSSES["poisson"], l2_weight=0.1)

    from jax.sharding import PartitionSpec as P

    def compute(b, w, v):
        obj = make_objective(b, LOSSES["poisson"], l2_weight=0.1, axis_name="data")
        f, g = obj.value_and_grad(w)
        return f, g, obj.hvp(w, v), obj.hessian_diag(w)

    f, g, hv, hd = jax.jit(
        jax.shard_map(
            compute, mesh=mesh, in_specs=(P("data"), P(), P()), out_specs=P(),
            check_vma=False,
        )
    )(sharded, w, v)
    f1, g1 = obj_local.value_and_grad(w)
    np.testing.assert_allclose(f, f1, rtol=1e-10)
    np.testing.assert_allclose(g, g1, rtol=1e-9)
    np.testing.assert_allclose(hv, obj_local.hvp(w, v), rtol=1e-9)
    np.testing.assert_allclose(hd, obj_local.hessian_diag(w), rtol=1e-9)


def test_sparse_mesh_densify_is_sharded(rng, monkeypatch):
    """A sparse batch whose dense form exceeds ONE chip's budget but fits
    the mesh total densifies PER-SHARD under shard_map — the full (n, d)
    matrix never materializes on a single device (budgeting the whole
    mesh's HBM for a one-device scatter was an OOM bug) — and the solve
    matches the single-node sparse objective."""
    import photon_ml_tpu.ops.streaming as st
    from photon_ml_tpu.ops.batch import DenseBatch, SparseBatch
    from photon_ml_tpu.parallel.distributed import _densify_sharded

    n, d, k = 160, 16, 3
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    batch = SparseBatch(
        indices=jnp.asarray(idx), values=jnp.asarray(val),
        labels=jnp.asarray(y),
        offsets=jnp.zeros(n, jnp.float32), weights=jnp.ones(n, jnp.float32),
        num_features=d,
    )
    # dense bytes = 160*16*4 = 10240: over one "chip" (4096), within 8 chips
    monkeypatch.setattr(
        st, "device_hbm_budget_bytes", lambda *a, **kw: 4096.0
    )
    mesh = data_mesh()
    dense = _densify_sharded(batch, mesh, "data")
    assert isinstance(dense, DenseBatch) and dense.X.shape == (n, d)
    # every X shard lives on its own device: 8 single-device shards
    assert len(dense.X.sharding.device_set) == 8

    cfg = OptimizerConfig(max_iterations=60, tolerance=1e-9)
    trainer = DistributedTrainer(
        mesh=mesh, config=cfg, loss=LOSSES["logistic"], l2_weight=0.5
    )
    res_d = trainer.train(batch, jnp.zeros(d, jnp.float32))
    obj = make_objective(batch, LOSSES["logistic"], l2_weight=0.5)
    res_s = lbfgs_minimize(obj, jnp.zeros(d, jnp.float32), cfg)
    np.testing.assert_allclose(res_d.value, res_s.value, rtol=1e-5)
    # two f32 solve paths (per-shard dense matmuls vs one sparse gather
    # objective) take different reduction orders — coefficient agreement
    # is convergence-level, not bitwise
    np.testing.assert_allclose(res_d.w, res_s.w, rtol=5e-3, atol=5e-4)


@pytest.mark.kernel
def test_sharded_tiled_solve_matches_the_untiled_single_device_solve(
    rng, monkeypatch
):
    """The per-shard MESH consumer: the 8-shard tiled solve
    (``_sharded_tiled_solve`` under ``shard_map``, one tile-COO layout a
    shard, the objective's psum over them) against the same L-BFGS on the
    untiled ``SparseBatch`` on one device (interpret mode, retuned-down
    constants)."""
    import photon_ml_tpu.ops.sparse_tiled as st_mod
    import photon_ml_tpu.ops.streaming as ost
    from photon_ml_tpu.ops.batch import SparseBatch
    from photon_ml_tpu.ops.losses import loss_for_task
    from photon_ml_tpu.parallel.distributed import sharded_minimize
    from photon_ml_tpu.types import TaskType

    monkeypatch.setattr(st_mod, "GROUPS_PER_STEP", 8)
    monkeypatch.setattr(st_mod, "SEGMENTS_PER_DMA", 2)
    # a tiny densify budget forces the sparse batch onto the tiled route
    monkeypatch.setattr(ost, "device_hbm_budget_bytes", lambda *a, **k: 1.0)
    calls = []
    apply = st_mod._tiled_apply
    monkeypatch.setattr(
        st_mod, "_tiled_apply", lambda *a, **k: calls.append(1) or apply(*a, **k)
    )

    n, d, k = 2048, 4096, 4
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    w_true = (rng.normal(size=d) * 0.3).astype(np.float32)
    m = (val * w_true[idx]).sum(axis=1)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-m))).astype(np.float32)
    batch = SparseBatch(
        indices=jnp.asarray(idx), values=jnp.asarray(val),
        labels=jnp.asarray(y),
        offsets=jnp.zeros(n, jnp.float32),
        weights=jnp.ones(n, jnp.float32),
        num_features=d,
    )
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    cfg = OptimizerConfig(max_iterations=6, tolerance=0.0)
    res = sharded_minimize(
        lbfgs_minimize, batch, jnp.zeros(d, jnp.float32), cfg,
        data_mesh(8), loss, l2_weight=1.0,
    )
    assert calls  # the kernels ran: the route was the tiled one
    ref = lbfgs_minimize(
        make_objective(batch, loss, l2_weight=1.0), jnp.zeros(d, jnp.float32), cfg
    )
    np.testing.assert_allclose(float(res.value), float(ref.value), rtol=1e-5)
    # per-shard kernels and one gather objective reduce in different orders
    np.testing.assert_allclose(
        np.asarray(res.w), np.asarray(ref.w), rtol=1e-3, atol=1e-4
    )