"""Fused one-pass Pallas kernels vs the XLA objective path.

The kernels (``ops/fused.py``) run here in interpreter mode on the CPU
backend — the identical program the TPU executes compiled — and must
reproduce the XLA objective's value/gradient/Hv numerics exactly (f32)
or to bf16-accumulation tolerance (bf16 storage)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.config import OptimizerConfig
from photon_ml_tpu.normalization import NormalizationType, build_normalization
from photon_ml_tpu.ops.batch import DenseBatch
from photon_ml_tpu.ops.fused import fused_hvp, fused_value_grad, supports_fused
from photon_ml_tpu.ops.glm import make_objective
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.optim import lbfgs_minimize, owlqn_minimize
from photon_ml_tpu.types import TaskType

TASKS = list(TaskType)


def _problem(rng, n, d, task, dtype=jnp.float32, zero_weights=True):
    X = rng.normal(size=(n, d)).astype(np.float32)
    w_true = (rng.normal(size=d) * 0.4).astype(np.float32)
    margin = X @ w_true
    if task in (TaskType.LOGISTIC_REGRESSION, TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM):
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float32)
    elif task is TaskType.POISSON_REGRESSION:
        y = rng.poisson(np.exp(np.clip(margin, -8, 3))).astype(np.float32)
    else:
        y = (margin + 0.1 * rng.normal(size=n)).astype(np.float32)
    offsets = (0.1 * rng.normal(size=n)).astype(np.float32)
    weights = rng.uniform(0.5, 1.5, size=n).astype(np.float32)
    if zero_weights:
        weights[:: max(n // 7, 1)] = 0.0  # padding rows
    return DenseBatch(
        X=jnp.asarray(X, dtype),
        labels=jnp.asarray(y),
        offsets=jnp.asarray(offsets),
        weights=jnp.asarray(weights),
    )


def _pair(batch, task, norm=None):
    loss = loss_for_task(task)
    kw = dict(l2_weight=0.7, norm=norm, intercept_index=None)
    return (
        make_objective(batch, loss, fused=False, **kw),
        make_objective(batch, loss, fused=True, **kw),
    )


@pytest.mark.parametrize("task", TASKS)
@pytest.mark.parametrize("n", [37, 512])
def test_fused_value_grad_matches_xla(rng, task, n):
    d = 128
    batch = _problem(rng, n, d, task)
    ref, fused = _pair(batch, task)
    w = jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.3)
    f0, g0 = ref.value_and_grad(w)
    f1, g1 = fused.value_and_grad(w)
    np.testing.assert_allclose(float(f1), float(f0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("task", [TaskType.LOGISTIC_REGRESSION, TaskType.LINEAR_REGRESSION])
def test_fused_hvp_matches_xla(rng, task):
    n, d = 300, 128  # 300 % 256 != 0: exercises the masked tail tile
    batch = _problem(rng, n, d, task)
    ref, fused = _pair(batch, task)
    w = jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.normal(size=d).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(fused.hvp(w, v)), np.asarray(ref.hvp(w, v)),
        rtol=1e-4, atol=1e-4,
    )


def test_fused_with_normalization(rng):
    n, d = 200, 128
    batch = _problem(rng, n, d, TaskType.LOGISTIC_REGRESSION)
    X = np.asarray(batch.X).copy()
    X[:, d - 1] = 1.0  # intercept column absorbs the standardization shift
    batch = DenseBatch(
        X=jnp.asarray(X), labels=batch.labels,
        offsets=batch.offsets, weights=batch.weights,
    )
    norm = build_normalization(
        NormalizationType.STANDARDIZATION,
        means=X.mean(axis=0),
        variances=X.var(axis=0),
        max_magnitudes=np.abs(X).max(axis=0),
        intercept_index=d - 1,
    )
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
    kw = dict(l2_weight=0.7, norm=norm, intercept_index=d - 1)
    ref = make_objective(batch, loss, fused=False, **kw)
    fused = make_objective(batch, loss, fused=True, **kw)
    w = jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.2)
    f0, g0 = ref.value_and_grad(w)
    f1, g1 = fused.value_and_grad(w)
    np.testing.assert_allclose(float(f1), float(f0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), rtol=1e-4, atol=1e-4)
    v = jnp.asarray(rng.normal(size=d).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(fused.hvp(w, v)), np.asarray(ref.hvp(w, v)),
        rtol=1e-4, atol=1e-4,
    )


def test_fused_bf16_matches_xla_bf16(rng):
    n, d = 512, 128
    batch = _problem(rng, n, d, TaskType.LOGISTIC_REGRESSION, dtype=jnp.bfloat16)
    ref, fused = _pair(batch, TaskType.LOGISTIC_REGRESSION)
    w = jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.3)
    f0, g0 = ref.value_and_grad(w)
    f1, g1 = fused.value_and_grad(w)
    # both paths feed bf16 MXU operands with f32 accumulation; only the
    # accumulation order differs
    np.testing.assert_allclose(float(f1), float(f0), rtol=2e-3)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), rtol=2e-2, atol=2e-2)


def test_lbfgs_fused_converges_to_same_optimum(rng):
    n, d = 400, 128
    batch = _problem(rng, n, d, TaskType.LOGISTIC_REGRESSION)
    ref, fused = _pair(batch, TaskType.LOGISTIC_REGRESSION)
    cfg = OptimizerConfig(max_iterations=60, tolerance=1e-9)
    w0 = jnp.zeros((d,), jnp.float32)
    r0 = lbfgs_minimize(ref, w0, cfg)
    r1 = lbfgs_minimize(fused, w0, cfg)
    np.testing.assert_allclose(float(r1.value), float(r0.value), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(r1.w), np.asarray(r0.w), rtol=1e-2, atol=1e-3)


def test_owlqn_fused_converges_to_same_optimum(rng):
    n, d = 300, 128
    batch = _problem(rng, n, d, TaskType.LOGISTIC_REGRESSION)
    ref, fused = _pair(batch, TaskType.LOGISTIC_REGRESSION)
    cfg = OptimizerConfig(max_iterations=80, tolerance=1e-9)
    w0 = jnp.zeros((d,), jnp.float32)
    r0 = owlqn_minimize(ref, w0, cfg, l1_weight=0.5)
    r1 = owlqn_minimize(fused, w0, cfg, l1_weight=0.5)
    np.testing.assert_allclose(float(r1.value), float(r0.value), rtol=1e-4)
    # same sparsity pattern (the OWL-QN contract)
    np.testing.assert_array_equal(
        np.asarray(r1.w) == 0.0, np.asarray(r0.w) == 0.0
    )


@pytest.mark.parametrize("n", [37, 512])
def test_fused_constant_aux_hints(rng, n):
    """Zero offsets + unit weights are detected statically and the kernels
    drop those aux streams; numerics must be unchanged."""
    d = 128
    task = TaskType.LOGISTIC_REGRESSION
    batch = _problem(rng, n, d, task, zero_weights=False)
    # host numpy offsets/weights: the free auto-detection path
    batch = DenseBatch(
        X=batch.X, labels=batch.labels,
        offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32),
    )
    loss = loss_for_task(task)
    ref = make_objective(batch, loss, l2_weight=0.7, fused=False)
    fused = make_objective(batch, loss, l2_weight=0.7, fused=True)
    assert fused.offsets_zero and fused.weights_one
    w = jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.3)
    f0, g0 = ref.value_and_grad(w)
    f1, g1 = fused.value_and_grad(w)
    np.testing.assert_allclose(float(f1), float(f0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0), rtol=1e-4, atol=1e-4)
    v = jnp.asarray(rng.normal(size=d).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(fused.hvp(w, v)), np.asarray(ref.hvp(w, v)),
        rtol=1e-4, atol=1e-4,
    )


def test_fused_inside_shard_map_matches_unsharded(rng):
    """The multichip path: fused kernels run per-device inside shard_map
    (decided outside on the concrete global batch), partial sums psum'd."""
    from photon_ml_tpu.parallel import data_mesh
    from photon_ml_tpu.parallel.distributed import sharded_minimize

    n, d = 8 * 50 + 3, 128  # forces zero-weight row padding across 8 devices
    task = TaskType.LOGISTIC_REGRESSION
    batch = _problem(rng, n, d, task)
    loss = loss_for_task(task)
    cfg = OptimizerConfig(max_iterations=40, tolerance=1e-9)
    w0 = jnp.zeros((d,), jnp.float32)
    mesh = data_mesh(8)
    r_ref = sharded_minimize(
        lbfgs_minimize, batch, w0, cfg, mesh, loss, l2_weight=0.7, fused=False
    )
    r_fused = sharded_minimize(
        lbfgs_minimize, batch, w0, cfg, mesh, loss, l2_weight=0.7, fused=True
    )
    np.testing.assert_allclose(float(r_fused.value), float(r_ref.value), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(r_fused.w), np.asarray(r_ref.w), rtol=1e-2, atol=1e-3
    )


def _narrow(shape) -> bool:
    """More than 8 rows of fewer than 128 elements: what a TPU stores at
    one 128-lane line (512 B in f32) a row, in HBM and in VMEM."""
    shape = [1 if s is None or not isinstance(s, int) else s for s in shape]
    return bool(shape) and math.prod(shape[:-1]) > 8 and shape[-1] < 128


@pytest.mark.parametrize("n", [37, 512, 8192 + 300])
@pytest.mark.parametrize("streams", [0, 1, 2])
@pytest.mark.parametrize("kernel", ["value_grad", "hvp"])
def test_fused_operands_are_lane_dense(kernel, streams, n):
    """Labels, offsets and weights reach the kernel lane-dense: no operand,
    block or output of the ``pallas_call`` is a narrow column, and no
    per-row vector is reshaped to (n, 1) on the way (PERF.md §6, PR 26)."""
    d = 128
    row = jax.ShapeDtypeStruct((n,), jnp.float32)
    vec = jax.ShapeDtypeStruct((d,), jnp.float32)
    X = jax.ShapeDtypeStruct((n, d), jnp.float32)
    loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)

    def evaluate(X, y, u, *aux):
        off, wt = (*aux, None, None)[:2]
        if kernel == "value_grad":
            return fused_value_grad(X, y, off, wt, u, 0.1, loss=loss)
        return fused_hvp(X, y, off, wt, u, u, 0.1, 0.1, loss=loss)

    jaxpr = jax.make_jaxpr(evaluate)(X, row, vec, *[row] * streams)
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1  # one custom call an evaluation
    (call,) = calls
    assert len(call.invars) == 2 + streams + 2  # X, labels, streams, vector(s), shift(s)
    shapes = [v.aval.shape for v in (*call.invars, *call.outvars)]
    shapes += [bm.block_shape for bm in call.params["grid_mapping"].block_mappings]
    assert [s for s in shapes if _narrow(tuple(s))] == []
    for eqn in jaxpr.eqns:  # the wrapper around the call, too
        for v in eqn.outvars:
            assert not _narrow(v.aval.shape), (eqn.primitive.name, v.aval.shape)


def test_supports_fused_gates():
    assert supports_fused(1024, 512, jnp.float32)
    assert supports_fused(1024, 512, jnp.bfloat16)
    # float32 takes any width of at least one sublane group (the
    # feature-major kernels, as the chip stores such a matrix); the MXU's
    # bfloat16 dots contract whole lane tiles
    assert supports_fused(1024, 500, jnp.float32)
    assert supports_fused(400_000, 2000, jnp.float32)
    assert not supports_fused(1024, 500, jnp.bfloat16)
    # narrower than one lane tile: float32 down to one sublane group (the
    # descent cells' 65-column fixed effect), bfloat16 never
    assert supports_fused(5_000_066, 65, jnp.float32)
    assert supports_fused(1024, 127, jnp.float32)
    assert supports_fused(1024, 8, jnp.float32)
    assert not supports_fused(1024, 7, jnp.float32)
    assert not supports_fused(5_000_066, 65, jnp.bfloat16)
    assert supports_fused(1024, 128, jnp.float32)
    assert not supports_fused(1024, 512, jnp.int8)
    assert not supports_fused(1024, 1 << 17, jnp.float32)  # tile over budget
    # the double-buffered shortest tile bounds the width: 128 rows of a
    # row-major float32 matrix (the subspace lanes' 8,192 columns fit), 256
    # of a feature-major or a bfloat16 one
    assert supports_fused(1024, 7168, jnp.float32)
    assert supports_fused(1024, 8192, jnp.float32)
    assert supports_fused(1024, 14336, jnp.float32)
    assert not supports_fused(1024, 14464, jnp.float32)
    assert supports_fused(1024, 7160, jnp.float32)  # feature-major: 7,160 rows
    assert not supports_fused(1024, 7170, jnp.float32)
    assert supports_fused(1024, 14336, jnp.bfloat16)
    assert not supports_fused(1024, 14464, jnp.bfloat16)


@pytest.mark.parametrize("n, d, dtype, tile, grid, masked", [
    # dense_dp4_fit's shard a chip; epsilon_tron_fit; the fixed effect of
    # ml20m_descent / ml20m_fixed_only and of sparse_re_descent
    (1 << 22, 512, jnp.bfloat16, 4096, 1024, False),
    (400_000, 2000, jnp.float32, 512, 782, True),
    (5_000_066, 65, jnp.float32, 8192, 611, True),
    (2_500_033, 65, jnp.float32, 8192, 306, True),
])
def test_the_other_cells_tiles_are_where_they_were(n, d, dtype, tile, grid, masked):
    """The float32 row-major tile came down to 128 rows for the subspace
    lanes (PR 37); the shapes the other cells run are bfloat16 or
    feature-major and keep the tiles they had (``_block_rows`` at PR 36)."""
    from photon_ml_tpu.ops import fused as F

    assert F.tile_rows(n, d, dtype) == tile
    got_grid, statics, *_, scratch, _ = _prep_of(n, d, dtype)
    assert (got_grid, statics["masked"]) == (grid, masked)
    assert statics["fm"] == (dtype == jnp.float32) and scratch == []


def _prep_of(n, d, dtype):
    """``_prep`` on shapes alone (nothing of that size is built)."""
    from photon_ml_tpu.ops import fused as F

    out = {}

    def run(X, y, vecs):
        out["prep"] = F._prep(X, y, None, None, vecs)
        return 0

    jax.eval_shape(
        run, jax.ShapeDtypeStruct((n, d), dtype),
        jax.ShapeDtypeStruct((n,), jnp.float32),
        jax.ShapeDtypeStruct((1, d), jnp.float32),
    )
    return out["prep"]


@pytest.mark.parametrize("rows, width, tile", [
    (64, 128, 128), (128, 128, 128), (256, 128, 256), (2048, 1024, 1024),
    (128, 4096, 128), (256, 4096, 256), (1024, 4096, 256), (128, 8192, 128),
    (256, 8192, 128), (8192, 8192, 128), (128, 14336, 128),
])
def test_a_float32_row_major_tile_comes_down_to_128_rows(rows, width, tile):
    from photon_ml_tpu.ops import fused as F

    assert F.tile_rows(rows, width, jnp.float32) == tile


def _lanes(rng, lanes, rows, width, nnz=4):
    """``lanes`` subspace lanes as ``prepare_buckets`` stages them: flat
    local indices with a REPEATED column in every row (its slots add),
    offsets, and trailing padding rows of weight 0 whose labels, offsets and
    nonzeros are garbage."""
    from photon_ml_tpu.ops.batch import LocalSparseBatch

    idx = rng.integers(0, width, (lanes, rows, nnz)).astype(np.int32)
    idx[..., 1] = idx[..., 0]
    val = rng.uniform(0.2, 1.0, (lanes, rows, nnz)).astype(np.float32)
    y = (rng.uniform(size=(lanes, rows)) < 0.5).astype(np.float32)
    off = (0.3 * rng.normal(size=(lanes, rows))).astype(np.float32)
    wt = rng.uniform(0.5, 1.5, (lanes, rows)).astype(np.float32)
    pad = max(rows // 5, 3)
    wt[:, -pad:] = 0.0
    y[:, -pad:] = 1e30
    off[:, -pad:] = -1e30
    val[:, -pad:] = 1e30
    return LocalSparseBatch(
        indices=jnp.asarray(idx.reshape(lanes, -1)),
        values=jnp.asarray(val.reshape(lanes, -1)), labels=jnp.asarray(y),
        offsets=jnp.asarray(off), weights=jnp.asarray(wt), num_features=width,
    )


@pytest.mark.parametrize("rows, width", [
    (64, 128), (128, 128), (256, 128), (128, 4096), (256, 4096), (128, 8192),
    (256, 8192), (64, 1024), (512, 256),
])
def test_vmapped_kernel_matches_the_subspace_multiply_reduces(rng, rows, width):
    """``ops/fused``'s row-major float32 value-and-gradient kernel batched
    over lanes (a ``vmap``: the lane axis is its outer grid axis) against
    ``SubspaceDenseBatch``'s two multiply-reduce sweeps, to float32
    rounding: on both sides of every edge of ``game/random_effect.
    subspace_one_read`` (64 rows, where the lane is shorter than its
    128-row tile and the tile is ragged; 128; 256; one lane block of
    columns; 8,192 columns, where the budget brings the tile to 128 rows
    and a 256-row lane takes two tiles; 512 rows in one tile of four row
    blocks, a loop)."""
    from photon_ml_tpu.ops import fused as F

    task = TaskType.LOGISTIC_REGRESSION
    loss = loss_for_task(task)
    lanes = _lanes(rng, 3, rows, width)
    U = jnp.asarray(0.2 * rng.normal(size=(3, width)).astype(np.float32))
    c = jnp.float32(0.25)

    def sweeps(lane, u):
        b = lane.densified()
        m = b.matvec(u) + b.offsets - c
        r = jnp.where(b.weights != 0, b.weights * loss.d1(m, b.labels), 0.0)
        lv = jnp.where(b.weights != 0, b.weights * loss.value(m, b.labels), 0.0)
        return jnp.sum(lv), b.rmatvec(r), jnp.sum(r)

    def kernel(lane, u):
        b = lane.densified()
        return b.value_grad_pass(
            u, c, loss, offsets=b.offsets, weights=b.weights, interpret=True
        )

    want = jax.vmap(sweeps)(lanes, U)
    got = jax.vmap(kernel)(lanes, U)
    assert F.tile_rows(rows, width, jnp.float32) <= max(rows, 128)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == jnp.float32
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=0, atol=2e-6 * scale
        )


def _reference_passes(batch, w, v, l2):
    """The benchmark's plain reference on the batch's real columns, with the
    batch's weights all one and offsets zero."""
    from benchmark.reference import tron as reference

    X, y = batch.X, batch.labels
    f, g = reference.value_grad(X, y, w, l2, block_rows=256)
    return f, g, reference.hvp(X, y, w, v, l2, block_rows=256)


@pytest.mark.parametrize("kernel", ["value_grad", "hvp"])
@pytest.mark.parametrize("d", [130, 200, 256, 2000])
def test_fused_takes_any_float32_width_of_a_lane_tile_or_more(rng, d, kernel):
    """A float32 matrix whose width is no multiple of 128 goes through the
    (feature-major) kernels: against the XLA path and the benchmark's
    reference, outputs of the real width, nothing of the rows the block
    overruns in any of them; 256 takes the row-major kernels as before."""
    from photon_ml_tpu.ops import fused as F

    n = 300 if d < 2000 else 700  # a ragged last tile either way
    task = TaskType.LOGISTIC_REGRESSION
    batch = _problem(rng, n, d, task, zero_weights=False)
    batch = DenseBatch(batch.X, batch.labels, jnp.zeros((n,), jnp.float32),
                       jnp.ones((n,), jnp.float32))
    assert supports_fused(n, d, jnp.float32)
    loss = loss_for_task(task)
    ref, fused = (
        make_objective(batch, loss, l2_weight=0.7, fused=f) for f in (False, True)
    )
    w = jnp.asarray(rng.normal(size=d).astype(np.float32) * (2.0 / d**0.5))
    v = jnp.asarray(rng.normal(size=d).astype(np.float32))
    f_ref, g_ref, hv_ref = _reference_passes(batch, w, v, 0.7)

    def close(a, b):  # in the 2-norm: float32 sums in another order
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.linalg.norm(a - b) <= 1e-5 * np.linalg.norm(b)

    if kernel == "value_grad":
        (f0, g0), (f1, g1) = ref.value_and_grad(w), fused.value_and_grad(w)
        assert g1.shape == (d,) and g1.dtype == jnp.float32
        np.testing.assert_allclose(f1, f0, rtol=1e-5)
        np.testing.assert_allclose(f1, f_ref, rtol=1e-5)
        close(g1, g0)
        close(g1, g_ref)
        raw = F.fused_value_grad(batch.X, batch.labels, None, None, w, 0.0,
                                 loss=loss, interpret=True)[1]
    else:
        hv0, hv1 = ref.hvp(w, v), fused.hvp(w, v)
        assert hv1.shape == (d,) and hv1.dtype == jnp.float32
        close(hv1, hv0)
        close(hv1, hv_ref)
        raw = F.fused_hvp(batch.X, batch.labels, None, None, w, v, 0.0, 0.0,
                          loss=loss, interpret=True)[0]
    assert raw.shape == (d,) and bool(jnp.all(jnp.isfinite(raw)))
    if d % 128:  # the feature-major kernels: blocks of whole sublane groups
        assert F.reads_feature_major(d, jnp.float32)
        d8 = F.sublane_width(d)
        *_, ins, in_specs, g_spec, g_shape, _, _ = F._prep(
            batch.X, batch.labels, None, None, w.reshape(1, d)
        )
        assert ins[0].shape == (d, n) and in_specs[0].block_shape[0] == d8
        # the block overruns the array past d: the coefficients there are 0
        assert ins[-1].shape == (1, d8, 128)
        assert bool(jnp.all(ins[-1][0, d:] == 0.0))
        assert g_shape.shape[1:] == (d8, 128)
    else:
        assert not F.reads_feature_major(d, jnp.float32)


@pytest.mark.parametrize("kernel", ["value_grad", "hvp"])
@pytest.mark.parametrize("n", [300, 8192 + 300])
def test_fused_takes_the_descents_65_columns_with_offsets_and_weights(rng, n, kernel):
    """The GLMix descent's fixed effect (64 features and an intercept) with
    residual offsets and weights present, a ragged last tile of one tile and
    of two: the feature-major kernels against the XLA objective and against
    a plain float64 reference of the weighted logistic objective."""
    d, l2 = 65, 0.7
    task = TaskType.LOGISTIC_REGRESSION
    batch = _problem(rng, n, d, task)
    assert supports_fused(n, d, jnp.float32)
    loss = loss_for_task(task)
    ref, fused = (
        make_objective(batch, loss, l2_weight=l2, fused=f) for f in (False, True)
    )
    w = jnp.asarray(rng.normal(size=d).astype(np.float32) * 0.25)
    v = jnp.asarray(rng.normal(size=d).astype(np.float32))

    X, y, off, wt = (np.asarray(a, np.float64) for a in
                     (batch.X, batch.labels, batch.offsets, batch.weights))
    w64, v64 = np.asarray(w, np.float64), np.asarray(v, np.float64)
    m = X @ w64 + off
    p = 0.5 * (1.0 + np.tanh(0.5 * m))
    f_ref = np.sum(wt * np.logaddexp(0.0, -(2.0 * y - 1.0) * m)) + 0.5 * l2 * w64 @ w64
    g_ref = X.T @ (wt * (p - y)) + l2 * w64
    hv_ref = X.T @ (wt * p * (1.0 - p) * (X @ v64)) + l2 * v64

    def close(a, b):  # in the 2-norm: float32 sums in another order
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert np.linalg.norm(a - b) <= 1e-5 * np.linalg.norm(b)

    if kernel == "value_grad":
        (f0, g0), (f1, g1) = ref.value_and_grad(w), fused.value_and_grad(w)
        assert g1.shape == (d,) and g1.dtype == jnp.float32
        np.testing.assert_allclose(f1, f0, rtol=1e-5)
        np.testing.assert_allclose(f1, f_ref, rtol=1e-5)
        close(g1, g0)
        close(g1, g_ref)
    else:
        hv0, hv1 = ref.hvp(w, v), fused.hvp(w, v)
        assert hv1.shape == (d,) and hv1.dtype == jnp.float32
        close(hv1, hv0)
        close(hv1, hv_ref)


def test_auto_fused_reads_the_width_and_how_the_array_is_stored(monkeypatch):
    """A float32 matrix of 2,000 columns, or of the descent cells' 65,
    takes the feature-major kernels where it is stored feature-major, as a
    TPU stores it (here the CPU's row-major array stands for one that is
    not: a narrow matrix of few rows); under one sublane group, and in
    bfloat16, ``auto_fused`` still says no; an aligned width is asked
    nothing new."""
    from photon_ml_tpu.ops import fused as F
    from photon_ml_tpu.ops import glm

    def batch(d, dtype=jnp.float32):
        return DenseBatch(jnp.zeros((512, d), dtype), jnp.zeros((512,)),
                          jnp.zeros((512,)), jnp.ones((512,)))

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wide = batch(2000)
    assert F.stored_feature_major(wide.X) is False
    assert F.stored_feature_major(np.zeros((4, 4))) is False
    assert glm.auto_fused(batch(65)) is False
    assert glm.auto_fused(wide) is False
    assert glm.auto_fused(batch(2000, jnp.bfloat16)) is False
    assert glm.auto_fused(batch(2048)) is True
    monkeypatch.setattr(F, "stored_feature_major", lambda X: True)
    assert glm.auto_fused(wide) is True
    assert glm.auto_fused(batch(65)) is True
    assert glm.auto_fused(batch(7)) is False
    assert glm.auto_fused(batch(65, jnp.bfloat16)) is False


def test_disable_fused_knob_strict_parse(monkeypatch):
    """Regression for the PHOTON_DISABLE_FUSED truthiness bug (found by
    the lint knob pass): '0' is a truthy string, so the old
    ``not os.environ.get(...)`` read made ``PHOTON_DISABLE_FUSED=0``
    DISABLE fusion. The knob now strict-parses like its siblings."""
    from photon_ml_tpu.ops.glm import fused_disabled

    monkeypatch.delenv("PHOTON_DISABLE_FUSED", raising=False)
    assert fused_disabled() is False
    monkeypatch.setenv("PHOTON_DISABLE_FUSED", "0")
    assert fused_disabled() is False  # the =0 case: fusion stays enabled
    monkeypatch.setenv("PHOTON_DISABLE_FUSED", "1")
    assert fused_disabled() is True
    monkeypatch.setenv("PHOTON_DISABLE_FUSED", "")
    assert fused_disabled() is False  # empty = unset, the knob convention
    monkeypatch.setenv("PHOTON_DISABLE_FUSED", "nope")
    with pytest.raises(ValueError):
        fused_disabled()  # a typo fails loudly, never silently un-fuses
