"""Tile-COO sparse kernels vs the XLA gather/scatter SparseBatch."""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

# The `kernel` marker (registered in pyproject.toml) tags the tests that
# trace Pallas kernels in interpret mode — the tier-1 runtime's biggest
# block — so the suite can split before the runtime budget forces cutting
# coverage; the full run stays the default. Pure host-side tests (layout
# builder invariants, cache bookkeeping) stay unmarked so `-m 'not
# kernel'` keeps that cheap coverage.

from photon_ml_tpu.ops.batch import SparseBatch, densify
from photon_ml_tpu.ops.sparse_tiled import (
    SLAB,
    TiledSparseBatch,
    supports_tiling,
    tile_sparse_batch,
)


def _sparse_problem(rng, n=1100, d=4608, k=5):
    # defaults retuned DOWN for the tier-1 budget (interpret-mode cost
    # scales with nnz = n*k): n must stay >= SLAB (1024) and d >= 4096
    # for supports_tiling; n > SLAB keeps the multi-row-slab path covered
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    # some explicit padding slots, like the ingest layer produces
    val[rng.uniform(size=(n, k)) < 0.1] = 0.0
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    batch = SparseBatch(
        indices=jnp.asarray(idx), values=jnp.asarray(val),
        labels=jnp.asarray(y),
        offsets=jnp.asarray(rng.normal(size=n).astype(np.float32) * 0.1),
        weights=jnp.ones((n,), jnp.float32),
        num_features=d,
    )
    return batch


@pytest.mark.kernel
class TestTiledSparse:
    def test_matvec_rmatvec_match_sparse_batch(self, rng):
        batch = _sparse_problem(rng)
        tiled = tile_sparse_batch(batch)
        w = jnp.asarray(rng.normal(size=batch.num_features).astype(np.float32))
        r = jnp.asarray(rng.normal(size=batch.num_rows).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(tiled.matvec(w)), np.asarray(batch.matvec(w)),
            rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(tiled.rmatvec(r)), np.asarray(batch.rmatvec(r)),
            rtol=1e-5, atol=1e-5,
        )
        # the Hessian diagonal squares the matrix's ENTRY: the build merges
        # a row's repeated draws of a column (SparseBatch squares each)
        np.testing.assert_allclose(
            np.asarray(tiled.rmatvec_sq(r)),
            np.asarray(densify(batch).rmatvec_sq(r)), rtol=1e-5, atol=1e-5,
        )

    def test_non_slab_aligned_shapes(self, rng):
        # n and d deliberately NOT multiples of the 1024 slab
        batch = _sparse_problem(rng, n=SLAB + 77, d=SLAB * 4 + 13, k=5)
        tiled = tile_sparse_batch(batch)
        w = jnp.asarray(rng.normal(size=batch.num_features).astype(np.float32))
        r = jnp.asarray(rng.normal(size=batch.num_rows).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(tiled.matvec(w)), np.asarray(batch.matvec(w)),
            rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(tiled.rmatvec(r)), np.asarray(batch.rmatvec(r)),
            rtol=1e-5, atol=1e-5,
        )

    def test_duplicate_indices_accumulate(self, rng):
        # duplicate (row, col) pairs must sum, exactly like SparseBatch
        n, d = 256, 4096
        idx = np.zeros((n, 4), np.int32)
        idx[:, 0] = 7
        idx[:, 1] = 7  # duplicate column in the same row
        idx[:, 2] = np.arange(n) % d
        idx[:, 3] = 2048
        val = rng.normal(size=(n, 4)).astype(np.float32)
        batch = SparseBatch(
            indices=jnp.asarray(idx), values=jnp.asarray(val),
            labels=jnp.zeros((n,), jnp.float32),
            offsets=jnp.zeros((n,), jnp.float32),
            weights=jnp.ones((n,), jnp.float32),
            num_features=d,
        )
        tiled = tile_sparse_batch(batch)
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(tiled.matvec(w)), np.asarray(batch.matvec(w)),
            rtol=1e-5, atol=1e-5,
        )
        r = jnp.asarray(rng.normal(size=n).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(tiled.rmatvec(r)), np.asarray(batch.rmatvec(r)),
            rtol=1e-5, atol=1e-5,
        )

    def test_objective_and_solve_match(self, rng):
        """End-to-end: the tiled batch drops into make_objective and the
        L-BFGS solve lands on the same optimum as the XLA sparse path."""
        from photon_ml_tpu.config import OptimizerConfig
        from photon_ml_tpu.ops.glm import make_objective
        from photon_ml_tpu.ops.losses import loss_for_task
        from photon_ml_tpu.optim import lbfgs_minimize
        from photon_ml_tpu.types import TaskType

        batch = _sparse_problem(rng, n=1100, d=4608, k=5)
        tiled = tile_sparse_batch(batch)
        loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)
        # both paths run the SAME iteration count, so the parity holds at
        # any bound — 8 keeps the interpret-mode solve inside the tier-1
        # budget (each extra iteration is two more interpreted kernel
        # sweeps through the line search)
        cfg = OptimizerConfig(max_iterations=8, tolerance=1e-8)
        w0 = jnp.zeros((batch.num_features,), jnp.float32)
        obj_a = make_objective(batch, loss, l2_weight=1.0)
        obj_b = make_objective(tiled, loss, l2_weight=1.0)
        va, ga = obj_a.value_and_grad(w0 + 0.01)
        vb, gb = obj_b.value_and_grad(w0 + 0.01)
        np.testing.assert_allclose(float(va), float(vb), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(ga), np.asarray(gb), rtol=1e-4, atol=1e-5
        )
        ra = lbfgs_minimize(obj_a, w0, cfg)
        rb = lbfgs_minimize(obj_b, w0, cfg)
        np.testing.assert_allclose(float(ra.value), float(rb.value), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(ra.w), np.asarray(rb.w), rtol=1e-2, atol=1e-3
        )

    def test_supports_tiling_gate(self, rng):
        big = _sparse_problem(rng, n=SLAB * 2, d=8192, k=4)
        assert supports_tiling(big)
        small = _sparse_problem(rng, n=200, d=512, k=4)
        assert not supports_tiling(small)
        assert not supports_tiling(densify(small))

    def test_supports_tiling_rejects_all_zero_values(self, rng):
        """All-padding batches tile to 0 groups (uncompilable kernel) —
        the gate must send them down the XLA path."""
        import dataclasses

        big = _sparse_problem(rng, n=SLAB * 2, d=8192, k=4)
        zeroed = dataclasses.replace(
            big, values=np.zeros_like(np.asarray(big.values))
        )
        assert not supports_tiling(zeroed)


@pytest.mark.kernel
def test_optimize_batch_layout_decision(rng):
    """Small-d sparse densifies; over-budget high-d sparse tiles; dense
    passes through."""
    from photon_ml_tpu.ops.batch import DenseBatch, optimize_batch_layout

    small = _sparse_problem(rng, n=300, d=600, k=4)
    out = optimize_batch_layout(small, hbm_budget_bytes=1e9)
    assert isinstance(out, DenseBatch)

    big = _sparse_problem(rng, n=SLAB + 5, d=8192, k=4)
    out = optimize_batch_layout(big, hbm_budget_bytes=1)  # force no densify
    assert isinstance(out, TiledSparseBatch)
    w = jnp.asarray(rng.normal(size=big.num_features).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(out.matvec(w)), np.asarray(big.matvec(w)),
        rtol=1e-5, atol=1e-5,
    )

    dense = optimize_batch_layout(small, hbm_budget_bytes=1e9)
    assert optimize_batch_layout(dense) is dense


@pytest.mark.kernel
def test_game_fixed_effect_rides_tiled_kernel(rng):
    """The ingest layout decision reaches the GAME fixed effect: a
    high-dimensional sparse fixed shard trains and scores through the
    cached tile-COO layout, matching the XLA path."""
    import photon_ml_tpu.ops.sparse_tiled as st
    from photon_ml_tpu.config import (
        FixedEffectCoordinateConfig,
        GameTrainingConfig,
        OptimizationConfig,
        OptimizerConfig,
        RegularizationContext,
    )
    from photon_ml_tpu.estimators import GameEstimator
    from photon_ml_tpu.game import make_game_batch
    from photon_ml_tpu.types import RegularizationType, TaskType

    from photon_ml_tpu.game.data import SparseFeatures

    n, d, k = 1100, 4096, 4
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    # (both fits run the same iteration count; 10 keeps two interpret-mode
    # estimator fits inside the tier-1 budget)
    batch = make_game_batch(
        y,
        {"s": SparseFeatures(
            indices=jnp.asarray(idx), values=jnp.asarray(val), num_features=d
        )},
        id_tags={},
    )
    cfg = GameTrainingConfig(
        task_type=TaskType.LOGISTIC_REGRESSION,
        coordinate_update_sequence=("fixed",),
        coordinate_descent_iterations=1,
        fixed_effect_coordinates={
            "fixed": FixedEffectCoordinateConfig(
                feature_shard_id="s",
                optimization=OptimizationConfig(
                    optimizer=OptimizerConfig(max_iterations=10),
                    regularization=RegularizationContext(RegularizationType.L2),
                    regularization_weight=1.0,
                ),
            )
        },
    )
    import photon_ml_tpu.ops.streaming as ops_streaming

    built = {"n": 0}
    orig = st.tile_sparse_batch

    def counting(b, **kw):
        built["n"] += 1
        return orig(b, **kw)

    # a tiny HBM budget forces the layout decision past densify into tiling
    orig_budget = ops_streaming.device_hbm_budget_bytes
    ops_streaming.device_hbm_budget_bytes = lambda *a, **k: 1.0
    st.tile_sparse_batch = counting
    try:
        model_t = GameEstimator(cfg).fit(batch)[0].model
    finally:
        st.tile_sparse_batch = orig
        ops_streaming.device_hbm_budget_bytes = orig_budget
    assert built["n"] == 1, "fixed coordinate should tile exactly once"

    orig_gate = st.supports_tiling
    ops_streaming.device_hbm_budget_bytes = lambda *a, **k: 1.0
    st.supports_tiling = lambda b, resident=False: False
    try:
        model_x = GameEstimator(cfg).fit(batch)[0].model
    finally:
        st.supports_tiling = orig_gate
        ops_streaming.device_hbm_budget_bytes = orig_budget
    np.testing.assert_allclose(
        np.asarray(model_t.models["fixed"].model.coefficients.means),
        np.asarray(model_x.models["fixed"].model.coefficients.means),
        rtol=1e-4, atol=1e-5,
    )


@pytest.mark.kernel
class TestTiledMesh:
    def test_sharded_minimize_routes_tiled_and_matches_single_device(
        self, rng, monkeypatch
    ):
        """sharded_minimize on a high-dim SparseBatch must take the
        per-shard tile-COO route (not the XLA gather/scatter fallback) and
        reach the single-device tiled optimum (VERDICT r4 missing #4 /
        next-2b: the file's own multi-device recipe, implemented). Small
        segment constants: this gates the MESH plumbing (stacked 4-array
        layouts, shard padding, psum), not the default-constant kernel —
        both sides of the comparison retune together."""
        import jax.numpy as jnp

        import photon_ml_tpu.ops.sparse_tiled as st_mod

        monkeypatch.setattr(st_mod, "GROUPS_PER_STEP", 8)
        monkeypatch.setattr(st_mod, "SEGMENTS_PER_DMA", 2)

        from photon_ml_tpu.config import OptimizerConfig
        from photon_ml_tpu.ops.batch import SparseBatch
        from photon_ml_tpu.ops.glm import make_objective
        from photon_ml_tpu.ops.losses import loss_for_task
        from photon_ml_tpu.optim import lbfgs_minimize
        from photon_ml_tpu.parallel import data_mesh
        from photon_ml_tpu.parallel.distributed import sharded_minimize
        from photon_ml_tpu.types import TaskType

        n, d, k = 2048, 4096, 4  # d >= 4096 satisfies supports_tiling;
        # dense = 128 MB > the CPU fallback budget? force the sparse route
        # by monkeypatching the budget below instead of relying on it
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
        # ref and mesh solves run the same bound, so the agreement check
        # compares the same trajectory point — 6 keeps two interpreted
        # solves inside the tier-1 budget
        cfg = OptimizerConfig(max_iterations=6, tolerance=1e-8)

        # single-device tiled reference
        from photon_ml_tpu.ops.sparse_tiled import tile_sparse_batch

        tb = tile_sparse_batch(batch)
        obj = make_objective(tb, loss, l2_weight=1.0)
        ref = lbfgs_minimize(obj, jnp.zeros(d, jnp.float32), cfg)

        # mesh route: shrink the densify budget so the sparse batch stays
        # sparse and must take the tiled route
        import photon_ml_tpu.parallel.distributed as dist

        calls = {"tiled": 0}
        orig = dist._sharded_tiled_solve

        def spy(*a, **kw):
            calls["tiled"] += 1
            return orig(*a, **kw)

        dist._sharded_tiled_solve = spy
        try:
            import photon_ml_tpu.ops.streaming as ost

            orig_budget = ost.device_hbm_budget_bytes
            ost.device_hbm_budget_bytes = lambda *a, **kw: 1.0
            try:
                res = sharded_minimize(
                    lbfgs_minimize, batch, jnp.zeros(d, jnp.float32), cfg,
                    data_mesh(8), loss, l2_weight=1.0,
                )
            finally:
                ost.device_hbm_budget_bytes = orig_budget
        finally:
            dist._sharded_tiled_solve = orig
        assert calls["tiled"] == 1, "mesh solve did not take the tiled route"
        # convergence-level agreement: the mesh (8-shard psum) and
        # single-device solves take different f32 reduction orders — and
        # the kernel's segment width sets the per-write-slab accumulation
        # order too — so coefficients agree to optimizer tolerance, while
        # the objective VALUE at the optimum stays tight
        np.testing.assert_allclose(
            np.asarray(res.w), np.asarray(ref.w), rtol=5e-3, atol=2.5e-3
        )
        np.testing.assert_allclose(
            float(res.value), float(ref.value), rtol=1e-5
        )


@pytest.mark.kernel
class TestSlabRunBatching:
    """Run-length edge conditions for the slab-run-batched phase 1: parity
    vs the XLA SparseBatch across run shapes (single-group runs, a run
    crossing the DMA-step boundary, an all-one-slab stream) and under
    retuned constants — same discipline as the segment-constant
    regression test below. The edge tests retune GROUPS_PER_STEP/
    SEGMENTS_PER_DMA down (8/2, the existing regression test's values) so
    each parity check traces a small kernel — default-constant parity is
    already covered by every pre-existing test in this file, which now
    runs the run-batched kernel too."""

    def _small_constants(self, monkeypatch):
        import photon_ml_tpu.ops.sparse_tiled as st

        monkeypatch.setattr(st, "GROUPS_PER_STEP", 8)
        monkeypatch.setattr(st, "SEGMENTS_PER_DMA", 2)

    def _make(self, rng, n, d, idx, val):
        return SparseBatch(
            indices=jnp.asarray(idx), values=jnp.asarray(val),
            labels=jnp.zeros(n, jnp.float32),
            offsets=jnp.zeros(n, jnp.float32),
            weights=jnp.ones(n, jnp.float32), num_features=d,
        )

    def _assert_parity(self, batch, rng, rtol=2e-3, atol=2e-3,
                       squared=False):
        tb = tile_sparse_batch(batch)
        w = jnp.asarray(rng.normal(size=batch.num_features).astype(np.float32))
        r = jnp.asarray(rng.normal(size=batch.num_rows).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(tb.matvec(w)), np.asarray(batch.matvec(w)),
            rtol=rtol, atol=atol,
        )
        np.testing.assert_allclose(
            np.asarray(tb.rmatvec(r)), np.asarray(batch.rmatvec(r)),
            rtol=rtol, atol=atol,
        )
        if squared:  # of the matrix's entry: repeated draws are merged
            np.testing.assert_allclose(
                np.asarray(tb.rmatvec_sq(r)),
                np.asarray(densify(batch).rmatvec_sq(r)), rtol=rtol, atol=atol,
            )
        return tb

    def test_single_group_runs(self, rng, monkeypatch):
        # k=1 over many column slabs: almost every cell holds ONE group,
        # so runs are minimal and every cell pads up to a whole run
        self._small_constants(monkeypatch)
        n, d = 2048, 8192
        idx = rng.integers(0, d, size=(n, 1)).astype(np.int32)
        val = rng.normal(size=(n, 1)).astype(np.float32)
        self._assert_parity(self._make(rng, n, d, idx, val), rng)

    def test_run_crossing_dma_step_boundary(self, rng, monkeypatch):
        # one hot column slab: a single (write-slab, read-slab) cell holds
        # more groups than a DMA step — its run crosses segment boundaries
        # AND the step boundary
        import photon_ml_tpu.ops.sparse_tiled as st

        self._small_constants(monkeypatch)
        n, d, k = 1024, 2048, 8
        idx = rng.integers(0, SLAB, size=(n, k)).astype(np.int32)  # col slab 0
        val = rng.normal(size=(n, k)).astype(np.float32)
        batch = self._make(rng, n, d, idx, val)
        self._assert_parity(batch, rng, squared=True)
        # the margins layout really does contain a run longer than one DMA
        # step (the condition under test, not an accident of the shapes)
        lay = st.build_write_major_layout(
            np.repeat(np.arange(n, dtype=np.int64), k),
            idx.reshape(-1).astype(np.int64), val.reshape(-1),
            SLAB, d,
        )
        runs = st.detect_slab_runs(lay.rslab)
        step_groups = st.GROUPS_PER_STEP * st.SEGMENTS_PER_DMA
        assert int(runs[:, 1].max()) > step_groups

    def test_all_one_slab_stream(self, rng, monkeypatch):
        # d and n both one slab: every group of BOTH directions reads
        # slab 0 — the whole stream is a single maximal run
        import photon_ml_tpu.ops.sparse_tiled as st

        self._small_constants(monkeypatch)
        n, d, k = SLAB, SLAB, 6
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        batch = self._make(rng, n, d, idx, val)
        self._assert_parity(batch, rng)
        lay = st.build_write_major_layout(
            np.repeat(np.arange(n, dtype=np.int64), k),
            idx.reshape(-1).astype(np.int64), val.reshape(-1),
            SLAB, SLAB,
        )
        assert (lay.rslab == 0).all() and (lay.rrun == 0).all()

    def test_retuned_run_constant(self, rng, monkeypatch):
        # the full retune surface at once, incl. the new runs-per-call
        # knob — layouts and kernel must agree at CALL-time values
        import photon_ml_tpu.ops.sparse_tiled as st

        self._small_constants(monkeypatch)
        monkeypatch.setattr(st, "GROUPS_PER_RUN", 4)
        n, d, k = 2048, 4096, 4
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        batch = self._make(rng, n, d, idx, val)
        tb = self._assert_parity(batch, rng, squared=True)
        for c in tb.chunks:
            for arrays in (c.m_arrays, c.g_arrays):
                n_groups = arrays[0].shape[0]
                assert n_groups % (8 * 2) == 0  # whole DMA steps
                assert arrays[3].shape[0] == n_groups // 4  # rrun stream

    def test_run_must_divide_segment(self, rng, monkeypatch):
        import photon_ml_tpu.ops.sparse_tiled as st

        monkeypatch.setattr(st, "GROUPS_PER_RUN", 3)  # does not divide 32
        with pytest.raises(ValueError, match="divide"):
            st.build_write_major_layout(
                np.zeros(4, np.int64), np.zeros(4, np.int64),
                np.ones(4, np.float32), SLAB, SLAB,
            )

    def test_run_metadata_invariants(self, rng):
        """The builder's run invariant, stated directly: every aligned
        GROUPS_PER_RUN block is single-slab, ``rrun`` is its slab stream,
        and maximal runs (detect_slab_runs) start and end on run-block
        boundaries — cells pad to whole runs, so no run straddles one."""
        import photon_ml_tpu.ops.sparse_tiled as st

        n, d, k = 3072, 6144, 5
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        R = st.GROUPS_PER_RUN
        for write_pad, read_pad, w_idx, r_idx in (
            (-(-n // SLAB) * SLAB, -(-d // SLAB) * SLAB,
             np.repeat(np.arange(n, dtype=np.int64), k),
             idx.reshape(-1).astype(np.int64)),
            (-(-d // SLAB) * SLAB, -(-n // SLAB) * SLAB,
             idx.reshape(-1).astype(np.int64),
             np.repeat(np.arange(n, dtype=np.int64), k)),
        ):
            lay = st.build_write_major_layout(
                w_idx, r_idx, val.reshape(-1), write_pad, read_pad
            )
            blocks = lay.rslab.reshape(-1, R)
            assert (blocks == blocks[:, :1]).all()
            np.testing.assert_array_equal(lay.rrun, blocks[:, 0])
            runs = st.detect_slab_runs(lay.rslab)
            assert int(runs[:, 1].sum()) == len(lay.rslab)
            assert (runs[:, 0] % R == 0).all()
            assert (runs[:, 1] % R == 0).all()


def _walk_layout(arrays, storage):
    """A built layout read the plain way, with no kernel: every slot of every
    group as ``(write index, read index, value)``, float64 values. A group's
    write slab is its segment's ``wslab``, its read slab its own ``rslab``
    (and its run's ``rrun``: they must agree), its within-slab offsets and
    value come from ``packed`` (int8: the value is q times the run's
    ``srun``). Reads the module's constants, as the kernel does."""
    import photon_ml_tpu.ops.sparse_tiled as st

    packed, wslab, rslab, rrun, srun = (np.asarray(a) for a in arrays)
    n_groups = packed.shape[0]
    # one slab id a group, spread over the group's 128 slots
    ws = np.repeat(wslab.astype(np.int64), st.GROUPS_PER_STEP)
    assert len(ws) == len(rslab) == n_groups
    ws = np.broadcast_to(ws[:, None], (n_groups, st.GROUP))
    if len(rrun) > n_groups:
        # the sparse-cell form: a read slab a GRANULE of a group's lanes
        # (``rslab`` keeps each group's first)
        sub = len(rrun) // n_groups
        assert storage == "f32" and sub in (st.SUB_SLABS, st.FINE_SLABS)
        np.testing.assert_array_equal(rrun[::sub], rslab)
        rs = np.repeat(
            rrun.astype(np.int64).reshape(n_groups, sub), st.GROUP // sub, axis=1
        )
    else:
        np.testing.assert_array_equal(np.repeat(rrun, st.GROUPS_PER_RUN), rslab)
        rs = np.broadcast_to(
            rslab.astype(np.int64)[:, None], (n_groups, st.GROUP)
        )
    if storage == "int8":
        pk = packed[:, 0, :].astype(np.int64)
        w_off, r_off = pk & 1023, (pk >> 10) & 1023
        q = (pk >> 20) & 255
        q = q - ((q & 128) << 1)
        scale = np.repeat(srun.astype(np.float64), st.GROUPS_PER_RUN)
        vals = q * scale[:, None]
    else:
        w_off = packed[:, 0, :].astype(np.int64) % SLAB
        r_off = packed[:, 1, :].astype(np.int64) % SLAB
        vals = np.ascontiguousarray(packed[:, 2, :]).view(np.float32).astype(
            np.float64
        )
        # the f32 rung stores whole indices: their slab is the stream's
        # (a filler's read index is 0 whatever slab its group reads)
        np.testing.assert_array_equal(packed[:, 0, :] // SLAB, ws)
        live = vals != 0
        np.testing.assert_array_equal((packed[:, 1, :] // SLAB)[live], rs[live])
    return (
        (ws * SLAB + w_off).reshape(-1),
        (rs * SLAB + r_off).reshape(-1),
        vals.reshape(-1),
    )


def _walk_apply(arrays, src, out_pad, storage, square=False):
    """``out[write] += value * src[read]`` over the walked slots, float64.
    On the int8 rung the kernel gathers from a bfloat16-rounded source."""
    import ml_dtypes

    write, read, vals = _walk_layout(arrays, storage)
    src = np.asarray(src, np.float32)
    if storage != "f32":
        src = src.astype(ml_dtypes.bfloat16)
    out = np.zeros(out_pad, np.float64)
    np.add.at(out, write, (vals * vals if square else vals)
              * src.astype(np.float64)[read])
    return out


def _entries(rows, cols, vals, width):
    """Sorted (row * width + col) keys and float64 sums of the nonzero
    entries: a matrix, whatever order and padding it was stored in."""
    keys, inv = np.unique(rows.astype(np.int64) * width + cols,
                          return_inverse=True)
    sums = np.bincount(inv.reshape(-1), weights=vals, minlength=len(keys))
    return keys[sums != 0], sums[sums != 0]


# the schedule's edge shapes: (GROUPS_PER_STEP, SEGMENTS_PER_DMA,
# GROUPS_PER_RUN, n, d, k)
_EDGE_SHAPES = {
    # >=2 DMA steps: step t starts step t+1's fetch into the other buffer
    # slot before it waits its own
    "cross_step_boundary": (8, 2, 2, 2048, 4096, 4),
    # the whole stream is ONE DMA step: no next fetch is ever started
    "one_step_stream": (8, 2, 2, 1024, 1024, 1),
    # SEGMENTS_PER_DMA=1: EVERY step (the last included) holds a single
    # segment, so every segment follows a wait
    "single_segment_steps": (8, 1, 2, 2048, 4096, 4),
    # GROUPS_PER_STEP == GROUPS_PER_RUN: each segment is ONE slab run,
    # so phase 1 is a single batched gather per segment
    "single_run_segments": (2, 2, 2, 1500, 4096, 3),
}


def _retune(monkeypatch, step=8, dma=2, run=2):
    import photon_ml_tpu.ops.sparse_tiled as st

    monkeypatch.setattr(st, "GROUPS_PER_STEP", step)
    monkeypatch.setattr(st, "SEGMENTS_PER_DMA", dma)
    monkeypatch.setattr(st, "GROUPS_PER_RUN", run)


def _plain_batch(rng, n, d, k):
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    return SparseBatch(
        indices=jnp.asarray(idx), values=jnp.asarray(val),
        labels=jnp.zeros(n, jnp.float32),
        offsets=jnp.zeros(n, jnp.float32),
        weights=jnp.ones(n, jnp.float32), num_features=d,
    )


def _edge_batch(rng, monkeypatch, shape):
    step, dma, run, n, d, k = _EDGE_SHAPES[shape]
    _retune(monkeypatch, step, dma, run)
    return _plain_batch(rng, n, d, k)


class TestLayoutWalk:
    """The layout ALONE encodes the matrix: walked with plain numpy
    (``_walk_layout``), a direction's five streams give back every entry of
    the batch at its (row, column), exactly on the f32 rung and to half a
    quantisation step of its cell on int8 — no kernel involved."""

    @pytest.mark.parametrize("side", ["m_arrays", "g_arrays"])
    @pytest.mark.parametrize("storage", ["f32", "int8"])
    @pytest.mark.parametrize("shape", list(_EDGE_SHAPES))
    def test_streams_decode_to_the_matrix(
        self, rng, monkeypatch, shape, storage, side
    ):
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", storage)
        batch = _edge_batch(rng, monkeypatch, shape)
        (chunk,) = tile_sparse_batch(batch).chunks
        idx = np.asarray(batch.indices).astype(np.int64)
        val = np.asarray(batch.values).astype(np.float64)
        rows = np.repeat(np.arange(idx.shape[0]), idx.shape[1])
        width = max(chunk.n_pad, chunk.d_pad)
        want_keys, want = _entries(rows, idx.reshape(-1), val.reshape(-1), width)
        write, read, vals = _walk_layout(getattr(chunk, side), storage)
        # margins write rows and read columns; the gradient the reverse
        r, c = (write, read) if side == "m_arrays" else (read, write)
        got_keys, got = _entries(r, c, vals, width)
        if storage == "f32":
            np.testing.assert_array_equal(got_keys, want_keys)
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)
        else:
            # an entry that quantises to 0 leaves the walk: it reads 0
            at = np.searchsorted(want_keys, got_keys)
            np.testing.assert_array_equal(want_keys[at], got_keys)
            got_all = np.zeros(len(want))
            got_all[at] = got
            half_step = np.max(np.abs(val)) / 127.0 / 2.0
            assert np.max(np.abs(got_all - want)) <= half_step * (1 + 1e-6)


# near-empty cells (20 nonzeros each; row slabs x column slabs) that take the
# sparse-cell form under each carve of ``_EDGE_SHAPES``
_EDGE_CELLS = {
    "cross_step_boundary": [[20] * 24] * 4,
    # 2 x 2 cells: two write slabs a direction, one segment each
    "one_step_stream": [[20] * 2] * 2,
    "single_segment_steps": [[20] * 24] * 4,
    "single_run_segments": [[20] * 24] * 2,
}
_DIRECTIONS = ("margins", "gradient", "gradient_sq")


def _apply_and_walk(tb, direction, rng):
    """One direction of a one-chunk tiled batch through the kernel, and the
    float64 walk of the same streams: ``(got, want, src)``."""
    (chunk,) = tb.chunks
    n, d = tb.num_rows, tb.num_features
    if direction == "margins":
        src = rng.normal(size=d).astype(np.float32)
        got = tb.matvec(jnp.asarray(src))
        want = _walk_apply(
            chunk.m_arrays, np.pad(src, (0, chunk.d_pad - d)), chunk.n_pad, "f32"
        )[:n]
    else:
        sq = direction == "gradient_sq"
        src = rng.normal(size=n).astype(np.float32)
        got = (tb.rmatvec_sq if sq else tb.rmatvec)(jnp.asarray(src))
        want = _walk_apply(
            chunk.g_arrays, np.pad(src, (0, chunk.n_pad - n)), chunk.d_pad,
            "f32", sq,
        )[:d]
    return np.asarray(got), want, src


def _direction_steps(tb, direction):
    import photon_ml_tpu.ops.sparse_tiled as st

    (chunk,) = tb.chunks
    arrays = chunk.m_arrays if direction == "margins" else chunk.g_arrays
    return int(arrays[0].shape[0]) // (st.GROUPS_PER_STEP * st.SEGMENTS_PER_DMA)


@pytest.mark.kernel
class TestSegmentSchedule:
    """The kernel's one schedule (a step starts the next fetch, waits its
    own, runs phase 1 then phase 2 of each segment) against the float64
    walk of the streams it reads, at the carves where a schedule can go
    wrong (``_EDGE_SHAPES``): a stream of one DMA step, steps of one
    segment, a boundary between steps, segments of one run. Both forms of
    a stream, and a stream run as several kernel calls. Retuned-down
    constants throughout (tier-1 runtime budget)."""

    @pytest.mark.parametrize("shape", list(_EDGE_SHAPES))
    def test_edge_shape(self, rng, monkeypatch, shape):
        batch = _edge_batch(rng, monkeypatch, shape)
        tb = tile_sparse_batch(batch)
        steps = _direction_steps(tb, "margins")
        if shape == "one_step_stream":
            assert steps == 1
        elif shape != "single_run_segments":
            assert steps >= 2
        for direction in _DIRECTIONS:
            got, want, _ = _apply_and_walk(tb, direction, rng)
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("direction", _DIRECTIONS)
    @pytest.mark.parametrize("shape", list(_EDGE_SHAPES))
    def test_edge_shape_in_the_sparse_cell_form(
        self, rng, monkeypatch, shape, direction
    ):
        import photon_ml_tpu.ops.sparse_tiled as st

        step, dma, run = _EDGE_SHAPES[shape][:3]
        _retune(monkeypatch, step, dma, run)
        cells = _EDGE_CELLS[shape]
        batch = _cell_batch(rng, len(cells) * SLAB, len(cells[0]) * SLAB, cells)
        tb = tile_sparse_batch(batch, hbm_budget_bytes=1e12)
        (chunk,) = tb.chunks
        for arrays in (chunk.m_arrays, chunk.g_arrays):
            assert arrays[3].shape[0] == arrays[0].shape[0] * st.SUB_SLABS
        steps = _direction_steps(tb, direction)
        if shape == "one_step_stream":
            assert steps == 1
        else:
            assert steps >= 2
        got, want, _ = _apply_and_walk(tb, direction, rng)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("form", ["runs", "sparse_cell"])
    def test_a_stream_run_as_several_kernel_calls(self, rng, monkeypatch, form):
        """With the SMEM budget cut to one DMA step's prefetch words a
        stream runs as one call a step, each starting its own fetch at its
        own ``step0``; their summed outputs are the walk's."""
        import photon_ml_tpu.ops.sparse_tiled as st

        _retune(monkeypatch)
        step_groups = st.GROUPS_PER_STEP * st.SEGMENTS_PER_DMA
        if form == "runs":
            batch = _plain_batch(rng, n=2048, d=4096, k=4)
            tb = tile_sparse_batch(batch)
            per_step = 4 * (st.SEGMENTS_PER_DMA + step_groups // st.GROUPS_PER_RUN)
        else:
            batch = _cell_batch(rng, 4 * SLAB, 6 * SLAB, [[20] * 6] * 4)
            tb = tile_sparse_batch(batch, hbm_budget_bytes=1e12)
            per_step = 4 * st.SEGMENTS_PER_DMA  # the slab ids stay in HBM
        (chunk,) = tb.chunks
        sub = chunk.m_arrays[3].shape[0] > chunk.m_arrays[0].shape[0]
        assert sub == (form == "sparse_cell")
        pieces = []
        bounds = st._piece_bounds
        monkeypatch.setattr(
            st, "_piece_bounds",
            lambda *a: pieces.append(bounds(*a)) or pieces[-1],
        )
        monkeypatch.setattr(st, "_SMEM_PREFETCH_BUDGET", per_step)
        # the budget is read at trace time and is not a jit key
        st._tiled_apply_jit.clear_cache()
        try:
            for direction in ("margins", "gradient"):
                steps = _direction_steps(tb, direction)
                assert steps >= 2
                got, want, _ = _apply_and_walk(tb, direction, rng)
                np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
                assert len(pieces[-1]) == steps
        finally:
            st._tiled_apply_jit.clear_cache()


class TestTileLayoutCache:
    """The process-wide layout cache (``ops/tile_cache``): identical
    sparsity structure never re-packs; anything layout-relevant — values,
    indices, tuned constants — misses by key."""

    def _batch(self, rng, n=2048, d=4096, k=4, seed_vals=None):
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = (seed_vals if seed_vals is not None
               else rng.normal(size=(n, k))).astype(np.float32)
        return SparseBatch(
            indices=jnp.asarray(idx), values=jnp.asarray(val),
            labels=jnp.asarray(rng.uniform(size=n).astype(np.float32)),
            offsets=jnp.zeros(n, jnp.float32),
            weights=jnp.ones(n, jnp.float32), num_features=d,
        )

    def test_hit_shares_layout_and_carries_callers_rows(self, rng):
        import dataclasses

        from photon_ml_tpu.ops import tile_cache

        tile_cache.clear()
        b1 = self._batch(rng)
        tb1 = tile_cache.tiled_layout_for(b1)
        # same structure, different labels/offsets (the GAME residual swap)
        b2 = dataclasses.replace(
            b1,
            labels=jnp.ones_like(b1.labels),
            offsets=jnp.full_like(b1.offsets, 0.5),
        )
        tb2 = tile_cache.tiled_layout_for(b2)
        s = tile_cache.stats()
        assert (s["hits"], s["misses"]) == (1, 1)
        assert tb2.chunks is tb1.chunks  # packed streams shared
        np.testing.assert_array_equal(np.asarray(tb2.labels), 1.0)
        np.testing.assert_array_equal(np.asarray(tb2.offsets), 0.5)

    def test_structure_change_misses(self, rng):
        import dataclasses

        from photon_ml_tpu.ops import tile_cache

        tile_cache.clear()
        b1 = self._batch(rng)
        tile_cache.tiled_layout_for(b1)
        b2 = dataclasses.replace(
            b1, values=b1.values.at[0, 0].add(1.0)
        )
        tile_cache.tiled_layout_for(b2)
        s = tile_cache.stats()
        assert (s["hits"], s["misses"]) == (0, 2)

    def test_retuned_constants_change_the_key(self, rng, monkeypatch):
        import photon_ml_tpu.ops.sparse_tiled as st
        from photon_ml_tpu.ops import tile_cache

        tile_cache.clear()
        b = self._batch(rng)
        tile_cache.tiled_layout_for(b)
        monkeypatch.setattr(st, "GROUPS_PER_RUN", 4)
        tb = tile_cache.tiled_layout_for(b)
        s = tile_cache.stats()
        assert (s["hits"], s["misses"]) == (0, 2)
        # the rebuilt layout actually reflects the retune (rrun granularity)
        for c in tb.chunks:
            assert c.m_arrays[3].shape[0] == c.m_arrays[0].shape[0] // 4

    def test_capacity_bounds_and_clear(self, rng, monkeypatch):
        from photon_ml_tpu.ops import tile_cache

        tile_cache.clear()
        old = tile_cache.capacity()
        old_bytes = tile_cache.byte_budget()
        try:
            tile_cache.set_capacity(2)
            batches = [self._batch(rng) for _ in range(3)]
            for b in batches:
                tile_cache.tiled_layout_for(b)
            assert tile_cache.stats()["entries"] == 2
            # oldest entry evicted: re-requesting it is a miss
            tile_cache.tiled_layout_for(batches[0])
            assert tile_cache.stats()["misses"] == 4
            # the BYTE budget also evicts (device-resident streams must
            # never pile up unbounded): one entry's worth keeps one entry
            one = tile_cache.stats()["bytes"] // 2
            tile_cache.set_byte_budget(one + 1)
            assert tile_cache.stats()["entries"] == 1
            # an over-budget layout still builds, but is never pinned
            tile_cache.set_byte_budget(1)
            tb = tile_cache.tiled_layout_for(batches[1])
            assert tb.chunks and tile_cache.stats()["entries"] == 0
        finally:
            tile_cache.set_capacity(old)
            tile_cache.set_byte_budget(old_bytes)
            tile_cache.clear()
        assert tile_cache.stats() == {
            "hits": 0, "misses": 0, "entries": 0, "bytes": 0
        }

    @pytest.mark.kernel  # the numerical-agreement check traces the kernel
    def test_streaming_objective_rebuild_hits_cache(self, rng, monkeypatch):
        """Rebuilding a StreamingGLMObjective over the same sparse chunks
        (GAME trainers rebuild per fit; drivers per sweep) re-packs
        nothing."""
        import photon_ml_tpu.ops.sparse_tiled as st
        from photon_ml_tpu.ops import tile_cache

        # small segment constants: this test gates the CACHE, not the
        # default-constant kernel (covered by the parity tests above)
        monkeypatch.setattr(st, "GROUPS_PER_STEP", 8)
        monkeypatch.setattr(st, "SEGMENTS_PER_DMA", 2)
        from photon_ml_tpu.ops.losses import loss_for_task
        from photon_ml_tpu.ops.streaming import (
            StreamingGLMObjective,
            sparse_chunks,
        )
        from photon_ml_tpu.types import TaskType

        tile_cache.clear()
        n, d, k = 1024, 2048, 3
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        chunks = sparse_chunks(idx, val, y, chunk_rows=512)
        loss = loss_for_task(TaskType.LOGISTIC_REGRESSION)

        builds = {"n": 0}
        orig = st.tile_sparse_batch

        def counting(b, **kw):
            builds["n"] += 1
            return orig(b, **kw)

        st.tile_sparse_batch = counting
        try:
            obj1 = StreamingGLMObjective(
                chunks, loss, num_features=d, tile_sparse=True
            )
            first = builds["n"]
            obj2 = StreamingGLMObjective(
                chunks, loss, num_features=d, tile_sparse=True
            )
        finally:
            st.tile_sparse_batch = orig
        assert first == len(chunks)
        assert builds["n"] == first, "rebuild re-packed a cached chunk"
        # and the two objectives agree numerically
        w = rng.normal(size=d).astype(np.float32)
        v1, g1 = obj1.value_and_grad(w)
        v2, g2 = obj2.value_and_grad(w)
        np.testing.assert_allclose(float(v1), float(v2), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-6)

    def test_cv_ingest_uses_cache(self, rng, monkeypatch):
        """The CV fold ingest applies the framework's ONE standard rule
        (optimize_batch_layout): dense-fitting sparse batches densify,
        over-budget high-dim sparse tiles through the process-wide cache,
        dense passes through."""
        import photon_ml_tpu.ops.batch as ob
        from photon_ml_tpu.ops import tile_cache
        from photon_ml_tpu.ops.batch import DenseBatch
        from photon_ml_tpu.supervised.cross_validation import (
            _ingest_training_batch,
        )

        tile_cache.clear()
        big = self._batch(rng, n=SLAB + 11, d=8192, k=4)
        # simulate an over-budget dense form (a real one needs >6 GB)
        monkeypatch.setattr(ob, "maybe_densify", lambda b, *a, **k: b)
        out1 = _ingest_training_batch(big)
        out2 = _ingest_training_batch(big)
        assert isinstance(out1, TiledSparseBatch)
        assert out2.chunks is out1.chunks
        s = tile_cache.stats()
        assert (s["hits"], s["misses"]) == (1, 1)
        monkeypatch.undo()
        # dense-fitting sparse takes the standard densify path
        small = self._batch(rng, n=256, d=512, k=4)
        assert isinstance(_ingest_training_batch(small), DenseBatch)
        dense = DenseBatch(
            X=jnp.zeros((8, 4), jnp.float32),
            labels=jnp.zeros(8, jnp.float32),
            offsets=jnp.zeros(8, jnp.float32),
            weights=jnp.ones(8, jnp.float32),
        )
        assert _ingest_training_batch(dense) is dense


@pytest.mark.kernel
def test_layout_tracks_retuned_segment_constants(rng, monkeypatch):
    """The layout builder must read GROUPS_PER_STEP / SEGMENTS_PER_DMA at
    CALL time: a default-arg capture froze the import-time value, so
    layouts built after retuning the constants silently disagreed with
    the kernel consuming them — garbage outputs with no error (caught by
    an on-hardware parity probe during the r5 G=32 retune)."""
    import photon_ml_tpu.ops.sparse_tiled as st
    from photon_ml_tpu.ops.batch import SparseBatch

    monkeypatch.setattr(st, "GROUPS_PER_STEP", 8)
    monkeypatch.setattr(st, "SEGMENTS_PER_DMA", 2)
    n, d, k = 2048, 4096, 4
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    b = SparseBatch(
        indices=jnp.asarray(idx), values=jnp.asarray(val),
        labels=jnp.zeros(n, jnp.float32),
        offsets=jnp.zeros(n, jnp.float32),
        weights=jnp.ones(n, jnp.float32), num_features=d,
    )
    tb = st.tile_sparse_batch(b)
    # stream must divide into whole retuned DMA steps
    step = 8 * 2 * st.GROUP
    for c in tb.chunks:
        assert c.m_arrays[0].shape[0] * st.GROUP % step == 0
    w = jnp.asarray(rng.normal(size=d).astype(np.float32))
    r = jnp.asarray(rng.normal(size=n).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(tb.matvec(w)), np.asarray(b.matvec(w)),
        rtol=2e-4, atol=2e-4,
    )
    np.testing.assert_allclose(
        np.asarray(tb.rmatvec(r)), np.asarray(b.rmatvec(r)),
        rtol=2e-3, atol=2e-3,
    )


class TestSmemPieces:
    """A kernel call's scalar-prefetch streams must fit SMEM. How many
    groups a layout pads to depends on where the nonzeros fall, so the
    split into calls is read off the built stream, never off a row bound
    guessed from shapes."""

    SHIPPED_STEP_BYTES = 272  # 4 segments + 64 runs of i32 per 128 groups

    @pytest.mark.parametrize(
        "groups",
        # padded group counts at shipped constants: A2's shape and the
        # n=2^20 shape (chip, PR 21); n=2^21 with 8 uniform nonzeros a row
        # at d=2^17 (pads 4.0x); 2^22 rows of the same
        [200448, 401024, 524288, 1 << 20],
    )
    def test_every_piece_fits_the_shipped_budget(self, groups):
        import photon_ml_tpu.ops.sparse_tiled as st

        n_steps = groups // 128
        bounds = st._piece_bounds(n_steps, self.SHIPPED_STEP_BYTES)
        assert bounds[-1] == n_steps and bounds == sorted(set(bounds))
        sizes = np.diff([0] + bounds)
        assert (sizes * self.SHIPPED_STEP_BYTES <= st._SMEM_PREFETCH_BUDGET).all()
        # one call when the stream fits, and never a call more than needed
        max_steps = st._SMEM_PREFETCH_BUDGET // self.SHIPPED_STEP_BYTES
        assert (len(bounds) - 1) * max_steps < n_steps
        assert len(bounds) == {200448: 1, 401024: 1, 524288: 2, 1 << 20: 3}[groups]

    @pytest.mark.kernel
    @pytest.mark.parametrize("columns", ["uniform_k8", "zipf"])
    @pytest.mark.parametrize("storage", ["f32", "int8"])
    def test_split_stream_matches_the_xla_path(
        self, rng, monkeypatch, columns, storage
    ):
        """Narrow rows and skewed columns pad far past the 1.5x of A2's
        traffic; with the budget cut to a few DMA steps the stream runs
        as several kernel calls whose summed outputs match the XLA path."""
        import photon_ml_tpu.ops.sparse_tiled as st

        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", storage)
        n, d, k = 2048, 8192, 8
        if columns == "zipf":
            idx = (rng.zipf(1.3, size=(n, k)) - 1) % d
        else:
            idx = rng.integers(0, d, size=(n, k))
        val = rng.normal(size=(n, k)).astype(np.float32)
        b = SparseBatch(
            indices=jnp.asarray(idx.astype(np.int32)), values=jnp.asarray(val),
            labels=jnp.zeros(n, jnp.float32),
            offsets=jnp.zeros(n, jnp.float32),
            weights=jnp.ones(n, jnp.float32), num_features=d,
        )
        tb = st.tile_sparse_batch(b)
        (chunk,) = tb.chunks
        step_groups = st.GROUPS_PER_STEP * st.SEGMENTS_PER_DMA
        n_steps = chunk.m_arrays[0].shape[0] // step_groups
        assert n_steps >= 3
        per_step = 4 * (st.SEGMENTS_PER_DMA + step_groups // st.GROUPS_PER_RUN
                        * (2 if storage == "int8" else 1))
        monkeypatch.setattr(st, "_SMEM_PREFETCH_BUDGET", 2 * per_step)
        assert len(st._piece_bounds(n_steps, per_step)) == -(-n_steps // 2)
        # the budget is read at trace time and is not a jit key
        st._tiled_apply_jit.clear_cache()
        try:
            w = jnp.asarray(rng.normal(size=d).astype(np.float32))
            r = jnp.asarray(rng.normal(size=n).astype(np.float32))
            # of the largest entry: f32 rounding, or the int8 rung's
            # documented quantization bound
            rel = 1e-5 if storage == "f32" else 6e-2
            for got, want in (
                (tb.matvec(w), b.matvec(w)), (tb.rmatvec(r), b.rmatvec(r)),
            ):
                want = np.asarray(want)
                np.testing.assert_allclose(
                    np.asarray(got), want, rtol=0,
                    atol=rel * float(np.abs(want).max()),
                )
        finally:
            st._tiled_apply_jit.clear_cache()


class TestTopologyKeyedCaches:
    """Executable and layout caches key on the EFFECTIVE device topology
    (backend, local device count, effective process count): re-entering
    the same topology grows nothing, and a degrade-in-place — which
    changes the effective group without a process restart — misses by
    key instead of reusing a stale executable by luck."""

    def test_tuned_constants_carry_effective_topology(self, monkeypatch):
        import jax

        import photon_ml_tpu.parallel.multihost as mh
        from photon_ml_tpu.ops import tile_cache

        t1 = tile_cache.tuned_constants()
        assert t1[-1] == (
            jax.default_backend(), len(jax.local_devices()), 1,
        )
        # same-topology re-entry: the IDENTICAL key, read at call time
        assert tile_cache.tuned_constants() == t1
        monkeypatch.setattr(
            mh, "_DEGRADED", {"survivors": (0, 1), "rank": 0}
        )
        t2 = tile_cache.tuned_constants()
        assert t2[:-1] == t1[:-1]
        assert t2[-1][2] == 2 and t2 != t1

    def test_keys_hold_exactly_the_constants_that_remain(self, monkeypatch):
        """One kernel, one schedule, two rungs: the layout cache's key and
        the kernel executable's static arguments name the constants the
        module still has, and nothing that is gone."""
        import inspect

        import photon_ml_tpu.ops.sparse_tiled as st
        from photon_ml_tpu.ops import tile_cache
        from photon_ml_tpu.parallel.multihost import effective_topology

        monkeypatch.delenv("PHOTON_KERNEL_DTYPE", raising=False)
        topology = effective_topology()
        assert tile_cache.tuned_constants() == (
            st.GROUP, st.SLAB, st.GROUPS_PER_STEP, st.SEGMENTS_PER_DMA,
            st.GROUPS_PER_RUN, st.HEAD_MIN_FILL, st.SUB_SLABS,
            st.SUB_GROUP_COST, st.FINE_SLABS, st.FINE_GROUP_COST, "f32",
            topology,
        )
        # a stream's FORM is no static argument: the kernel reads it off the
        # stream's own shape (one read slab a run, or SUB_SLABS a group)
        params = list(inspect.signature(st._tiled_apply_jit).parameters)
        assert params == [
            "layout_arrays", "src", "out_pad", "src_pad", "square_vals",
            "groups", "segs", "run_groups", "storage", "interpret",
            "topology", "stack",
        ]
        assert not hasattr(st, "PIPELINE_SEGMENTS")
        assert not hasattr(st, "_run_segment_schedule")
        # _tiled_apply hands the jitted call exactly those, in that order
        # (it reads the streams' shapes for its counters)
        lay = st.build_write_major_layout(
            np.arange(8), np.arange(8), np.ones(8, np.float32), 2048, 1024
        )
        streams = (lay.packed, lay.wslab, lay.rslab, lay.rrun, lay.srun)
        monkeypatch.setattr(st, "_tiled_apply_jit", lambda *args: args)
        args = st._tiled_apply(streams, "src", 2048, 1024, True)
        assert args[0] is streams and args[1:] == (
            "src", 2048, 1024, True,
            st.GROUPS_PER_STEP, st.SEGMENTS_PER_DMA, st.GROUPS_PER_RUN,
            "f32", st._interpret(), topology, 1,
        )
        assert st.KERNEL_DTYPES == ("f32", "int8")

    def test_tiled_apply_zero_growth_then_topology_miss(
        self, rng, monkeypatch
    ):
        import photon_ml_tpu.ops.sparse_tiled as st
        import photon_ml_tpu.parallel.multihost as mh

        monkeypatch.setattr(st, "GROUPS_PER_STEP", 8)
        monkeypatch.setattr(st, "SEGMENTS_PER_DMA", 2)
        monkeypatch.setattr(st, "GROUPS_PER_RUN", 2)
        n, d, k = 1024, 1024, 1
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        batch = SparseBatch(
            indices=jnp.asarray(idx), values=jnp.asarray(val),
            labels=jnp.zeros(n, jnp.float32),
            offsets=jnp.zeros(n, jnp.float32),
            weights=jnp.ones(n, jnp.float32), num_features=d,
        )
        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        tb = tile_sparse_batch(batch)
        tb.matvec(w)
        size0 = st._tiled_apply_jit._cache_size()
        tb.matvec(w)  # same topology: ZERO executable-cache growth
        assert st._tiled_apply_jit._cache_size() == size0
        monkeypatch.setattr(
            mh, "_DEGRADED", {"survivors": (0, 1), "rank": 0}
        )
        tb.matvec(w)  # degraded topology: new static key, fresh compile
        assert st._tiled_apply_jit._cache_size() == size0 + 1

    def test_topology_change_misses_layout_cache(self, rng, monkeypatch):
        import photon_ml_tpu.parallel.multihost as mh
        from photon_ml_tpu.ops import tile_cache

        tile_cache.clear()
        n, d, k = 2048, 4096, 4
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        b = SparseBatch(
            indices=jnp.asarray(idx), values=jnp.asarray(val),
            labels=jnp.zeros(n, jnp.float32),
            offsets=jnp.zeros(n, jnp.float32),
            weights=jnp.ones(n, jnp.float32), num_features=d,
        )
        tile_cache.tiled_layout_for(b)
        monkeypatch.setattr(
            mh, "_DEGRADED", {"survivors": (0, 1), "rank": 0}
        )
        tile_cache.tiled_layout_for(b)
        s = tile_cache.stats()
        assert (s["hits"], s["misses"]) == (0, 2)
        tile_cache.clear()


def _zipf_problem(rng, n=1100, d=4608, k=12, repeats=True):
    """Rows whose columns follow Zipf(1.0) popularity ranks sent through an
    affine permutation, as ``benchmark/datagen.sparse_glm_rows`` draws them:
    a row may draw a column twice, and such entries add. ``repeats=False``
    blanks a row's later draws of a column, as real rows have none."""
    u = rng.uniform(size=(n, k))
    rank = np.clip(np.floor(np.exp(u * np.log(d + 1.0)) - 1.0), 0, d - 1)
    idx = ((rank.astype(np.int64) * 3571 + 17) % d).astype(np.int32)
    val = rng.uniform(0.05, 1.0, size=(n, k)).astype(np.float32)
    val[rng.uniform(size=(n, k)) < 0.05] = 0.0  # ingest padding slots
    if not repeats:
        val[_later_draws(idx)] = 0.0
    return SparseBatch(
        indices=jnp.asarray(idx), values=jnp.asarray(val),
        labels=jnp.asarray((rng.uniform(size=n) < 0.5).astype(np.float32)),
        offsets=jnp.zeros((n,), jnp.float32),
        weights=jnp.ones((n,), jnp.float32), num_features=d,
    )


def _later_draws(idx):
    """Slots that name a column an earlier slot of their row names."""
    same = idx[:, :, None] == idx[:, None, :]
    return np.tril(same, k=-1).any(axis=2)


# the tail's padding these tests' byte arithmetic assumes, whatever head
_QUARTER = lambda head_cols: (0, 1.25)  # the run form, a quarter over


def _without_repeats(batch):
    val = np.asarray(batch.values).copy()
    val[_later_draws(np.asarray(batch.indices))] = 0.0
    import dataclasses

    return dataclasses.replace(batch, values=jnp.asarray(val))


def _parents_chunk(batch):
    """The one chunk the builder made before the dense head existed: every
    stored nonzero through the (untouched) tile-COO layout builder."""
    import photon_ml_tpu.ops.sparse_tiled as st

    idx, val = np.asarray(batch.indices), np.asarray(batch.values)
    n, k = idx.shape
    keep = val.reshape(-1) != 0.0
    n_pad, d_pad = -(-n // SLAB) * SLAB, -(-batch.num_features // SLAB) * SLAB
    m, g, _ = st._pack_chunk(
        np.repeat(np.arange(n, dtype=np.int64), k)[keep],
        idx.reshape(-1).astype(np.int64)[keep], val.reshape(-1)[keep],
        n_pad=n_pad, d_pad=d_pad,
    )
    return st._stage_chunk([(m, g)], 0, n_pad, d_pad)


def _assert_chunks_equal(chunk, parent):
    for side in ("m_arrays", "g_arrays"):
        for a, b in zip(getattr(chunk, side), getattr(parent, side)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _stored_values(arrays):
    """Nonzero values a direction's packed f32 stream holds."""
    return np.count_nonzero(np.asarray(arrays[0])[:, 2, :])


class TestDenseHead:
    """The dense head beside the tile-COO tail (PR 28): popular columns
    leave the kernels' streams for one float32 matrix, by a rule that reads
    the column counts and the budget and nothing else; and one entry a
    (row, column) in head and tail alike."""

    @pytest.mark.kernel
    def test_zipf_matrix_builds_a_head_and_matches_sparse_batch(self, rng):
        batch = _zipf_problem(rng)
        tiled = tile_sparse_batch(batch, hbm_budget_bytes=8e9)
        n, d = batch.num_rows, batch.num_features
        assert tiled.head_X.shape == (n, 128) and tiled.head_X.dtype == jnp.float32
        assert tiled.head_cols.shape == (128,) and tiled.head_cols.dtype == jnp.int32
        head_cols = np.asarray(tiled.head_cols)
        idx, val = np.asarray(batch.indices), np.asarray(batch.values)
        in_head = np.isin(idx, head_cols) & (val != 0.0)
        in_tail = ~np.isin(idx, head_cols) & (val != 0.0)
        assert in_head.sum() > np.count_nonzero(val) // 3  # log(129) / log(d + 1)
        # every (row, column) of the tail lies in the streams once
        rows = np.repeat(np.arange(n)[:, None], idx.shape[1], axis=1)
        entries = len(set(zip(rows[in_tail], idx[in_tail])))
        assert entries < in_tail.sum()  # the tail has repeated draws too
        for c in tiled.chunks:
            assert _stored_values(c.m_arrays) == entries
            assert _stored_values(c.g_arrays) == entries
        # a row's repeated draws of a head column add into one entry
        slot = np.searchsorted(np.sort(head_cols), idx[in_head])
        expect = np.zeros((n, 128), np.float64)
        np.add.at(expect, (rows[in_head], slot), val[in_head])
        by_id = np.argsort(head_cols)
        assert (np.count_nonzero(expect) < in_head.sum())  # duplicates exist
        np.testing.assert_allclose(
            np.asarray(tiled.head_X)[:, by_id], expect, rtol=1e-6
        )

        w = jnp.asarray(rng.normal(size=d).astype(np.float32))
        r = jnp.asarray(rng.normal(size=n).astype(np.float32))
        np.testing.assert_allclose(
            np.asarray(tiled.matvec(w)), np.asarray(batch.matvec(w)),
            rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(tiled.rmatvec(r)), np.asarray(batch.rmatvec(r)),
            rtol=1e-5, atol=1e-4,
        )
        # the Hessian diagonal squares the matrix's ENTRY, the sum of a
        # row's draws of a column, in head and tail alike
        np.testing.assert_allclose(
            np.asarray(tiled.rmatvec_sq(r)),
            np.asarray(densify(batch).rmatvec_sq(r)),
            rtol=1e-5, atol=1e-4,
        )
        # and where no row repeats a column that is SparseBatch's answer
        plain = _without_repeats(batch)
        np.testing.assert_allclose(
            np.asarray(tile_sparse_batch(plain, hbm_budget_bytes=8e9).rmatvec_sq(r)),
            np.asarray(plain.rmatvec_sq(r)), rtol=1e-5, atol=1e-4,
        )

    @pytest.mark.kernel
    @pytest.mark.parametrize(
        "kwargs",
        [dict(), dict(hbm_budget_bytes=8e9), dict(keep_empty_chunks=True),
         dict(fe_range=(0, 0, 4608, 1))],
    )
    def test_repeated_draws_merge_into_one_entry_in_every_build(self, rng, kwargs):
        """One contract whatever the builder and whatever the column: the
        layout holds the sum of a row's draws of a column once."""
        batch = _zipf_problem(rng)
        tiled = tile_sparse_batch(batch, **kwargs)
        assert (tiled.head_X is not None) == ("hbm_budget_bytes" in kwargs)
        dense = densify(batch)
        w = jnp.asarray(rng.normal(size=batch.num_features).astype(np.float32))
        r = jnp.asarray(rng.normal(size=batch.num_rows).astype(np.float32))
        for method, arg in (("matvec", w), ("rmatvec", r), ("rmatvec_sq", r)):
            np.testing.assert_allclose(
                np.asarray(getattr(tiled, method)(arg)),
                np.asarray(getattr(dense, method)(arg)), rtol=1e-5, atol=1e-4,
            )

    def test_merge_keeps_the_first_draw_and_leaves_plain_rows_alone(self):
        import photon_ml_tpu.ops.sparse_tiled as st

        idx = np.array([[7, 3, 7, 0, 7], [1, 2, 3, 4, 5], [0, 0, 9, 9, 0]], np.int32)
        val = np.array([[1, 2, 4, 0, 8], [1, 2, 3, 4, 5], [0, 2, -3, 3, 5]], np.float32)
        live = val != 0.0
        out = st._merge_repeats(idx, val, live, 16)
        np.testing.assert_array_equal(
            out, [[13, 2, 0, 0, 0], [1, 2, 3, 4, 5], [0, 7, 0, 0, 0]]
        )
        # slots that are not live (the head's columns) neither merge nor move
        live[0, 0] = False
        np.testing.assert_array_equal(
            st._merge_repeats(idx, val, live, 16)[0], [1, 2, 12, 0, 0]
        )
        # no repeated draw: the very array comes back
        plain = np.array([[1.0, 2.0], [3.0, 0.0]], np.float32)
        cols = np.array([[4, 5], [4, 0]], np.int32)
        assert st._merge_repeats(cols, plain, plain != 0.0, 16) is plain

    @pytest.mark.kernel
    @pytest.mark.parametrize("popular", [False, True])
    def test_matrix_without_repeats_or_head_is_the_parents_layout_bit_for_bit(
        self, rng, popular
    ):
        """Uniform columns find no head; Zipf columns without a budget are
        not asked. Either way, rows that repeat no column are laid out
        value for value as the builder laid them out before PR 28 (the
        budgeted build's cells are full, 1,100 nonzeros each, so it keeps
        the run form too: ``TestSparseCellForm`` has the other side)."""
        batch = (
            _zipf_problem(rng, repeats=False) if popular
            else _without_repeats(_sparse_problem(rng, n=2 * SLAB))
        )
        tiled = tile_sparse_batch(
            batch, **({} if popular else dict(hbm_budget_bytes=8e9))
        )
        assert tiled.head_X is None and tiled.head_cols is None
        parent = _parents_chunk(batch)
        (chunk,) = tiled.chunks
        _assert_chunks_equal(chunk, parent)
        import dataclasses

        before = dataclasses.replace(tiled, chunks=(parent,))
        w = jnp.asarray(rng.normal(size=batch.num_features).astype(np.float32))
        r = jnp.asarray(rng.normal(size=batch.num_rows).astype(np.float32))
        np.testing.assert_array_equal(
            np.asarray(tiled.matvec(w)), np.asarray(before.matvec(w))
        )
        np.testing.assert_array_equal(
            np.asarray(tiled.rmatvec(r)), np.asarray(before.rmatvec(r))
        )

    @staticmethod
    def _zipf_counts(d=47236, nnz=98_900_254):
        ranks = np.arange(d)
        share = np.log((ranks + 2.0) / (ranks + 1.0)) / np.log(d + 1.0)
        counts = np.zeros(d, np.int64)
        counts[(ranks * 3571 + 17) % d] = np.round(share * nnz)
        return counts

    @pytest.mark.parametrize("rows", [1_354_798, 2 * 1_354_798, 1 << 20])
    def test_rule_takes_the_most_popular_columns_in_lane_blocks(self, rows):
        import photon_ml_tpu.ops.sparse_tiled as st

        # the rule knows fills, not sizes: the same matrix at another
        # number of rows (a streamed chunk's 2^20 too) gets the same head
        counts = self._zipf_counts(nnz=73 * rows)
        head, _ = st._head_columns(counts, rows, 1e12, _QUARTER)
        assert len(head) == 384
        np.testing.assert_array_equal(
            head, np.argsort(-counts, kind="stable")[: len(head)]
        )
        # the last block is filled to the threshold, the next one is not
        by_count = np.sort(counts)[::-1]
        last = by_count[len(head) - 128: len(head)].sum()
        following = by_count[len(head): len(head) + 128].sum()
        assert last >= st.HEAD_MIN_FILL * 128 * rows > following

    @pytest.mark.parametrize("fits_blocks", [2, 1])
    def test_budget_cap_shrinks_the_head_to_what_fits_at_the_edge(self, fits_blocks):
        import photon_ml_tpu.ops.sparse_tiled as st

        counts = self._zipf_counts()
        rows = 1_354_798
        free, _ = st._head_columns(counts, rows, 1e12, _QUARTER)
        by_count = np.sort(counts)[::-1]
        # what ``fits_blocks`` blocks of head pin beside their tail: the
        # tail's two slots a nonzero, a quarter of padding, and the
        # relayout's copy of the streams
        width = 128 * fits_blocks
        need = 4.0 * rows * width + 2 * 12 * 1.25 * 2 * by_count[width:].sum()
        capped, _ = st._head_columns(counts, rows, need, _QUARTER)
        assert len(capped) == width < len(free)
        np.testing.assert_array_equal(capped, free[:width])
        # a byte less and the last block does not fit any more
        fewer, _ = st._head_columns(counts, rows, need - 1.0, _QUARTER)
        assert (0 if fewer is None else len(fewer)) == width - 128

    def test_cap_counts_the_input_batch(self, rng):
        """The budget is what the caller may pin, the padded-sparse input
        still on the device included."""
        batch = _zipf_problem(rng)
        n, k = batch.indices.shape
        idx, val = np.asarray(batch.indices), np.asarray(batch.values)
        import photon_ml_tpu.ops.sparse_tiled as st

        counts = np.bincount(idx[val != 0.0], minlength=batch.num_features)
        by_count = np.sort(counts)[::-1]
        # the tail's padding is the build's own figure for the tail that the
        # one block the fills choose leaves, in the form its cells will get
        block = np.argsort(-counts, kind="stable")[:128]
        _, padding = st._tail_form(idx, val != 0.0, batch.num_features, block)
        assert padding > 1.0
        need = 4.0 * n * 128 + 2 * 12 * padding * 2 * by_count[128:].sum()
        at_edge = tile_sparse_batch(batch, hbm_budget_bytes=need + 8 * n * k)
        assert at_edge.head_X is not None
        short = tile_sparse_batch(batch, hbm_budget_bytes=need + 8 * n * k - 1.0)
        assert short.head_X is None

    def test_no_budget_for_any_block_means_no_head(self):
        import photon_ml_tpu.ops.sparse_tiled as st

        assert st._head_columns(self._zipf_counts(), 1_354_798, 1.0, _QUARTER)[0] is None

    @pytest.mark.parametrize("case", ["uniform", "one_full_column", "empty"])
    def test_matrix_without_popular_columns_gets_no_head(self, case):
        import photon_ml_tpu.ops.sparse_tiled as st

        rows, d = 100_000, 8192
        counts = np.zeros(d, np.int64)
        if case == "uniform":
            counts[:] = rows * 32 // d  # a fill of 0.4% everywhere
        elif case == "one_full_column":
            # an intercept among 31 uniform nonzeros a row: its block pays,
            # but holds a thirtieth of the nonzeros, under the eighth
            counts[:] = rows * 31 // d
            counts[5] = rows
        assert st._head_columns(counts, rows, 1e12, _QUARTER)[0] is None

    @pytest.mark.parametrize(
        "kwargs", [dict(keep_empty_chunks=True), dict(fe_range=(0, 0, 4608, 1))]
    )
    def test_streamed_sharded_and_range_builds_never_get_a_head(
        self, rng, kwargs
    ):
        batch = _zipf_problem(rng, repeats=False)
        tiled = tile_sparse_batch(batch, **kwargs)
        assert tiled.head_X is None and tiled.head_cols is None
        (chunk,) = tiled.chunks
        _assert_chunks_equal(chunk, _parents_chunk(batch))
        # the head is asked for by handing the budget, and only for the
        # resident single-device layout
        with pytest.raises(ValueError, match="resident single-device"):
            tile_sparse_batch(batch, hbm_budget_bytes=8e9, **kwargs)

    def test_sharded_build_has_no_head(self, rng):
        from photon_ml_tpu.ops.sparse_tiled import tile_sparse_batch_sharded

        stacked, _ = tile_sparse_batch_sharded(_zipf_problem(rng, n=2048), 2)
        assert stacked.head_X is None and stacked.head_cols is None

    def test_cache_hit_returns_the_head_and_counts_its_bytes(self, rng):
        import dataclasses

        from photon_ml_tpu.ops import tile_cache

        tile_cache.clear()
        b1 = _zipf_problem(rng)
        tb1 = tile_cache.tiled_layout_for(b1, hbm_budget_bytes=8e9)
        assert tb1.head_X is not None
        b2 = dataclasses.replace(b1, labels=jnp.ones_like(b1.labels))
        tb2 = tile_cache.tiled_layout_for(b2, hbm_budget_bytes=8e9)
        s = tile_cache.stats()
        assert (s["hits"], s["misses"]) == (1, 1)
        assert tb2.chunks is tb1.chunks
        assert tb2.head_X is tb1.head_X and tb2.head_cols is tb1.head_cols
        streams = sum(
            int(a.nbytes) for c in tb1.chunks
            for arrays in (c.m_arrays, c.g_arrays) for a in arrays
        )
        assert s["bytes"] == streams + tb1.head_X.nbytes + tb1.head_cols.nbytes
        # another budget is another layout decision: a miss, not a stale hit
        tb3 = tile_cache.tiled_layout_for(b1, hbm_budget_bytes=1.0)
        assert tb3.head_X is None
        assert tile_cache.stats()["misses"] == 2
        # and so is no budget at all (the streamed builders' call)
        assert tile_cache.tiled_layout_for(b1).head_X is None
        assert tile_cache.stats()["misses"] == 3
        tile_cache.clear()

    @pytest.mark.parametrize("popular", [True, False])
    def test_counters_equal_the_counted_nonzeros(self, rng, popular):
        from photon_ml_tpu.obs.metrics import REGISTRY

        batch = _zipf_problem(rng) if popular else _sparse_problem(rng)
        REGISTRY.reset(prefix="tile_layout.")
        tiled = tile_sparse_batch(batch, hbm_budget_bytes=8e9)
        got = {
            k: v["value"]
            for k, v in REGISTRY.snapshot("tile_layout.")["counters"].items()
        }
        idx, val = np.asarray(batch.indices), np.asarray(batch.values)
        head_cols = [] if tiled.head_cols is None else np.asarray(tiled.head_cols)
        in_head = int((np.isin(idx, head_cols) & (val != 0.0)).sum())
        assert (in_head > 0) == popular
        # the tail's cells (one direction) and slots (both), as built
        tail = ~np.isin(idx, head_cols) & (val != 0.0)
        cell = (np.arange(idx.shape[0])[:, None] // SLAB) * (1 << 20) + idx // SLAB
        cells = np.unique(cell[tail])
        slots = sum(
            a[0].shape[0] * a[0].shape[-1]
            for c in tiled.chunks for a in (c.m_arrays, c.g_arrays)
        )
        assert got == {
            "tile_layout.head_columns": float(len(head_cols)),
            "tile_layout.head_nonzeros": float(in_head),
            "tile_layout.tail_nonzeros": float(np.count_nonzero(val) - in_head),
            "tile_layout.tail_cells": float(len(cells)),
            "tile_layout.tail_slots": float(slots),
            # one column chunk; the fine form holds nothing here (PR 40)
            "tile_layout.col_chunks": 1.0,
            "tile_layout.fine_cells": 0.0,
            "tile_layout.fine_nonzeros": 0.0,
            "tile_layout.fine_slots": 0.0,
        }

    @pytest.mark.parametrize("built", ["head", "no_head", "never"])
    def test_the_benchmarks_reader_gives_the_heads_share(self, rng, built):
        """``layout.head_nonzero_share`` reads the registry itself (the
        counters are set in set-up, before the harness's window), and finds
        nothing in a program that built no tile-COO layout."""
        from benchmark import harness
        from photon_ml_tpu.obs.metrics import REGISTRY

        read = harness.layer_reader("layout.head_nonzero_share")
        REGISTRY.reset(prefix="tile_layout.")
        if built == "never":
            assert read(None) is None
            return
        batch = _zipf_problem(rng) if built == "head" else _sparse_problem(rng)
        tiled = tile_sparse_batch(batch, hbm_budget_bytes=8e9)
        idx, val = np.asarray(batch.indices), np.asarray(batch.values)
        head_cols = [] if tiled.head_cols is None else np.asarray(tiled.head_cols)
        in_head = (np.isin(idx, head_cols) & (val != 0.0)).sum()
        assert read(None) == pytest.approx(100.0 * in_head / np.count_nonzero(val))
        assert (read(None) > 30.0) == (built == "head")

    @pytest.mark.parametrize("built", ["head", "no_head", "never"])
    def test_the_benchmarks_reader_gives_the_tails_pad_ratio(self, rng, built):
        """``layout.tail_pad_ratio`` divides the streams' slots by the
        nonzeros the build left to them, not by the whole matrix's, which
        is what ``layout.pad_ratio`` does and reads under 1 beside a head."""
        from types import SimpleNamespace

        from benchmark import harness
        from photon_ml_tpu.obs.metrics import REGISTRY

        read = harness.layer_reader("layout.tail_pad_ratio")
        whole = harness.layer_reader("layout.pad_ratio")
        REGISTRY.reset(prefix="tile_layout.")
        if built == "never":
            assert read(SimpleNamespace(counters={"layout.slots": 4096.0})) is None
            return
        batch = _zipf_problem(rng) if built == "head" else _sparse_problem(rng)
        tiled = tile_sparse_batch(batch, hbm_budget_bytes=8e9)
        # as benchmark/runners/fit.py counts them
        slots = float(sum(
            int(arrays[0].shape[0]) * int(arrays[0].shape[-1])
            for c in tiled.chunks for arrays in (c.m_arrays, c.g_arrays)
        ))
        stored = float(np.count_nonzero(np.asarray(batch.values)))
        obs = SimpleNamespace(
            counters={"layout.slots": slots, "layout.nonzeros": stored}
        )
        tail = REGISTRY.snapshot("tile_layout.")["counters"][
            "tile_layout.tail_nonzeros"]["value"]
        assert read(obs) == pytest.approx(slots / (2.0 * tail)) and read(obs) >= 1.0
        if built == "head":
            assert tail < stored and read(obs) > whole(obs)
        else:
            assert tail == stored and read(obs) == whole(obs)

    @pytest.mark.parametrize("built", [True, False])
    def test_the_run_report_renders_the_layout_counters(self, tmp_path, built):
        from photon_ml_tpu.obs.report import format_summary, summarize_run
        from photon_ml_tpu.obs.sink import TelemetrySink

        counters = {
            "tile_layout.head_columns": {"value": 384.0},
            "tile_layout.head_nonzeros": {"value": 3000.0},
            "tile_layout.tail_nonzeros": {"value": 1000.0},
        } if built else {}
        sink = TelemetrySink(str(tmp_path), run_id="HEAD", shard_index=None)
        sink.emit({"event": "run_start", "t": 1000.0, "schema_version": 1,
                   "run_id": "HEAD", "pid": 0, "process_index": 0, "knobs": {},
                   "fleet": {"process_count": 1}, "metrics_baseline": {}})
        sink.emit({"event": "run_end", "t": 1002.0, "run_id": "HEAD",
                   "metrics": {"counters": counters, "gauges": {},
                               "histograms": {}, "timers": {}}})
        sink.close()
        summary = summarize_run(sink.path)
        if not built:
            assert "tile_layout" not in summary
            assert "tile-layout" not in format_summary(summary)
            return
        assert summary["tile_layout"]["head_nonzero_share"] == 0.75
        assert "(75.0%) in a dense head of 384 columns" in format_summary(summary)

    def test_the_run_report_renders_the_kernel_calls(self, tmp_path):
        """``tile_apply.*`` beside ``col_chunks``: the kernel calls
        traced and the column chunks they ran as grid steps."""
        from photon_ml_tpu.obs.report import format_summary, summarize_run
        from photon_ml_tpu.obs.sink import TelemetrySink

        counters = {
            "tile_layout.tail_nonzeros": {"value": 1000.0},
            "tile_layout.col_chunks": {"value": 10.0},
            "tile_apply.calls": {"value": 2.0},
            "tile_apply.grid_chunks": {"value": 20.0},
        }
        sink = TelemetrySink(str(tmp_path), run_id="CALLS", shard_index=None)
        sink.emit({"event": "run_start", "t": 1000.0, "schema_version": 1,
                   "run_id": "CALLS", "pid": 0, "process_index": 0, "knobs": {},
                   "fleet": {"process_count": 1}, "metrics_baseline": {}})
        sink.emit({"event": "run_end", "t": 1002.0, "run_id": "CALLS",
                   "metrics": {"counters": counters, "gauges": {},
                               "histograms": {}, "timers": {}}})
        sink.close()
        summary = summarize_run(sink.path)
        til = summary["tile_layout"]
        assert (til["col_chunks"], til["kernel_calls"], til["grid_chunks"]) == (
            10.0, 2.0, 20.0
        )
        assert (
            "10 column chunks built; 2 kernel calls traced, running 20 "
            "column chunks as grid steps"
        ) in format_summary(summary)


def _cell_batch(rng, n, d, cells_nnz):
    """A batch whose cell (row slab i, column slab j) holds
    ``cells_nnz[i][j]`` nonzeros at random places, padded-sparse rows as
    wide as the fullest row needs."""
    rows, cols = [], []
    for i, per_col_slab in enumerate(cells_nnz):
        for j, count in enumerate(per_col_slab):
            at = rng.choice(SLAB * SLAB, size=count, replace=False)
            rows.append(i * SLAB + at // SLAB)
            cols.append(j * SLAB + at % SLAB)
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    keep = (rows < n) & (cols < d)
    rows, cols = rows[keep], cols[keep]
    k = int(np.bincount(rows, minlength=n).max())
    idx = np.zeros((n, k), np.int32)
    val = np.zeros((n, k), np.float32)
    order = np.argsort(rows, kind="stable")
    rows, cols = rows[order], cols[order]
    slot = np.arange(len(rows)) - np.searchsorted(rows, rows)
    idx[rows, slot] = cols
    val[rows, slot] = rng.normal(size=len(rows)).astype(np.float32)
    return SparseBatch(
        indices=jnp.asarray(idx), values=jnp.asarray(val),
        labels=jnp.zeros(n, jnp.float32), offsets=jnp.zeros(n, jnp.float32),
        weights=jnp.ones(n, jnp.float32), num_features=d,
    )


# cell occupancies (row slabs x column slabs) and the form the build gives
# them: "runs" where the cells are full, "sub" where they are near empty
_FORM_CASES = {
    # rcv1_fit's tail: 711 nonzeros a cell
    "full_cells": ([[711] * 4] * 2, "runs"),
    # glm_sparse_criteo's tail: 20 nonzeros a cell
    "near_empty_cells": ([[20] * 24] * 2, "sub"),
    # cells of one: the fine form's granules of 2 slots (PR 40)
    "one_nonzero_a_cell": ([[1] * 24] * 2, "fine"),
    # the middle row slab holds nothing: no segment writes it
    "empty_write_slab": ([[20] * 24, [0] * 24, [20] * 24], "sub"),
    # just under and just over a whole run: the sums decide, cell by cell
    "just_under_a_run": ([[250] * 8] * 2, "runs"),
    "just_over_a_run": ([[260] * 8] * 2, "sub"),
}


class TestSparseCellForm:
    """The tail's second form (PR 31): where cells are near empty the
    resident build pads a cell to a granule of a group's lanes, not to a run
    of 256 slots, and the kernel reads ``SUB_SLABS`` source slabs a group.
    The build chooses from the occupancy it observes; a matrix whose cells
    are full gets the streams it always had."""

    def _built(self, rng, monkeypatch, case, n=None, d=None):
        cells, form = _FORM_CASES[case]
        _retune(monkeypatch)
        n = n or len(cells) * SLAB
        d = d or len(cells[0]) * SLAB
        batch = _cell_batch(rng, n, d, cells)
        return batch, tile_sparse_batch(batch, hbm_budget_bytes=1e12), form

    @pytest.mark.parametrize("case", list(_FORM_CASES))
    def test_build_chooses_the_form_by_occupancy(self, rng, monkeypatch, case):
        import photon_ml_tpu.ops.sparse_tiled as st

        batch, tb, form = self._built(rng, monkeypatch, case)
        assert tb.head_X is None  # uniform columns: no head
        (chunk,) = tb.chunks
        for arrays in (chunk.m_arrays, chunk.g_arrays):
            groups, ids = arrays[0].shape[0], arrays[3].shape[0]
            if form == "sub":
                assert ids == groups * st.SUB_SLABS
            elif form == "fine":
                assert ids == groups * st.FINE_SLABS
            else:
                assert ids * st.GROUPS_PER_RUN == groups
        # without the budget (shards, streamed chunks): runs, whatever
        (plain,) = tile_sparse_batch(batch).chunks
        for arrays in (plain.m_arrays, plain.g_arrays):
            assert arrays[3].shape[0] * st.GROUPS_PER_RUN == arrays[0].shape[0]
        if form == "runs":
            _assert_chunks_equal(chunk, plain)
            _assert_chunks_equal(chunk, _parents_chunk(batch))

    @pytest.mark.parametrize("case", list(_FORM_CASES))
    def test_streams_decode_to_the_matrix(self, rng, monkeypatch, case):
        batch, tb, _ = self._built(rng, monkeypatch, case)
        (chunk,) = tb.chunks
        idx = np.asarray(batch.indices).astype(np.int64)
        val = np.asarray(batch.values).astype(np.float64)
        rows = np.repeat(np.arange(idx.shape[0]), idx.shape[1])
        width = max(chunk.n_pad, chunk.d_pad)
        want_keys, want = _entries(rows, idx.reshape(-1), val.reshape(-1), width)
        for side in ("m_arrays", "g_arrays"):
            write, read, vals = _walk_layout(getattr(chunk, side), "f32")
            r, c = (write, read) if side == "m_arrays" else (read, write)
            got_keys, got = _entries(r, c, vals, width)
            np.testing.assert_array_equal(got_keys, want_keys)
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)

    def test_padding_stays_near_the_nonzeros_where_cells_are_near_empty(
        self, rng, monkeypatch
    ):
        """20 nonzeros a cell: runs would pad 12.8x, granules of 16 lanes
        pad a cell to 32 slots at the most and a write slab to a segment."""
        import photon_ml_tpu.ops.sparse_tiled as st
        from photon_ml_tpu.obs.metrics import REGISTRY

        REGISTRY.reset(prefix="tile_layout.")
        batch, tb, _ = self._built(rng, monkeypatch, "near_empty_cells")
        counters = {
            k: v["value"]
            for k, v in REGISTRY.snapshot("tile_layout.")["counters"].items()
        }
        nnz = int(np.count_nonzero(np.asarray(batch.values)))
        assert counters["tile_layout.tail_nonzeros"] == nnz == 2 * 24 * 20
        assert counters["tile_layout.tail_cells"] == 2 * 24
        (chunk,) = tb.chunks
        slots = sum(
            a[0].shape[0] * st.GROUP for a in (chunk.m_arrays, chunk.g_arrays)
        )
        assert counters["tile_layout.tail_slots"] == slots
        # a cell of 20 pads to 32; the margins' 2 write slabs of 768 slots
        # fill a segment of 1,024 each, the gradient's 24 of 64 slots too
        assert slots == 2 * 1024 + 24 * 1024
        run_nnz = st.GROUP * st.GROUPS_PER_RUN
        assert st._cell_form(np.full(48, 20), st.GROUPS_PER_RUN, "f32") == (
            st.SUB_SLABS, 48 * 32
        )
        assert st._cell_form(np.full(48, 20), st.GROUPS_PER_RUN, "int8") == (
            0, 48 * run_nnz
        )

    @pytest.mark.kernel
    @pytest.mark.parametrize("case", list(_FORM_CASES))
    def test_kernels_match_the_walk_and_the_dense_matrix(
        self, rng, monkeypatch, case
    ):
        batch, tb, _ = self._built(rng, monkeypatch, case)
        self._check_against_walk_and_dense(batch, tb, rng)

    @pytest.mark.kernel
    def test_a_row_naming_a_column_twice_and_ragged_edges(self, rng, monkeypatch):
        """n and d off the slab grid, and rows that name a column twice
        (merged into one entry, so ``rmatvec_sq`` squares the sum)."""
        batch, _, form = self._built(
            rng, monkeypatch, "near_empty_cells", n=2 * SLAB - 77,
            d=24 * SLAB - 13,
        )
        idx = np.asarray(batch.indices).copy()
        val = np.asarray(batch.values).copy()
        wide = np.flatnonzero((val != 0).sum(axis=1) >= 2)[:50]
        idx[wide, 1] = idx[wide, 0]  # the second entry names the first's column
        import dataclasses

        batch = dataclasses.replace(
            batch, indices=jnp.asarray(idx), values=jnp.asarray(val)
        )
        tb = tile_sparse_batch(batch, hbm_budget_bytes=1e12)
        (chunk,) = tb.chunks
        assert chunk.m_arrays[3].shape[0] > chunk.m_arrays[0].shape[0]
        self._check_against_walk_and_dense(batch, tb, rng)

    @staticmethod
    def _check_against_walk_and_dense(batch, tb, rng):
        dense = densify(batch)
        by_dense = {
            "margins": dense.matvec, "gradient": dense.rmatvec,
            "gradient_sq": dense.rmatvec_sq,
        }
        for direction in _DIRECTIONS:
            got, by_walk, src = _apply_and_walk(tb, direction, rng)
            np.testing.assert_allclose(got, by_walk, rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(
                got, np.asarray(by_dense[direction](jnp.asarray(src))),
                rtol=1e-4, atol=1e-4,
            )

    def test_head_budget_counts_the_tail_at_its_own_padding(self, rng, monkeypatch):
        """The head's budget arithmetic takes the tail's padding from the
        cells the head would leave: popular columns over near-empty cells
        get a tail budgeted at the sparse-cell form's 1.6-2, not at runs'
        12.8, and not at a quarter whatever the cells hold."""
        import photon_ml_tpu.ops.sparse_tiled as st

        _retune(monkeypatch)
        n, d = 2 * SLAB, 24 * SLAB
        sparse = _cell_batch(rng, n, d, [[20] * 24] * 2)
        k = sparse.indices.shape[1]
        # 128 columns every row names, beside the sparse ones
        idx = np.concatenate(
            [np.asarray(sparse.indices),
             np.broadcast_to(np.arange(128, dtype=np.int32) * 7, (n, 128))], axis=1
        )
        val = np.concatenate(
            [np.asarray(sparse.values), np.ones((n, 128), np.float32)], axis=1
        )
        import dataclasses

        batch = dataclasses.replace(
            sparse, indices=jnp.asarray(idx), values=jnp.asarray(val)
        )
        live = val != 0.0
        head = np.arange(128) * 7
        _, padding = st._tail_form(idx, live, d, head)
        in_tail = np.ones(d, bool)
        in_tail[head] = False
        tail = int(np.count_nonzero(live & in_tail[idx]))
        assert 1.5 < padding < 2.1
        need = 4.0 * n * 128 + 2 * 12 * padding * 2 * tail
        at_edge = tile_sparse_batch(batch, hbm_budget_bytes=need + idx.nbytes + val.nbytes)
        assert at_edge.head_X is not None and at_edge.head_X.shape == (n, 128)
        short = tile_sparse_batch(
            batch, hbm_budget_bytes=need + idx.nbytes + val.nbytes - 1.0
        )
        assert short.head_X is None


# the fine form's edge shapes: cells of one nonzero (PR 40)
_FINE_EDGE_CELLS = {
    "cross_step_boundary": [[1] * 24] * 4,
    "single_segment_steps": [[1] * 24] * 4,
}


def _dense64(batch):
    """The batch's matrix in float64 numpy: a row's draws of a column add."""
    idx = np.asarray(batch.indices).astype(np.int64)
    val = np.asarray(batch.values).astype(np.float64)
    X = np.zeros((idx.shape[0], batch.num_features))
    np.add.at(X, (np.repeat(np.arange(idx.shape[0]), idx.shape[1]), idx.ravel()),
              val.ravel())
    return X


class TestFineForm:
    """The tail's third form (PR 40): where a cell holds one or two nonzeros,
    as at 2 x 10^7 columns and 36 a row, the resident build pads a cell to a
    granule of 2 slots and the kernel reads ``FINE_SLABS`` source slabs a
    group; the build's column chunks, past the 2^23-column cap, are one
    stacked chunk whose pass is one kernel call a direction."""

    N_COL_CHUNKS = 6  # more than four: past the old cap's chunk count

    def _wide(self, rng, monkeypatch):
        import photon_ml_tpu.ops.sparse_tiled as st

        # a small carve and 4-slab column chunks: six of them at 22 slabs
        _retune(monkeypatch, step=2, dma=2)
        monkeypatch.setattr(st, "_MAX_TABLE_COLS", 4 * SLAB)
        n, d = 3 * SLAB - 100, 5 * 4 * SLAB + 1500
        cells = rng.integers(0, 4, size=(3, -(-d // SLAB))).tolist()
        batch = _cell_batch(rng, n, d, cells)
        y = (rng.uniform(size=n) < 0.6).astype(np.float32)
        import dataclasses

        return dataclasses.replace(batch, labels=jnp.asarray(y))

    def test_the_build_stacks_more_than_four_chunks_in_the_fine_form(
        self, rng, monkeypatch
    ):
        import photon_ml_tpu.ops.sparse_tiled as st
        from photon_ml_tpu.obs.metrics import REGISTRY

        REGISTRY.reset(prefix="tile_layout.")
        batch = self._wide(rng, monkeypatch)
        tb = tile_sparse_batch(batch, hbm_budget_bytes=1e12)
        (chunk,) = tb.chunks
        assert chunk.stack == self.N_COL_CHUNKS
        assert tb.d_pad_total == self.N_COL_CHUNKS * chunk.d_pad >= batch.num_features
        for arrays in (chunk.m_arrays, chunk.g_arrays):
            packed, wslab, rslab, rrun, srun = (a.shape for a in arrays)
            # the chunks lie end to end, at a lone chunk's rank, each as
            # long as the others and whole DMA steps
            assert len(packed) == 3 and packed[0] % self.N_COL_CHUNKS == 0
            step_groups = st.GROUPS_PER_STEP * st.SEGMENTS_PER_DMA
            assert packed[0] // self.N_COL_CHUNKS % step_groups == 0
            assert rrun == (packed[0] * st.FINE_SLABS,)
            assert srun == (packed[0] // st.GROUPS_PER_RUN,)
            assert wslab == (packed[0] // st.GROUPS_PER_STEP,)
            assert rslab == packed[:1]
        counters = {
            k: v["value"]
            for k, v in REGISTRY.snapshot("tile_layout.")["counters"].items()
        }
        nnz = int(np.count_nonzero(np.asarray(batch.values)))
        assert counters["tile_layout.col_chunks"] == self.N_COL_CHUNKS
        assert counters["tile_layout.fine_nonzeros"] == nnz
        assert counters["tile_layout.tail_nonzeros"] == nnz
        assert counters["tile_layout.fine_cells"] == counters["tile_layout.tail_cells"]
        assert counters["tile_layout.fine_slots"] == counters["tile_layout.tail_slots"]
        # without the budget (shards, streamed chunks): runs, stacked too
        (plain,) = tile_sparse_batch(batch).chunks
        assert plain.stack == self.N_COL_CHUNKS
        for arrays in (plain.m_arrays, plain.g_arrays):
            assert arrays[3].ndim == 1
            assert arrays[3].shape[0] * st.GROUPS_PER_RUN == arrays[0].shape[0]

    @pytest.mark.kernel
    @pytest.mark.parametrize("direction", _DIRECTIONS)
    def test_kernels_match_a_float64_reference_across_the_chunks(
        self, rng, monkeypatch, direction
    ):
        batch = self._wide(rng, monkeypatch)
        tb = tile_sparse_batch(batch, hbm_budget_bytes=1e12)
        X = _dense64(batch)
        n, d = X.shape
        if direction == "margins":
            src = rng.normal(size=d).astype(np.float32)
            got, want = tb.matvec(jnp.asarray(src)), X @ src.astype(np.float64)
        else:
            src = rng.normal(size=n).astype(np.float32)
            sq = direction == "gradient_sq"
            got = (tb.rmatvec_sq if sq else tb.rmatvec)(jnp.asarray(src))
            want = (X * X if sq else X).T @ src.astype(np.float64)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)

    @pytest.mark.kernel
    def test_a_fit_reads_the_float64_loss_and_gradient(self, rng, monkeypatch):
        from photon_ml_tpu.config import OptimizerConfig
        from photon_ml_tpu.supervised.training import train_glm
        from photon_ml_tpu.types import OptimizerType, TaskType

        batch = self._wide(rng, monkeypatch)
        tb = tile_sparse_batch(batch, hbm_budget_bytes=1e12)
        res = train_glm(
            tb, TaskType.LOGISTIC_REGRESSION,
            optimizer_config=OptimizerConfig(
                optimizer_type=OptimizerType.LBFGS, max_iterations=8,
                tolerance=1e-9,
            ),
            regularization_weights=[0.5],
        ).trackers[0.5]
        X, y = _dense64(batch), np.asarray(batch.labels, np.float64)
        w = np.asarray(res.w, np.float64)
        m = X @ w
        loss = np.sum(np.logaddexp(0.0, -(2 * y - 1) * m)) + 0.25 * w @ w
        grad = X.T @ (0.5 * (1 + np.tanh(0.5 * m)) - y) + 0.5 * w
        grad0 = X.T @ (0.5 - y)
        np.testing.assert_allclose(float(res.value), loss, rtol=1e-6)
        # the fit went downhill to a small gradient on every chunk's columns
        assert np.linalg.norm(grad) < 0.05 * np.linalg.norm(grad0)
        for c in range(self.N_COL_CHUNKS):
            at = slice(c * 4 * SLAB, (c + 1) * 4 * SLAB)
            assert np.any(w[at] != 0)

    @pytest.mark.kernel
    def test_the_chunks_share_one_traced_kernel(self, rng, monkeypatch):
        import jax

        import photon_ml_tpu.ops.sparse_tiled as st

        batch = self._wide(rng, monkeypatch)
        tb = tile_sparse_batch(batch, hbm_budget_bytes=1e12)
        traced = []
        kernel = st._tile_kernel_seg
        monkeypatch.setattr(
            st, "_tile_kernel_seg",
            lambda *a, **kw: traced.append(kw["sub_slabs"]) or kernel(*a, **kw),
        )
        st._tiled_apply_jit.clear_cache()
        try:
            w = jnp.ones((batch.num_features,), jnp.float32)
            r = jnp.ones((batch.num_rows,), jnp.float32)
            jax.block_until_ready(jax.jit(lambda b, w: b.matvec(w))(tb, w))
            jax.block_until_ready(jax.jit(lambda b, r: b.rmatvec(r))(tb, r))
        finally:
            st._tiled_apply_jit.clear_cache()
        # six chunks, two directions: one trace a direction
        assert traced == [st.FINE_SLABS, st.FINE_SLABS]

    @pytest.mark.kernel
    @pytest.mark.parametrize("direction", _DIRECTIONS)
    @pytest.mark.parametrize("shape", list(_FINE_EDGE_CELLS))
    def test_edge_shape_in_the_fine_form(self, rng, monkeypatch, shape, direction):
        import photon_ml_tpu.ops.sparse_tiled as st

        step, dma, run = _EDGE_SHAPES[shape][:3]
        _retune(monkeypatch, step, dma, run)
        cells = _FINE_EDGE_CELLS[shape]
        batch = _cell_batch(rng, len(cells) * SLAB, len(cells[0]) * SLAB, cells)
        tb = tile_sparse_batch(batch, hbm_budget_bytes=1e12)
        (chunk,) = tb.chunks
        for arrays in (chunk.m_arrays, chunk.g_arrays):
            assert arrays[3].shape[0] == arrays[0].shape[0] * st.FINE_SLABS
        assert _direction_steps(tb, direction) >= 2
        got, want, _ = _apply_and_walk(tb, direction, rng)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    @pytest.mark.kernel
    def test_streamed_and_sharded_builds_stack_their_column_chunks(
        self, rng, monkeypatch
    ):
        """Without the budget (streamed chunks, shards) the run form's
        column chunks stack as the resident build's do: padded to common
        group counts across the halves, each half reads its float64 rows,
        and the sharded build is those halves on a leading device axis."""
        from dataclasses import replace

        import jax

        import photon_ml_tpu.ops.sparse_tiled as st

        batch = self._wide(rng, monkeypatch)
        half = batch.num_rows // 2
        halves = [
            jax.tree.map(lambda a: a[lo:lo + half], batch) for lo in (0, half)
        ]
        tbs = [tile_sparse_batch(h, keep_empty_chunks=True) for h in halves]
        padded = st.pad_chunks_to_common_groups(tbs)
        w = rng.normal(size=batch.num_features).astype(np.float32)
        for tb, (chunk,), h in zip(tbs, zip(*padded), halves):
            assert chunk.stack == self.N_COL_CHUNKS
            got = replace(tb, chunks=(chunk,)).matvec(jnp.asarray(w))
            want = _dense64(h) @ w.astype(np.float64)
            np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
        sharded, _ = st.tile_sparse_batch_sharded(batch, 2)
        (stacked,) = sharded.chunks
        assert stacked.stack == self.N_COL_CHUNKS
        for i, (chunk,) in enumerate(zip(*padded)):
            # a device's streams: its chunks end to end, padded alike
            for side in ("m_arrays", "g_arrays"):
                packed = getattr(stacked, side)[0]
                assert packed.ndim == 4 and packed.shape[0] == 2
                assert packed.shape[1] % self.N_COL_CHUNKS == 0
            for side in ("m_arrays", "g_arrays"):
                for a, b in zip(getattr(stacked, side), getattr(chunk, side)):
                    np.testing.assert_array_equal(np.asarray(a)[i], np.asarray(b))


# nonzeros a non-empty cell holds, as the accepted cells' tails have them,
# and the form each has had: the fine form's price must leave them there
_KDDA_CELL_SHARES = [0.507, 0.274, 0.116, 0.046, 0.020, 0.011, 0.006, 0.020]


@pytest.mark.parametrize("cell, form", [
    ("rcv1_fit", "runs"), ("criteo_fit", "sub"), ("kdda_fit", "fine"),
])
def test_cell_form_keeps_the_accepted_cells_forms(cell, form):
    """``_cell_form`` on occupancies like the three sparse cells' tails:
    rcv1's full cells keep the run form, criteo's 20 a cell the sparse-cell
    form, and kdda's 1-2 (the tail's histogram at 2 x 10^7 columns, 36 a
    row, Zipf(1.0), 128 head columns: PERF.md PR 40) take the fine form."""
    import photon_ml_tpu.ops.sparse_tiled as st

    rng = np.random.default_rng(0)
    if cell == "rcv1_fit":
        counts = rng.poisson(710.3, 20_000)
    elif cell == "criteo_fit":
        counts = rng.poisson(20.43, 20_000)
    else:
        counts = rng.choice(np.arange(1, 9), 20_000, p=_KDDA_CELL_SHARES)
    counts = counts[counts > 0]
    sub, slots = st._cell_form(counts, st.GROUPS_PER_RUN, "f32")
    assert sub == {"runs": 0, "sub": st.SUB_SLABS, "fine": st.FINE_SLABS}[form]
    granule = st.GROUP // sub if sub else st.GROUP * st.GROUPS_PER_RUN
    assert slots == int((-(-counts // granule) * granule).sum())
    if form == "fine":
        assert slots / counts.sum() < 1.5  # 16-slot granules: 8 times over
    # the int8 rung keeps the run form whatever the cells hold
    assert st._cell_form(counts, st.GROUPS_PER_RUN, "int8")[0] == 0
