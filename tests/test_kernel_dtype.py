"""The PHOTON_KERNEL_DTYPE precision ladder (f32 | int8).

Parity contract (ROADMAP "Mixed-precision sparse-tiled kernels"): the f32
rung is the BITWISE anchor — knob unset, knob=f32 (module global) and
env=f32 must reproduce the pre-ladder results exactly, asserted with
``assert_array_equal`` across all four streamed consumers. The reduced
rung (int8) is NOT bitwise: it gates on model quality (AUC / loss
deltas within the tolerances documented in README's precision-ladder
section) and on kernel-level numerical agreement with the XLA reference.

Host-side tests (knob parsing, transfer packing, raw-chunk consumers) are
unmarked; tests that trace Pallas kernels in interpret mode carry the
``kernel`` marker and ride the conftest retuned-down-constants guard.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

import photon_ml_tpu.ops.sparse_tiled as st
from photon_ml_tpu.config import OptimizerConfig
from photon_ml_tpu.ops import prefetch
from photon_ml_tpu.ops.batch import SparseBatch, densify
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.streaming import (
    StreamingGLMObjective,
    dense_chunks,
    sparse_chunks,
    stream_scores,
)
from photon_ml_tpu.types import TaskType

LOSS = loss_for_task(TaskType.LOGISTIC_REGRESSION)

# Documented quality-parity tolerances (README precision-ladder section):
# train-to-convergence deltas against the f32 anchor on a small GLM fit.
INT8_AUC_TOL = 0.01
INT8_LOSS_RTOL = 5e-3


class TestKnobParsing:
    def test_default_is_f32(self, monkeypatch):
        monkeypatch.delenv("PHOTON_KERNEL_DTYPE", raising=False)
        monkeypatch.setattr(st, "KERNEL_DTYPE", "f32")
        assert st.kernel_dtype() == "f32"

    def test_env_wins_and_reads_at_call_time(self, monkeypatch):
        monkeypatch.setattr(st, "KERNEL_DTYPE", "f32")
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "int8")
        assert st.kernel_dtype() == "int8"
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "f32")
        assert st.kernel_dtype() == "f32"
        monkeypatch.delenv("PHOTON_KERNEL_DTYPE")
        monkeypatch.setattr(st, "KERNEL_DTYPE", "int8")
        assert st.kernel_dtype() == "int8"

    @pytest.mark.parametrize(
        "bad", ["fp16", "float32", "8", "", " ", "f64", "bf16"]
    )
    def test_unknown_rung_rejected_loudly(self, monkeypatch, bad):
        # strict parse, like the sibling PHOTON_RE_* strict-int knobs: the
        # error must NAME the valid rungs (bf16 was a rung until PR 29)
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", bad or "x")
        with pytest.raises(ValueError, match="f32, int8"):
            st.kernel_dtype()

    def test_case_and_whitespace_normalized(self, monkeypatch):
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", " INT8 ")
        assert st.kernel_dtype() == "int8"


class TestTransferPacking:
    """Raw (un-tiled) streamed chunks pack their feature arrays at the
    ladder's transfer dtype — bf16 under the reduced rung, identity on
    f32 — while labels/offsets/weights always stay f32."""

    def test_f32_rung_is_identity(self, monkeypatch):
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "f32")
        tree = {"values": np.ones((4, 2), np.float32),
                "labels": np.zeros(4, np.float32)}
        assert prefetch.pack_host_chunk(tree) is tree

    @pytest.mark.parametrize("rung", ["int8"])
    def test_reduced_rungs_pack_feature_arrays_only(self, monkeypatch, rung):
        import ml_dtypes

        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", rung)
        vals = np.linspace(-1, 1, 8, dtype=np.float32).reshape(4, 2)
        tree = {
            "values": vals,
            "X": vals * 2,
            "indices": np.zeros((4, 2), np.int32),
            "labels": np.zeros(4, np.float32),
            "offsets": np.zeros(4, np.float32),
            "weights": np.ones(4, np.float32),
        }
        out = prefetch.pack_host_chunk(tree)
        assert out["values"].dtype == ml_dtypes.bfloat16
        assert out["X"].dtype == ml_dtypes.bfloat16
        assert out["values"].nbytes == vals.nbytes // 2
        for k in ("indices", "labels", "offsets", "weights"):
            assert out[k] is tree[k]

    def test_cached_put_packs_and_keys_on_rung(self, monkeypatch):
        import ml_dtypes

        prefetch.clear_cache()
        vals = np.arange(64, dtype=np.float32)
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "int8")
        d1 = prefetch.cached_device_put({"values": vals})
        assert d1["values"].dtype == jnp.bfloat16
        # repeat pass over the SAME host storage: device hit, no re-pack
        d2 = prefetch.cached_device_put({"values": vals})
        assert d2["values"] is d1["values"]
        # toggling the rung must MISS (a bf16-packed entry never serves f32)
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "f32")
        d3 = prefetch.cached_device_put({"values": vals})
        assert d3["values"].dtype == jnp.float32
        s = prefetch.cache_stats()
        assert s["device_hits"] == 1 and s["misses"] == 2
        np.testing.assert_array_equal(
            np.asarray(d1["values"]).astype(np.float32),
            vals.astype(ml_dtypes.bfloat16).astype(np.float32),
        )
        prefetch.clear_cache()


def _sparse_fit_problem(rng, n=1024, d=2048, k=4):
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    w_true = (rng.normal(size=d) * 0.5).astype(np.float32)
    m = (val * w_true[idx]).sum(axis=1)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-m))).astype(np.float32)
    return idx, val, y


class TestRawConsumerF32Parity:
    """Knob-unset vs knob=f32 over the four streamed consumers on RAW
    (un-tiled) chunks: the f32 rung must be bitwise inert end to end —
    pack_host_chunk identity, unchanged cache keys, unchanged math.
    Host-side only (no Pallas trace), so unmarked."""

    def _objective_outputs(self, chunks, d, w, num_rows):
        sobj = StreamingGLMObjective(
            chunks, LOSS, num_features=d, l2_weight=0.7,
            intercept_index=d - 1,
        )
        v, g = sobj.value_and_grad(w)
        return (
            float(v),
            np.asarray(g),
            np.asarray(sobj.hvp(w, w + 0.5)),
            np.asarray(sobj.hessian_diag(w)),
            sobj.stream_scores(np.asarray(w), num_rows=num_rows),
            stream_scores(chunks, np.asarray(w), num_rows=num_rows),
        )

    @pytest.mark.parametrize("depth", ["0", "2"])
    def test_streamed_objective_and_scorers_bitwise(
        self, rng, monkeypatch, depth
    ):
        n, d, k = 300, 50, 5
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        chunks = sparse_chunks(idx, val, y, chunk_rows=97)
        w = jnp.asarray(rng.normal(size=d), jnp.float32)
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", depth)
        monkeypatch.delenv("PHOTON_KERNEL_DTYPE", raising=False)
        ref = self._objective_outputs(chunks, d, w, n)
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "f32")
        got = self._objective_outputs(chunks, d, w, n)
        for a, b in zip(got, ref):
            if isinstance(a, float):
                assert a == b
            else:
                np.testing.assert_array_equal(a, b)

    def test_game_streamed_fit_bitwise(self, monkeypatch):
        from photon_ml_tpu.config import (
            FixedEffectCoordinateConfig,
            GameTrainingConfig,
            OptimizationConfig,
            RandomEffectCoordinateConfig,
            RegularizationContext,
        )
        from photon_ml_tpu.game.streaming import (
            StreamedGameData,
            StreamedGameTrainer,
        )
        from photon_ml_tpu.types import RegularizationType

        def fit():
            rng = np.random.default_rng(11)
            n, d, dr, E = 220, 5, 3, 6
            w_fixed = (rng.normal(size=d) * 0.6).astype(np.float32)
            W_re = (rng.normal(size=(E, dr)) * 0.6).astype(np.float32)
            X = rng.normal(size=(n, d)).astype(np.float32)
            Xr = rng.normal(size=(n, dr)).astype(np.float32)
            ids = rng.integers(0, E, size=n).astype(np.int32)
            margin = X @ w_fixed + np.sum(W_re[ids] * Xr, axis=1)
            y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(
                np.float32
            )
            opt = OptimizationConfig(
                optimizer=OptimizerConfig(max_iterations=25, tolerance=1e-8),
                regularization=RegularizationContext(RegularizationType.L2),
                regularization_weight=1.0,
            )
            cfg = GameTrainingConfig(
                task_type=TaskType.LOGISTIC_REGRESSION,
                coordinate_update_sequence=("fixed", "user"),
                coordinate_descent_iterations=1,
                fixed_effect_coordinates={
                    "fixed": FixedEffectCoordinateConfig(
                        feature_shard_id="g", optimization=opt
                    )
                },
                random_effect_coordinates={
                    "user": RandomEffectCoordinateConfig(
                        feature_shard_id="r", random_effect_type="uid",
                        optimization=opt,
                    )
                },
            )
            data = StreamedGameData(
                labels=y, features={"g": X, "r": Xr}, id_tags={"uid": ids}
            )
            model, _ = StreamedGameTrainer(cfg, chunk_rows=64).fit(data)
            return model

        monkeypatch.delenv("PHOTON_KERNEL_DTYPE", raising=False)
        ref = fit()
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "f32")
        got = fit()
        np.testing.assert_array_equal(
            np.asarray(got.models["fixed"].model.coefficients.means),
            np.asarray(ref.models["fixed"].model.coefficients.means),
        )
        np.testing.assert_array_equal(
            np.asarray(got.models["user"].coefficients),
            np.asarray(ref.models["user"].coefficients),
        )

    def test_cv_folds_bitwise(self, rng, monkeypatch):
        from photon_ml_tpu.ops.batch import DenseBatch
        from photon_ml_tpu.supervised.cross_validation import (
            cross_validate_glm,
        )

        d = 6
        w_true = (rng.normal(size=d) * 0.8).astype(np.float32)
        X = rng.normal(size=(200, d)).astype(np.float32)
        y = (rng.uniform(size=200) < 1 / (1 + np.exp(-(X @ w_true)))).astype(
            np.float32
        )
        batch = DenseBatch(
            X=jnp.asarray(X), labels=jnp.asarray(y),
            offsets=jnp.zeros((200,), jnp.float32),
            weights=jnp.ones((200,), jnp.float32),
        )

        def run():
            return cross_validate_glm(
                batch, TaskType.LOGISTIC_REGRESSION, k=4,
                regularization_weights=[0.5, 5.0],
                optimizer_config=OptimizerConfig(
                    max_iterations=30, tolerance=1e-8
                ),
                seed=3,
            )

        monkeypatch.delenv("PHOTON_KERNEL_DTYPE", raising=False)
        ref = run()
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "f32")
        got = run()
        assert got.best_weight == ref.best_weight
        for lam in (0.5, 5.0):
            assert got.metric_values[lam] == ref.metric_values[lam]
        np.testing.assert_array_equal(
            np.asarray(got.final.models[got.best_weight].coefficients.means),
            np.asarray(ref.final.models[ref.best_weight].coefficients.means),
        )

    @pytest.mark.parametrize("rung", ["int8"])
    def test_reduced_rung_raw_sparse_objective_runs_close(
        self, rng, monkeypatch, rung
    ):
        """Raw SPARSE chunks under a reduced rung: bf16 values flow
        through the XLA chunk objective (gather path) end to end, with
        value/gradient close to the f32 pass — the un-tiled consumers'
        smoke for the transfer packing."""
        n, d, k = 300, 50, 5
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        chunks = sparse_chunks(idx, val, y, chunk_rows=97)
        w = jnp.asarray(rng.normal(size=d), jnp.float32)
        outs = {}
        for dt in ("f32", rung):
            prefetch.clear_cache()
            monkeypatch.setenv("PHOTON_KERNEL_DTYPE", dt)
            sobj = StreamingGLMObjective(
                chunks, LOSS, num_features=d, l2_weight=0.7
            )
            v, g = sobj.value_and_grad(w)
            outs[dt] = (float(v), np.asarray(g))
        assert outs[rung][0] == pytest.approx(outs["f32"][0], rel=2e-2)
        np.testing.assert_allclose(
            outs[rung][1], outs["f32"][1],
            atol=2e-2 * max(np.max(np.abs(outs["f32"][1])), 1.0),
        )
        prefetch.clear_cache()

    def test_reduced_rung_changes_raw_transfer_bytes(self, rng, monkeypatch):
        """The satellite accounting claim on a CPU-measurable surface: a
        reduced-rung pass through the chunk cache moves half the feature
        bytes and pins half the device bytes of an f32 pass."""
        from photon_ml_tpu.obs.metrics import REGISTRY

        prefetch.clear_cache()
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "2")
        X, y = (rng.normal(size=(256, 8)).astype(np.float32),
                (rng.uniform(size=256) < 0.5).astype(np.float32))
        chunks = dense_chunks(X, y, chunk_rows=64)
        w = jnp.zeros(8, jnp.float32)
        traffic = {}
        for rung in ("f32", "int8"):
            prefetch.clear_cache()
            REGISTRY.reset("prefetch.cache.")
            monkeypatch.setenv("PHOTON_KERNEL_DTYPE", rung)
            sobj = StreamingGLMObjective(
                chunks, LOSS, num_features=8, l2_weight=0.5
            )
            sobj.value_and_grad(w)
            snap = REGISTRY.snapshot()["counters"]
            traffic[rung] = (
                snap["prefetch.cache.miss_bytes"]["value"],
                prefetch.cache_stats()["device_bytes"],
            )
        f32_X = X.nbytes  # the packable share of the traffic
        assert traffic["f32"][0] - traffic["int8"][0] == f32_X // 2
        assert traffic["f32"][1] - traffic["int8"][1] == f32_X // 2
        prefetch.clear_cache()


@pytest.mark.kernel
class TestTiledLadderParity:
    """The tile-COO kernels across the ladder (interpret mode, conftest
    retuned-down constants): f32 knob-on/off BITWISE, the reduced rung
    within kernel-level numerical tolerance of the XLA reference."""

    # problem sizes retuned DOWN for the tier-1 budget (interpret-mode
    # trace cost scales with nnz; the ladder changes decode, not carve,
    # so small streams exercise every code path — multi-slab/multi-cell
    # edge coverage lives in test_sparse_tiled)
    def _batch(self, rng, n=700, d=1037, k=3):
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        val[rng.uniform(size=(n, k)) < 0.1] = 0.0
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        return SparseBatch(
            indices=jnp.asarray(idx), values=jnp.asarray(val),
            labels=jnp.asarray(y),
            offsets=jnp.zeros(n, jnp.float32),
            weights=jnp.ones(n, jnp.float32),
            num_features=d,
        )

    def _apply_all(self, tb, w, r):
        return (
            np.asarray(tb.matvec(w)),
            np.asarray(tb.rmatvec(r)),
            np.asarray(tb.rmatvec_sq(r)),
        )

    def test_f32_knob_bitwise_inert(self, rng, monkeypatch):
        # bitwise identity is size-independent: the smallest multi-slab
        # stream keeps the kernel honest at a fraction of the trace cost
        batch = self._batch(rng, n=384)
        w = jnp.asarray(rng.normal(size=batch.num_features).astype(np.float32))
        r = jnp.asarray(rng.normal(size=batch.num_rows).astype(np.float32))
        monkeypatch.delenv("PHOTON_KERNEL_DTYPE", raising=False)
        ref = self._apply_all(st.tile_sparse_batch(batch), w, r)
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "f32")
        got = self._apply_all(st.tile_sparse_batch(batch), w, r)
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("rung,rtol", [("int8", 6e-2)])
    def test_reduced_rungs_match_xla_reference(
        self, rng, monkeypatch, rung, rtol
    ):
        batch = self._batch(rng)
        w = jnp.asarray(rng.normal(size=batch.num_features).astype(np.float32))
        r = jnp.asarray(rng.normal(size=batch.num_rows).astype(np.float32))
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", rung)
        tb = st.tile_sparse_batch(batch)
        # the packed streams really narrowed (the bytes-moved claim)
        for c in tb.chunks:
            assert c.m_arrays[0].dtype.itemsize == 4
            assert c.m_arrays[0].shape[1] == 1
        got = self._apply_all(tb, w, r)
        ref = (
            np.asarray(batch.matvec(w)),
            np.asarray(batch.rmatvec(r)),
            # of the matrix's entry: the build merges a row's repeated draws
            np.asarray(densify(batch).rmatvec_sq(r)),
        )
        for a, b in zip(got, ref):
            scale = np.max(np.abs(b)) or 1.0
            np.testing.assert_allclose(a / scale, b / scale, atol=rtol)

    def test_int8_per_cell_scales_exact_for_uniform_cells(self, rng):
        """A batch whose every cell holds values from {-s, 0, s} must
        quantize EXACTLY (q in {-127, 0, 127}, per-cell scale s/127) —
        the int8 rung's round-trip identity case."""
        n, d, k = SLAB_ROWS, 2048, 3
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        signs = rng.choice([-1.0, 0.0, 1.0], size=(n, k))
        val = (signs * 0.375).astype(np.float32)
        batch = SparseBatch(
            indices=jnp.asarray(idx), values=jnp.asarray(val),
            labels=jnp.zeros(n, jnp.float32),
            offsets=jnp.zeros(n, jnp.float32),
            weights=jnp.ones(n, jnp.float32),
            num_features=d,
        )
        lay = st.build_write_major_layout(
            np.repeat(np.arange(n), k)[val.reshape(-1) != 0],
            idx.reshape(-1)[val.reshape(-1) != 0],
            val.reshape(-1)[val.reshape(-1) != 0],
            st.SLAB * ((n + st.SLAB - 1) // st.SLAB),
            st.SLAB * ((d + st.SLAB - 1) // st.SLAB),
            groups_per_step=8, groups_per_run=2, storage="int8",
        )
        q = (lay.packed.reshape(-1) >> 20) & 255
        q = q - ((q & 128) << 1)
        assert set(np.unique(q)) <= {-127, 0, 127}
        live = lay.srun[lay.srun != 1.0]
        np.testing.assert_allclose(live, 0.375 / 127.0, rtol=1e-6)

    def test_dtype_toggle_misses_layout_cache(self, rng, monkeypatch):
        from photon_ml_tpu.ops import tile_cache

        tile_cache.clear()
        batch = self._batch(rng)
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "f32")
        tile_cache.tiled_layout_for(batch)
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "int8")
        tb = tile_cache.tiled_layout_for(batch)
        s = tile_cache.stats()
        assert (s["hits"], s["misses"]) == (0, 2)
        assert tb.chunks[0].m_arrays[0].shape[1] == 1
        # and back: the f32 entry is still there — a HIT, never a stale mix
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "f32")
        tb32 = tile_cache.tiled_layout_for(batch)
        assert tile_cache.stats()["hits"] == 1
        assert tb32.chunks[0].m_arrays[0].shape[1] == 3
        tile_cache.clear()

    def test_tiled_streamed_consumer_f32_bitwise_and_reduced_quality(
        self, rng, monkeypatch
    ):
        """The tiled STREAMED consumer across the ladder: f32 knob
        bitwise-inert on value/grad/scores; int8 runs end to end with
        scores close to the XLA path."""
        n, d, k = 1024, 2048, 3
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        chunks = sparse_chunks(idx, val, y, chunk_rows=512)
        w = jnp.asarray(rng.normal(size=d), jnp.float32)

        def outputs():
            obj = StreamingGLMObjective(
                chunks, LOSS, num_features=d, l2_weight=0.4, tile_sparse=True
            )
            v, g = obj.value_and_grad(w)
            return (
                float(v), np.asarray(g),
                obj.stream_scores(np.asarray(w), num_rows=n),
            )

        monkeypatch.delenv("PHOTON_KERNEL_DTYPE", raising=False)
        ref = outputs()
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "f32")
        got = outputs()
        assert got[0] == ref[0]
        np.testing.assert_array_equal(got[1], ref[1])
        np.testing.assert_array_equal(got[2], ref[2])
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "int8")
        red = outputs()
        scale = np.max(np.abs(ref[2])) or 1.0
        np.testing.assert_allclose(red[2] / scale, ref[2] / scale, atol=6e-2)


SLAB_ROWS = 1024  # SLAB-sized row count for the int8 exactness test


@pytest.mark.kernel
class TestLadderQualityGates:
    """Small GLM fits to convergence on the reduced rung: AUC/loss deltas
    against the f32 anchor stay within the tolerances documented in
    README's precision-ladder section."""

    def _fit(self, rng_seed=17):
        from photon_ml_tpu.evaluation.evaluators import auc_roc
        from photon_ml_tpu.ops.glm import make_objective
        from photon_ml_tpu.optim import lbfgs_minimize

        rng = np.random.default_rng(rng_seed)
        d = 1037  # retuned-down fit shape (tier-1 budget): the gate is
        # about storage error at convergence, not scale
        # n=640 keeps the int8 delta 25x inside the documented
        # tolerance (measured: dAUC ~3.8e-4 vs 1e-2)
        idx, val, y = _sparse_fit_problem(rng, n=640, d=d, k=3)
        batch = SparseBatch(
            indices=jnp.asarray(idx), values=jnp.asarray(val),
            labels=jnp.asarray(y),
            offsets=jnp.zeros(len(y), jnp.float32),
            weights=jnp.ones(len(y), jnp.float32),
            num_features=d,
        )
        tb = st.tile_sparse_batch(batch)
        obj = make_objective(tb, LOSS, l2_weight=1.0)
        res = lbfgs_minimize(
            obj, jnp.zeros(d, jnp.float32),
            OptimizerConfig(max_iterations=6, tolerance=1e-8),
        )
        auc = float(auc_roc(batch.matvec(res.w), batch.labels))
        return auc, float(res.value)

    def test_int8_quality_within_documented_tolerances(self, monkeypatch):
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "f32")
        auc32, loss32 = self._fit()
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "int8")
        auc, loss = self._fit()
        assert abs(auc - auc32) <= INT8_AUC_TOL, (
            f"int8: AUC delta {auc - auc32:+.6f} exceeds {INT8_AUC_TOL}"
        )
        assert abs(loss - loss32) <= INT8_LOSS_RTOL * abs(loss32), (
            f"int8: loss delta {loss - loss32:+.6f} exceeds "
            f"{INT8_LOSS_RTOL:.0e} relative"
        )
