"""The host-ingest prefetch pipeline + device-resident chunk cache.

Determinism contract: prefetch reorders PREPARATION only — every kernel
call and accumulation stays on the consumer thread in item order — so all
outputs must be BITWISE identical (assert_array_equal / ``==``, never
allclose) to ``PHOTON_PREFETCH_DEPTH=0``, which restores the synchronous
schedule bit-for-bit. Covered across all four streamed consumers: the
chunk objective (value/grad/HVP/diag streams), the module + objective
scorers, the streamed GAME trainer (bucket ingest + visit scoring), and
CV fold ingest. Pure host-side tests stay unmarked; the one tile-COO
consumer check traces Pallas interpret kernels and carries the ``kernel``
marker on retuned-down constants.
"""

from __future__ import annotations

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.config import OptimizerConfig
from photon_ml_tpu.ops import prefetch
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.streaming import (
    StreamingGLMObjective,
    dense_chunks,
    sparse_chunks,
    stream_scores,
)
from photon_ml_tpu.types import TaskType

LOSS = loss_for_task(TaskType.LOGISTIC_REGRESSION)


@pytest.fixture(autouse=True)
def _clean_cache():
    prefetch.clear_cache()
    yield
    prefetch.clear_cache()


def _dense_problem(rng, n=500, d=8):
    X = rng.normal(size=(n, d)).astype(np.float32)
    X[:, d - 1] = 1.0
    w_true = (rng.normal(size=d) * 0.5).astype(np.float32)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-X @ w_true))).astype(np.float32)
    return X, y


class TestPrefetchIter:
    def test_yields_in_order_any_depth(self):
        for depth in (0, 1, 2, 7, 50):
            out = list(prefetch.prefetch_iter(9, lambda i: i * i, depth))
            assert out == [i * i for i in range(9)], depth

    def test_depth_exceeding_item_count(self):
        # depth > num_items must neither hang nor over-submit
        out = list(prefetch.prefetch_iter(3, lambda i: i, depth=10))
        assert out == [0, 1, 2]

    def test_single_item_and_empty(self):
        assert list(prefetch.prefetch_iter(1, lambda i: "x", depth=4)) == ["x"]
        assert list(prefetch.prefetch_iter(0, lambda i: "x", depth=4)) == []

    def test_depth_zero_never_touches_threads(self):
        main = threading.get_ident()
        seen = []
        list(prefetch.prefetch_iter(
            4, lambda i: seen.append(threading.get_ident()), depth=0
        ))
        assert set(seen) == {main}

    def test_worker_exception_propagates_no_deadlock(self):
        def prepare(i):
            if i == 2:
                raise ValueError("boom in worker")
            return i

        got = []
        t0 = time.perf_counter()
        with pytest.raises(ValueError, match="boom in worker"):
            for x in prefetch.prefetch_iter(100, prepare, depth=3):
                got.append(x)
        # items before the failing one arrived in order; the raise was
        # prompt (a deadlock would hang until the suite timeout)
        assert got == [0, 1]
        assert time.perf_counter() - t0 < 30.0

    def test_consumer_abandonment_cancels_tail(self):
        started = []

        def prepare(i):
            started.append(i)
            return i

        it = prefetch.prefetch_iter(1000, prepare, depth=2)
        assert next(it) == 0
        it.close()  # consumer bails; queued futures are cancelled
        time.sleep(0.05)
        assert len(started) < 1000

    def test_env_knob_is_read_at_call_time(self, monkeypatch):
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "0")
        assert prefetch.prefetch_depth() == 0
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "5")
        assert prefetch.prefetch_depth() == 5
        monkeypatch.delenv("PHOTON_PREFETCH_DEPTH")
        monkeypatch.setattr(prefetch, "PREFETCH_DEPTH", 3)
        assert prefetch.prefetch_depth() == 3


class TestDeviceChunkCache:
    def test_repeat_pass_hits_device_tier(self):
        a = np.arange(64, dtype=np.float32)
        b = np.arange(64, dtype=np.float32) * 2
        d1 = prefetch.cached_device_put({"x": a, "y": b})
        d2 = prefetch.cached_device_put({"x": a, "y": b})
        s = prefetch.cache_stats()
        assert s["misses"] == 2 and s["device_hits"] == 2
        # the SAME resident buffers replay — no re-transfer
        assert d1["x"] is d2["x"] and d1["y"] is d2["y"]
        np.testing.assert_array_equal(np.asarray(d1["x"]), a)

    def test_per_array_granularity_on_offsets_swap(self):
        # the GAME visit swap: features unchanged, offsets fresh — only
        # the offsets column re-transfers
        X = np.ones((8, 4), np.float32)
        d1 = prefetch.cached_device_put(
            {"X": X, "offsets": np.zeros(8, np.float32)}
        )
        d2 = prefetch.cached_device_put(
            {"X": X, "offsets": np.ones(8, np.float32)}
        )
        s = prefetch.cache_stats()
        assert d1["X"] is d2["X"]
        assert s["device_hits"] == 1  # X only
        assert s["misses"] == 3  # X once, each offsets array once

    def test_eviction_spills_to_host_tier(self, monkeypatch):
        arrays = [np.full(256, i, np.float32) for i in range(4)]
        # budget fits exactly one 1 KiB array on the device tier
        monkeypatch.setattr(prefetch, "CHUNK_CACHE_BUDGET", 1024)
        monkeypatch.setattr(prefetch, "HOST_SPILL_BUDGET", 1 << 20)
        for a in arrays:
            prefetch.cached_device_put({"x": a})
        s = prefetch.cache_stats()
        assert s["device_entries"] == 1 and s["evictions"] == 3
        assert s["host_entries"] == 3
        # re-entering an evicted key is a HOST hit (device_put, no re-pack)
        out = prefetch.cached_device_put({"x": arrays[0]})
        np.testing.assert_array_equal(np.asarray(out["x"]), arrays[0])
        assert prefetch.cache_stats()["host_hits"] == 1

    def test_over_budget_array_never_pinned(self, monkeypatch):
        monkeypatch.setattr(prefetch, "CHUNK_CACHE_BUDGET", 16)
        out = prefetch.cached_device_put({"x": np.zeros(64, np.float32)})
        assert out["x"].shape == (64,)
        assert prefetch.cache_stats()["device_entries"] == 0

    def test_env_budget_read_at_call_time(self, monkeypatch):
        monkeypatch.setenv("PHOTON_CHUNK_CACHE_BUDGET", "12345")
        assert prefetch.chunk_cache_budget_bytes() == 12345
        monkeypatch.delenv("PHOTON_CHUNK_CACHE_BUDGET")
        monkeypatch.setattr(prefetch, "CHUNK_CACHE_BUDGET", 99)
        assert prefetch.chunk_cache_budget_bytes() == 99
        monkeypatch.setattr(prefetch, "CHUNK_CACHE_BUDGET", None)
        assert prefetch.chunk_cache_budget_bytes() > 0  # device query

    def test_device_tier_charges_post_pack_nbytes(self, monkeypatch):
        """The device budget charges the ACTUAL device array (post-pack
        dtype), not the host f32: a bf16 pass fits ~2x the chunks under
        the same PHOTON_CHUNK_CACHE_BUDGET."""
        arrays = [np.full(256, i, np.float32) for i in range(2)]  # 1 KiB each
        monkeypatch.setattr(prefetch, "CHUNK_CACHE_BUDGET", 1024)
        # ample HOST budget: this test isolates the DEVICE-tier charge
        # (the host-pinning bound has its own admission check)
        monkeypatch.setattr(prefetch, "HOST_SPILL_BUDGET", 1 << 20)
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "f32")
        for a in arrays:
            prefetch.cached_device_put({"values": a})
        s = prefetch.cache_stats()
        assert s["device_entries"] == 1 and s["evictions"] == 1
        prefetch.clear_cache()
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "int8")
        for a in arrays:
            prefetch.cached_device_put({"values": a})
        s = prefetch.cache_stats()
        # both bf16 twins (512 B each) fit where one f32 array did
        assert s["device_entries"] == 2 and s["evictions"] == 0
        assert s["device_bytes"] == 1024

    def test_aggregate_view_pinning_bounded_by_host_budget(self, monkeypatch):
        """Many small views of DISTINCT large bases: each admits alone,
        but the AGGREGATE host RAM their refs pin is bounded by the host
        budget — device entries evict on host-pin pressure, not just on
        their (tiny) device bytes."""
        monkeypatch.setattr(prefetch, "CHUNK_CACHE_BUDGET", 1 << 20)
        monkeypatch.setattr(prefetch, "HOST_SPILL_BUDGET", 8192)
        bases = [np.zeros(1024, np.float32) for _ in range(8)]  # 4 KiB each
        for b in bases:
            prefetch.cached_device_put({"x": b[:16]})  # 64 B on device
        s = prefetch.cache_stats()
        assert s["device_host_pinned_bytes"] <= 8192  # two bases' worth
        assert s["device_entries"] <= 2 and s["evictions"] >= 6

    def test_small_view_of_huge_base_never_pinned(self, monkeypatch):
        """A few-KB slice VIEW of a base larger than the host budget must
        not cache: its device copy is tiny, but holding the ref would pin
        the whole base in host RAM past both budgets (the pre-ladder
        guarantee, kept alongside the post-pack device-tier charge)."""
        monkeypatch.setattr(prefetch, "CHUNK_CACHE_BUDGET", 1 << 20)
        monkeypatch.setattr(prefetch, "HOST_SPILL_BUDGET", 4096)
        base = np.zeros(4096, np.float32)  # 16 KiB > host budget
        out = prefetch.cached_device_put({"x": base[:64]})
        assert out["x"].shape == (64,)
        assert prefetch.cache_stats()["device_entries"] == 0

    def test_eviction_at_mixed_dtypes(self, monkeypatch):
        """Eviction with packed (values → bf16) and unpacked (labels, f32)
        entries interleaved: byte totals stay coherent, and a spilled
        packed entry re-enters from the host tier with its PACKED twin —
        one device_put, no re-pack, correct values."""
        import ml_dtypes

        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "int8")
        vals = [np.full(256, i, np.float32) for i in range(3)]  # 512 B bf16
        labs = [np.full(128, i, np.float32) for i in range(3)]  # 512 B f32
        # fits exactly one (values, labels) pair on the device tier
        monkeypatch.setattr(prefetch, "CHUNK_CACHE_BUDGET", 1024)
        monkeypatch.setattr(prefetch, "HOST_SPILL_BUDGET", 1 << 20)
        for v, l in zip(vals, labs):
            out = prefetch.cached_device_put({"values": v, "labels": l})
            assert out["values"].dtype == jnp.bfloat16
            assert out["labels"].dtype == np.float32
        s = prefetch.cache_stats()
        assert s["device_bytes"] <= 1024
        assert s["evictions"] == 4  # two pairs pushed out
        # re-entry of the oldest pair: HOST hits (staged bf16 retained)
        out = prefetch.cached_device_put({"values": vals[0], "labels": labs[0]})
        assert prefetch.cache_stats()["host_hits"] == 2
        np.testing.assert_array_equal(
            np.asarray(out["values"]).astype(np.float32),
            vals[0].astype(ml_dtypes.bfloat16).astype(np.float32),
        )
        np.testing.assert_array_equal(np.asarray(out["labels"]), labs[0])

    def test_concurrent_mixed_puts_stay_coherent(self, monkeypatch):
        from concurrent.futures import ThreadPoolExecutor

        monkeypatch.setattr(prefetch, "CHUNK_CACHE_BUDGET", 2048)
        arrays = [np.full(128, i, np.float32) for i in range(8)]

        def worker(seed):
            rng = np.random.default_rng(seed)
            for _ in range(40):
                i = int(rng.integers(0, len(arrays)))
                out = prefetch.cached_device_put({"x": arrays[i]})
                np.testing.assert_array_equal(np.asarray(out["x"]), arrays[i])

        with ThreadPoolExecutor(max_workers=8) as ex:
            list(ex.map(worker, range(8)))
        s = prefetch.cache_stats()
        assert s["device_hits"] + s["host_hits"] + s["misses"] == 8 * 40
        assert s["device_bytes"] <= 2048


class TestStreamedObjectiveParity:
    """Bitwise prefetch-on vs depth-0 parity for the chunk objective's
    value / gradient / HVP / Hessian-diag streams and both scorers."""

    def _outputs(self, chunks, d, w, num_rows):
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
            float(sobj.value(w)),
            sobj.stream_scores(np.asarray(w), num_rows=num_rows),
            stream_scores(chunks, np.asarray(w), num_rows=num_rows),
        )

    def _assert_bitwise(self, a, b):
        for x, y in zip(a, b):
            if isinstance(x, float):
                assert x == y
            else:
                np.testing.assert_array_equal(x, y)

    @pytest.mark.parametrize("depth", ["2", "5"])
    def test_dense_chunks_bitwise(self, rng, monkeypatch, depth):
        X, y = _dense_problem(rng)
        chunks = dense_chunks(X, y, chunk_rows=128)
        w = jnp.asarray(rng.normal(size=8), jnp.float32)
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "0")
        ref = self._outputs(chunks, 8, w, 500)
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", depth)
        self._assert_bitwise(self._outputs(chunks, 8, w, 500), ref)

    def test_sparse_chunks_bitwise(self, rng, monkeypatch):
        n, d, k = 300, 50, 5
        idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
        val = rng.normal(size=(n, k)).astype(np.float32)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        chunks = sparse_chunks(idx, val, y, chunk_rows=97)
        w = jnp.asarray(rng.normal(size=d), jnp.float32)
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "0")
        ref = self._outputs(chunks, d, w, n)
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "2")
        self._assert_bitwise(self._outputs(chunks, d, w, n), ref)

    def test_one_chunk_stream_bitwise(self, rng, monkeypatch):
        X, y = _dense_problem(rng, n=100)
        chunks = dense_chunks(X, y, chunk_rows=128)
        assert len(chunks) == 1
        w = jnp.asarray(rng.normal(size=8), jnp.float32)
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "0")
        ref = self._outputs(chunks, 8, w, 100)
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "2")
        self._assert_bitwise(self._outputs(chunks, 8, w, 100), ref)

    def test_depth_exceeding_chunk_count_bitwise(self, rng, monkeypatch):
        X, y = _dense_problem(rng)
        chunks = dense_chunks(X, y, chunk_rows=128)  # 4 chunks
        w = jnp.asarray(rng.normal(size=8), jnp.float32)
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "0")
        ref = self._outputs(chunks, 8, w, 500)
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "32")
        self._assert_bitwise(self._outputs(chunks, 8, w, 500), ref)

    def test_cache_eviction_mid_pass_bitwise(self, rng, monkeypatch):
        # a budget of ONE chunk's labels column forces evictions while the
        # pass is still streaming — values must not change, only timings
        X, y = _dense_problem(rng)
        chunks = dense_chunks(X, y, chunk_rows=128)
        w = jnp.asarray(rng.normal(size=8), jnp.float32)
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "0")
        ref = self._outputs(chunks, 8, w, 500)
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "2")
        monkeypatch.setattr(prefetch, "CHUNK_CACHE_BUDGET", 128 * 4)
        monkeypatch.setattr(prefetch, "HOST_SPILL_BUDGET", 128 * 8)
        self._assert_bitwise(self._outputs(chunks, 8, w, 500), ref)
        assert prefetch.cache_stats()["evictions"] > 0

    def test_worker_failure_in_stream_raises_not_hangs(self, rng, monkeypatch):
        X, y = _dense_problem(rng)
        sobj = StreamingGLMObjective(
            dense_chunks(X, y, chunk_rows=128), LOSS, num_features=8,
            l2_weight=0.7, intercept_index=7,
        )
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "2")
        calls = []
        orig = prefetch.cached_device_put

        def failing(tree):
            calls.append(1)
            if len(calls) == 3:
                raise RuntimeError("staging failed")
            return orig(tree)

        monkeypatch.setattr(prefetch, "cached_device_put", failing)
        t0 = time.perf_counter()
        with pytest.raises(RuntimeError, match="staging failed"):
            sobj.value_and_grad(jnp.zeros(8, jnp.float32))
        assert time.perf_counter() - t0 < 30.0

    def test_optimizer_passes_replay_resident_chunks(self, rng, monkeypatch):
        from photon_ml_tpu.optim.host_lbfgs import host_lbfgs_minimize

        X, y = _dense_problem(rng, n=400)
        chunks = dense_chunks(X, y, chunk_rows=128)
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "2")
        sobj = StreamingGLMObjective(
            chunks, LOSS, num_features=8, l2_weight=1.0, intercept_index=7
        )
        host_lbfgs_minimize(
            sobj, np.zeros(8, np.float32),
            OptimizerConfig(max_iterations=20, tolerance=0.0),
        )
        s = prefetch.cache_stats()
        # every pass after the first replays device-resident buffers: the
        # whole solve transfers each host array exactly once
        assert s["misses"] == len(chunks) * 4  # X, labels, offsets, weights
        assert s["device_hits"] > s["misses"]


@pytest.mark.kernel
def test_tiled_streamed_consumer_prefetch_bitwise(rng, monkeypatch):
    """The tile-COO streamed consumer (device-resident packed streams,
    slim per-pass uploads) under prefetch: bitwise parity vs depth 0, in
    interpret mode on retuned-down constants."""
    import photon_ml_tpu.ops.sparse_tiled as st_mod

    monkeypatch.setattr(st_mod, "GROUPS_PER_STEP", 8)
    monkeypatch.setattr(st_mod, "SEGMENTS_PER_DMA", 2)
    n, d, k = 2048, 4096, 4
    idx = rng.integers(0, d, size=(n, k)).astype(np.int32)
    val = rng.normal(size=(n, k)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    chunks = sparse_chunks(idx, val, y, chunk_rows=1024)
    w = jnp.asarray(rng.normal(size=d), jnp.float32)
    outs = {}
    for depth in ("0", "2"):
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", depth)
        obj = StreamingGLMObjective(
            chunks, LOSS, num_features=d, l2_weight=0.4, tile_sparse=True
        )
        v, g = obj.value_and_grad(w)
        outs[depth] = (
            float(v), np.asarray(g),
            obj.stream_scores(np.asarray(w), num_rows=n),
        )
    assert outs["2"][0] == outs["0"][0]
    np.testing.assert_array_equal(outs["2"][1], outs["0"][1])
    np.testing.assert_array_equal(outs["2"][2], outs["0"][2])


class TestGameStreamingParity:
    def _fit(self, rng_seed=7, n=300):
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

        rng = np.random.default_rng(rng_seed)
        d, dr, E = 6, 3, 8
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
            optimizer=OptimizerConfig(max_iterations=40, tolerance=1e-8),
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
        model, _info = StreamedGameTrainer(cfg, chunk_rows=64).fit(data)
        return model

    def test_streamed_game_fit_bitwise(self, monkeypatch):
        """The whole streamed GAME fit — chunk-objective solves, bucket
        ingest, visit scoring, residual exchange — is bitwise identical
        prefetch-on vs off (same data, same seed)."""
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "0")
        ref = self._fit()
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "2")
        got = self._fit()
        np.testing.assert_array_equal(
            np.asarray(got.models["fixed"].model.coefficients.means),
            np.asarray(ref.models["fixed"].model.coefficients.means),
        )
        np.testing.assert_array_equal(
            np.asarray(got.models["user"].coefficients),
            np.asarray(ref.models["user"].coefficients),
        )


class TestCrossValidationParity:
    def test_cv_folds_bitwise(self, rng, monkeypatch):
        from photon_ml_tpu.ops.batch import DenseBatch
        from photon_ml_tpu.supervised.cross_validation import (
            cross_validate_glm,
        )

        d = 6
        w_true = (rng.normal(size=d) * 0.8).astype(np.float32)
        X = rng.normal(size=(240, d)).astype(np.float32)
        y = (rng.uniform(size=240) < 1 / (1 + np.exp(-(X @ w_true)))).astype(
            np.float32
        )
        batch = DenseBatch(
            X=jnp.asarray(X), labels=jnp.asarray(y),
            offsets=jnp.zeros((240,), jnp.float32),
            weights=jnp.ones((240,), jnp.float32),
        )

        def run():
            return cross_validate_glm(
                batch, TaskType.LOGISTIC_REGRESSION, k=4,
                regularization_weights=[0.5, 5.0],
                optimizer_config=OptimizerConfig(
                    max_iterations=40, tolerance=1e-8
                ),
                seed=3,
            )

        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "0")
        ref = run()
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "3")
        got = run()
        assert got.best_weight == ref.best_weight
        for lam in (0.5, 5.0):
            assert got.metric_values[lam] == ref.metric_values[lam]
        np.testing.assert_array_equal(
            np.asarray(got.final.models[got.best_weight].coefficients.means),
            np.asarray(ref.final.models[ref.best_weight].coefficients.means),
        )


class TestTileCacheHammer:
    def test_concurrent_layout_lookups_stay_coherent(self, rng):
        """Prefetch workers hit the process-wide tile-layout cache
        concurrently: hammer it from a thread pool over several distinct
        structures with a capacity that forces constant eviction —
        bookkeeping must stay coherent and every returned layout correct
        (host-side pack only; no kernels traced)."""
        from concurrent.futures import ThreadPoolExecutor

        from photon_ml_tpu.ops import tile_cache
        from photon_ml_tpu.ops.batch import SparseBatch

        batches = []
        for s in range(4):
            r = np.random.default_rng(s)
            n, d, k = 256, 4096, 3
            batches.append(SparseBatch(
                indices=r.integers(0, d, size=(n, k)).astype(np.int32),
                values=r.normal(size=(n, k)).astype(np.float32),
                labels=np.zeros(n, np.float32),
                offsets=np.zeros(n, np.float32),
                weights=np.ones(n, np.float32),
                num_features=d,
            ))
        refs = [
            tuple(c.m_arrays[0].shape for c in
                  tile_cache.tiled_layout_for(b).chunks)
            for b in batches
        ]
        tile_cache.clear()
        old_cap = tile_cache.capacity()
        tile_cache.set_capacity(2)  # below the working set: evict nonstop
        try:
            def worker(seed):
                r = np.random.default_rng(seed)
                for _ in range(15):
                    i = int(r.integers(0, len(batches)))
                    tb = tile_cache.tiled_layout_for(batches[i])
                    assert tuple(
                        c.m_arrays[0].shape for c in tb.chunks
                    ) == refs[i]

            with ThreadPoolExecutor(max_workers=8) as ex:
                list(ex.map(worker, range(8)))
            s = tile_cache.stats()
            assert s["hits"] + s["misses"] == 8 * 15
            assert s["entries"] <= 2
        finally:
            tile_cache.set_capacity(old_cap)
            tile_cache.clear()


class TestStageCounters:
    def test_prefetch_run_populates_counters(self, rng, monkeypatch):
        from photon_ml_tpu.utils import profiling

        profiling.reset_counters("prefetch.")
        X, y = _dense_problem(rng)
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "2")
        sobj = StreamingGLMObjective(
            dense_chunks(X, y, chunk_rows=128), LOSS, num_features=8,
            l2_weight=0.7, intercept_index=7,
        )
        sobj.value_and_grad(jnp.zeros(8, jnp.float32))
        snap = profiling.counter_snapshot("prefetch.")
        for name in (
            "prefetch.host_pack_s",
            "prefetch.device_put_s",
            "prefetch.consumer_wait_s",
        ):
            assert name in snap and snap[name]["calls"] > 0, snap
        profiling.reset_counters("prefetch.")
        assert profiling.counter_snapshot("prefetch.") == {}
