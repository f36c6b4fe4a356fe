"""Feature-range-sharded fixed-effect solves (PHOTON_FE_SHARD).

Coverage tiers, cheapest first (tier-1 sits near its wall-clock budget):

- partitioner property tests — pure host arithmetic on
  ``data/index_map.plan_feature_ranges`` (coverage/disjointness,
  determinism, weight modes, pathological histograms, strict knob parse);
- ``_fe_restrict_chunks`` structural properties — the per-range chunk
  restriction partitions the live nonzeros exactly and SHARES
  label/offset/weight storage with the originals;
- knob-off bitwise identity — ``PHOTON_FE_SHARD=0`` and unset produce
  byte-identical results across all four streamed consumers (objective
  contracts, both optimizers, method + module scoring), and the P=1
  sharded path (identity restriction) matches the replicated path
  bitwise on padding-free chunks;
- gloo loopback parity at P∈{2, 4} — sharded coefficients/objective/
  scores match the single-process reference per the stated contract
  (gradient segments exact; margins under the fixed-ascending-range
  reduction reassociate in f32), with both process groups spawned
  CONCURRENTLY so the suite pays one jax-import wall, not two;
- one kernel-marked tiled test — an ``fe_range`` column-sliced layout's
  matvec/rmatvec against the dense partial, under the 8x2 retuned carve
  the conftest fixture installs.
"""

import json
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax.numpy as jnp

from photon_ml_tpu.config import OptimizerConfig
from photon_ml_tpu.data.index_map import (
    FeatureRangePlan,
    fe_shard_enabled,
    fe_split_weight,
    plan_feature_ranges,
)
from photon_ml_tpu.ops.losses import logistic_loss
from photon_ml_tpu.ops.streaming import (
    StreamingGLMObjective,
    _fe_nnz_histogram,
    _fe_restrict_chunks,
    _to_batch,
    stream_scores,
)
from photon_ml_tpu.optim.host_lbfgs import host_lbfgs_minimize
from photon_ml_tpu.optim.host_tron import host_tron_minimize


def _zipf_hist(d: int, draws: int = 200_000, a: float = 1.3) -> np.ndarray:
    rng = np.random.default_rng(7)
    idx = (rng.zipf(a, size=draws).astype(np.int64) - 1) % d
    return np.bincount(idx, minlength=d).astype(np.int64)


class TestPlanFeatureRanges:
    def test_cover_and_disjoint_on_zipf(self):
        hist = _zipf_hist(4096)
        for p in (1, 2, 3, 4, 7):
            plan = plan_feature_ranges(hist, p)
            b = plan.boundaries
            assert b[0] == 0 and b[-1] == 4096
            assert list(b) == sorted(b)
            # strictly ascending: every range nonempty even where the
            # histogram is zero (coverage is structural)
            assert all(hi > lo for lo, hi in zip(b, b[1:]))
            assert plan.num_ranges == p
            # per-range weights partition the histogram total exactly
            assert sum(plan.weights) == float(hist.sum())

    def test_deterministic_and_pid_independent(self):
        """The rule reads ONLY (histogram, P): repeated calls agree, and
        no per-process input exists — ``range_of(pid)`` just indexes the
        one shared boundary tuple (how every process derives the same
        partition with zero communication)."""
        hist = _zipf_hist(1024)
        a = plan_feature_ranges(hist, 4)
        b = plan_feature_ranges(hist.copy(), 4)
        assert a == b
        ranges = [a.range_of(pid) for pid in range(4)]
        assert ranges == sorted(ranges)
        assert [lo for lo, _ in ranges] == list(a.boundaries[:-1])

    def test_nnz_balance_on_zipf_meets_the_r12_gate(self):
        """The prefix cut on an r12-shaped Zipf histogram lands inside the
        acceptance bound (nnz balance ≤ 1.15x at P∈{2,4}) — the committed
        MULTICHIP_r12.json numbers are not a lucky draw."""
        hist = _zipf_hist(100_000, draws=500_000)
        for p in (2, 4):
            assert plan_feature_ranges(hist, p).balance <= 1.15

    def test_width_mode_splits_uniformly(self):
        hist = _zipf_hist(1000)
        plan = plan_feature_ranges(hist, 4, mode="width")
        assert plan.boundaries == (0, 250, 500, 750, 1000)

    def test_zero_weights_fall_back_to_uniform(self):
        plan = plan_feature_ranges(np.zeros(100), 4)
        assert plan.boundaries == (0, 25, 50, 75, 100)
        assert plan.balance == 1.0

    def test_all_weight_in_one_column_still_covers(self):
        """A single hot column carrying ALL the weight: contiguity caps
        what any split can do — the hot range owns everything — but the
        plan must stay a legal cover with nonempty ranges, not collapse."""
        hist = np.zeros(64)
        hist[40] = 1e6
        plan = plan_feature_ranges(hist, 4)
        b = plan.boundaries
        assert b[0] == 0 and b[-1] == 64
        assert all(hi > lo for lo, hi in zip(b, b[1:]))
        assert sum(plan.weights) == 1e6
        assert plan.balance == pytest.approx(4.0)

    def test_rejects_bad_inputs(self):
        hist = np.ones(8)
        with pytest.raises(ValueError, match="positive"):
            plan_feature_ranges(hist, 0)
        with pytest.raises(ValueError, match="cannot split"):
            plan_feature_ranges(np.ones(3), 4)
        with pytest.raises(ValueError, match="split mode"):
            plan_feature_ranges(hist, 2, mode="rows")


class TestKnobParsing:
    def test_fe_shard_env_wins_and_strict_parses(self, monkeypatch):
        import photon_ml_tpu.data.index_map as im

        monkeypatch.setattr(im, "FE_SHARD", 0)
        monkeypatch.delenv("PHOTON_FE_SHARD", raising=False)
        assert fe_shard_enabled() is False
        monkeypatch.setenv("PHOTON_FE_SHARD", "1")
        assert fe_shard_enabled() is True
        monkeypatch.setenv("PHOTON_FE_SHARD", "0")
        assert fe_shard_enabled() is False
        # module global is the env-less fallback (bench retune surface)
        monkeypatch.delenv("PHOTON_FE_SHARD")
        monkeypatch.setattr(im, "FE_SHARD", 1)
        assert fe_shard_enabled() is True
        # strict parse: a typo fails loudly, never benches the default
        monkeypatch.setenv("PHOTON_FE_SHARD", "yes")
        with pytest.raises(ValueError):
            fe_shard_enabled()

    def test_fe_split_weight_strict_membership(self, monkeypatch):
        monkeypatch.delenv("PHOTON_FE_SPLIT_WEIGHT", raising=False)
        assert fe_split_weight() == "nnz"
        monkeypatch.setenv("PHOTON_FE_SPLIT_WEIGHT", "width")
        assert fe_split_weight() == "width"
        monkeypatch.setenv("PHOTON_FE_SPLIT_WEIGHT", "bytes")
        with pytest.raises(ValueError, match="PHOTON_FE_SPLIT_WEIGHT"):
            fe_split_weight()


def _make_chunks(rng, n_chunks=3, n=64, d=96, k=5, pad_zeros=False):
    """Sparse chunk dicts with Zipf-skewed columns. ``pad_zeros`` plants
    zero-value slots (excluded from the histogram and inert in matvecs)."""
    chunks = []
    for _ in range(n_chunks):
        idx = ((rng.zipf(1.4, size=(n, k)).astype(np.int64) - 1) % d).astype(
            np.int32
        )
        val = rng.standard_normal((n, k)).astype(np.float32)
        val = np.where(val == 0.0, np.float32(0.5), val)  # all-live default
        if pad_zeros:
            val[:, -1] = 0.0
        chunks.append({
            "indices": idx,
            "values": val,
            "labels": (rng.uniform(size=n) < 0.5).astype(np.float32),
            "offsets": rng.standard_normal(n).astype(np.float32) * 0.1,
            "weights": np.ones(n, np.float32),
        })
    return chunks


class TestRestrictChunks:
    def test_partitions_live_nnz_exactly(self, rng):
        d = 96
        chunks = _make_chunks(rng, pad_zeros=True)
        hist = _fe_nnz_histogram(chunks, d)
        assert hist.sum() == sum(
            int((c["values"] != 0.0).sum()) for c in chunks
        )
        plan = plan_feature_ranges(hist, 3)
        per_range_nnz = 0
        dense_sum = np.zeros((len(chunks), 64, d), np.float64)
        for pid in range(3):
            lo, hi = plan.range_of(pid)
            restricted, k_max = _fe_restrict_chunks(chunks, lo, hi)
            assert k_max <= chunks[0]["values"].shape[1]
            for ci, r in enumerate(restricted):
                live = r["values"] != 0.0
                per_range_nnz += int(live.sum())
                # shifted-local indices stay inside [0, hi-lo)
                assert r["indices"][live].min(initial=0) >= 0
                assert r["indices"][live].max(initial=0) < hi - lo
                # per-row arrays SHARE storage (the prefetch chunk-cache
                # and per-visit residual-swap contract)
                for key in ("labels", "offsets", "weights"):
                    assert r[key] is chunks[ci][key]
                np.add.at(
                    dense_sum[ci],
                    (np.arange(64)[:, None], r["indices"] + lo),
                    np.where(live, r["values"], 0.0),
                )
        assert per_range_nnz == int(hist.sum())
        # densified per-range restrictions reassemble the original matrix
        dense_ref = np.zeros_like(dense_sum)
        for ci, c in enumerate(chunks):
            np.add.at(
                dense_ref[ci],
                (np.arange(64)[:, None], c["indices"]),
                np.where(c["values"] != 0.0, c["values"], 0.0),
            )
        np.testing.assert_array_equal(dense_sum, dense_ref)

    def test_identity_range_is_bitwise_on_padding_free_chunks(self, rng):
        chunks = _make_chunks(rng)
        restricted, k_max = _fe_restrict_chunks(chunks, 0, 96)
        assert k_max == chunks[0]["values"].shape[1]
        for r, c in zip(restricted, chunks):
            np.testing.assert_array_equal(r["indices"], c["indices"])
            np.testing.assert_array_equal(r["values"], c["values"])


class TestTileCacheFeRangeKey:
    def test_fe_range_joins_the_layout_cache_key(self, rng):
        """Two layouts over the SAME sparsity structure but different
        ``fe_range`` identities must occupy distinct cache entries — a
        re-plan or P change invalidates by key, never by luck."""
        from photon_ml_tpu.ops import tile_cache

        chunks = _make_chunks(rng, n_chunks=1)
        b = _to_batch(chunks[0], 96)
        tile_cache.clear()
        before = tile_cache.stats()
        tb0 = tile_cache.tiled_layout_for(b, fe_range=None)
        tb1 = tile_cache.tiled_layout_for(b, fe_range=(0, 0, 96, 2))
        stats = tile_cache.stats()
        assert stats["misses"] - before["misses"] == 2
        assert stats["entries"] >= 2
        assert tb0.fe_range is None and tb1.fe_range == (0, 0, 96, 2)
        # repeat lookups hit, per key
        tile_cache.tiled_layout_for(b, fe_range=(0, 0, 96, 2))
        assert tile_cache.stats()["hits"] - before["hits"] >= 1
        tile_cache.clear()


def _consume_all(obj, w_local, w_probe_local, n_rows):
    """Every streamed contract at one probe point, as host numpy."""
    v, g = obj.value_and_grad(jnp.asarray(w_local, jnp.float32))
    hv = obj.hvp(
        jnp.asarray(w_local, jnp.float32),
        jnp.asarray(w_probe_local, jnp.float32),
    )
    hd = obj.hessian_diag(jnp.asarray(w_local, jnp.float32))
    sc = obj.stream_scores(jnp.asarray(w_local, jnp.float32), num_rows=n_rows)
    return (
        np.asarray(v), np.asarray(g), np.asarray(hv), np.asarray(hd),
        np.asarray(sc),
    )


class TestKnobOffBitwise:
    """``PHOTON_FE_SHARD=0`` and unset are byte-identical across all four
    streamed consumers; the P=1 sharded path (identity restriction on
    padding-free chunks) matches them bitwise too — same per-chunk
    arithmetic, margins combined through the identity reduction."""

    def _objective(self, chunks, d):
        return StreamingGLMObjective(
            chunks=chunks, loss=logistic_loss, num_features=d,
            l2_weight=0.25, tile_sparse=False,
        )

    def test_off_and_unset_and_p1_shard_agree_bitwise(self, rng, monkeypatch):
        d, n_rows = 96, 3 * 64
        chunks = _make_chunks(rng)
        w = rng.standard_normal(d).astype(np.float32) * 0.1
        vp = rng.standard_normal(d).astype(np.float32)
        w0 = np.zeros(d, np.float32)

        monkeypatch.delenv("PHOTON_FE_SHARD", raising=False)
        obj = self._objective(chunks, d)
        assert obj.fe_active is False
        ref = _consume_all(obj, w, vp, n_rows)
        res_ref = host_lbfgs_minimize(
            obj, w0, OptimizerConfig(max_iterations=4, tolerance=1e-12)
        )
        tron_ref = host_tron_minimize(
            obj, w0, OptimizerConfig(max_iterations=3, tolerance=1e-12)
        )
        mod_ref = stream_scores(
            chunks, w, num_rows=n_rows, num_features=d, tile_sparse=False
        )

        for knob in ("0", "1"):
            monkeypatch.setenv("PHOTON_FE_SHARD", knob)
            obj2 = self._objective(chunks, d)
            assert obj2.fe_active is (knob == "1")
            got = _consume_all(
                obj2,
                obj2.fe_slice(w) if obj2.fe_active else w,
                obj2.fe_slice(vp) if obj2.fe_active else vp,
                n_rows,
            )
            gather = obj2.fe_gather if obj2.fe_active else (lambda x: x)
            np.testing.assert_array_equal(got[0], ref[0], err_msg=knob)
            for gi in (1, 2, 3):  # grad/hvp/hessian_diag segments
                np.testing.assert_array_equal(
                    gather(got[gi]), ref[gi], err_msg=knob
                )
            np.testing.assert_array_equal(got[4], ref[4], err_msg=knob)
            res = host_lbfgs_minimize(
                obj2,
                obj2.fe_slice(w0) if obj2.fe_active else w0,
                OptimizerConfig(max_iterations=4, tolerance=1e-12),
            )
            np.testing.assert_array_equal(
                gather(np.asarray(res.w)), np.asarray(res_ref.w),
                err_msg=knob,
            )
            assert int(res.iterations) == int(res_ref.iterations)
            tron = host_tron_minimize(
                obj2,
                obj2.fe_slice(w0) if obj2.fe_active else w0,
                OptimizerConfig(max_iterations=3, tolerance=1e-12),
            )
            np.testing.assert_array_equal(
                gather(np.asarray(tron.w)), np.asarray(tron_ref.w),
                err_msg=knob,
            )
            mod = stream_scores(
                chunks, w, num_rows=n_rows, num_features=d, tile_sparse=False
            )
            np.testing.assert_array_equal(mod, np.asarray(mod_ref), err_msg=knob)

    def test_p1_shard_padded_chunks_match_numerically(self, rng, monkeypatch):
        """Zero-value padding compacts away under restriction (a shorter
        per-row width, not the replicated path's layout), so the identity
        claim weakens to numerical agreement — but stays tight: the same
        nonzeros sum in the same row order."""
        d, n_rows = 96, 3 * 64
        chunks = _make_chunks(rng, pad_zeros=True)
        w = rng.standard_normal(d).astype(np.float32) * 0.1
        vp = rng.standard_normal(d).astype(np.float32)
        monkeypatch.delenv("PHOTON_FE_SHARD", raising=False)
        ref = _consume_all(self._objective(chunks, d), w, vp, n_rows)
        monkeypatch.setenv("PHOTON_FE_SHARD", "1")
        obj = self._objective(chunks, d)
        got = _consume_all(obj, obj.fe_slice(w), obj.fe_slice(vp), n_rows)
        np.testing.assert_allclose(got[0], ref[0], rtol=1e-6)
        for gi in (1, 2, 3):
            np.testing.assert_allclose(
                obj.fe_gather(got[gi]), ref[gi], rtol=1e-5, atol=1e-6
            )
        np.testing.assert_allclose(got[4], ref[4], rtol=1e-5, atol=1e-6)

    def test_fe_shard_rejects_dense_cross_process_and_norm(
        self, rng, monkeypatch
    ):
        monkeypatch.setenv("PHOTON_FE_SHARD", "1")
        X = rng.standard_normal((8, 4)).astype(np.float32)
        dense = [{
            "X": X,
            "labels": np.ones(8, np.float32),
            "offsets": np.zeros(8, np.float32),
            "weights": np.ones(8, np.float32),
        }]
        # the env knob auto-rule silently skips dense chunks (they fit one
        # chip's HBM by construction); only FORCING fe_shard raises
        assert StreamingGLMObjective(
            chunks=dense, loss=logistic_loss, num_features=4,
        ).fe_active is False
        with pytest.raises(ValueError, match="sparse"):
            StreamingGLMObjective(
                chunks=dense, loss=logistic_loss, num_features=4,
                fe_shard=True,
            )
        chunks = _make_chunks(rng, n_chunks=1)
        with pytest.raises(ValueError, match="cross_process"):
            StreamingGLMObjective(
                chunks=chunks, loss=logistic_loss, num_features=96,
                cross_process=True, tile_sparse=False,
            )


# -- gloo loopback parity (P∈{2,4}) -----------------------------------------
# Replicated rows, PHOTON_FE_SHARD=1: every process holds one feature
# range; coefficients/objective/scores must match the single-process
# reference computed IN-PROCESS by the parent (spawning a P=1 worker
# would buy nothing — the replicated path has no collectives).

_FE_WORKER = textwrap.dedent(
    """
    import json, os, sys
    coordinator, pid, nproc = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    os.environ["PHOTON_FE_SHARD"] = "1"
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    import numpy as np
    from photon_ml_tpu.parallel.multihost import initialize_multihost
    initialize_multihost(coordinator, num_processes=nproc, process_id=pid)

    import jax.numpy as jnp
    from photon_ml_tpu.config import OptimizerConfig
    from photon_ml_tpu.ops.losses import logistic_loss
    from photon_ml_tpu.ops.streaming import (
        StreamingGLMObjective, stream_scores,
    )
    from photon_ml_tpu.optim.host_lbfgs import host_lbfgs_minimize
    from photon_ml_tpu.optim.host_tron import host_tron_minimize

    # the SAME deterministic dataset as the parent (rows replicated:
    # every process streams all rows, the win is the feature axis)
    rng = np.random.default_rng(1218)
    d, n, k = 96, 64, 5
    chunks = []
    for _ in range(3):
        idx = ((rng.zipf(1.4, size=(n, k)).astype(np.int64) - 1) % d
               ).astype(np.int32)
        val = rng.standard_normal((n, k)).astype(np.float32)
        val = np.where(val == 0.0, np.float32(0.5), val)
        chunks.append({
            "indices": idx, "values": val,
            "labels": (rng.uniform(size=n) < 0.5).astype(np.float32),
            "offsets": rng.standard_normal(n).astype(np.float32) * 0.1,
            "weights": np.ones(n, np.float32),
        })
    w_probe = (rng.standard_normal(d) * 0.1).astype(np.float32)
    n_rows = 3 * n

    obj = StreamingGLMObjective(
        chunks=chunks, loss=logistic_loss, num_features=d,
        l2_weight=0.25, tile_sparse=False,
    )
    assert obj.fe_active
    wp = obj.fe_slice(w_probe)
    v, g = obj.value_and_grad(jnp.asarray(wp, jnp.float32))
    g_full = obj.fe_gather(np.asarray(g))
    res = host_lbfgs_minimize(
        obj, obj.fe_slice(np.zeros(d, np.float32)),
        OptimizerConfig(max_iterations=4, tolerance=1e-12),
    )
    w_lbfgs = obj.fe_gather(np.asarray(res.w))
    tron = host_tron_minimize(
        obj, obj.fe_slice(np.zeros(d, np.float32)),
        OptimizerConfig(max_iterations=3, tolerance=1e-12),
    )
    w_tron = obj.fe_gather(np.asarray(tron.w))
    sc_method = obj.stream_scores(np.asarray(res.w), num_rows=n_rows)
    sc_module = stream_scores(
        chunks, w_lbfgs, num_rows=n_rows, num_features=d, tile_sparse=False,
    )
    from photon_ml_tpu.obs.metrics import REGISTRY
    gauges = {
        key: val for key, val in
        REGISTRY.snapshot().get("gauges", {}).items()
        if key.startswith("fe_shard.")
    }
    print("RESULT " + json.dumps({
        "pid": pid,
        "probe_value": float(v),
        "grad": np.asarray(g_full, np.float64).tolist(),
        "w_lbfgs": np.asarray(w_lbfgs, np.float64).tolist(),
        "iters_lbfgs": int(res.iterations),
        "value_lbfgs": float(res.value),
        "w_tron": np.asarray(w_tron, np.float64).tolist(),
        "scores_method": np.asarray(sc_method, np.float64).tolist(),
        "scores_module": np.asarray(sc_module, np.float64).tolist(),
        "gauges": gauges,
    }))
    """
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_fe_workers(nproc: int) -> list:
    coordinator = f"127.0.0.1:{_free_port()}"
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "PHOTON_FE_SHARD")
    }
    return [
        subprocess.Popen(
            [sys.executable, "-c", _FE_WORKER, coordinator,
             str(pid), str(nproc)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        for pid in range(nproc)
    ]


def _collect_fe_workers(procs, nproc: int) -> dict:
    results = {}
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, f"worker failed:\n{out}\n{err[-4000:]}"
        for line in out.splitlines():
            if line.startswith("RESULT "):
                r = json.loads(line[len("RESULT "):])
                results[r["pid"]] = r
    assert set(results) == set(range(nproc))
    return results


def test_fe_shard_loopback_parity_matches_single_process(monkeypatch):
    d, n, n_rows = 96, 64, 3 * 64
    # the P=2 and P=4 groups launch together and ride out the jax-import
    # wall concurrently while the parent computes the reference
    groups = {nproc: _spawn_fe_workers(nproc) for nproc in (2, 4)}

    rng = np.random.default_rng(1218)
    chunks = _make_chunks(rng)  # identical draw order to the worker
    w_probe = (rng.standard_normal(d) * 0.1).astype(np.float32)
    monkeypatch.delenv("PHOTON_FE_SHARD", raising=False)
    obj = StreamingGLMObjective(
        chunks=chunks, loss=logistic_loss, num_features=d,
        l2_weight=0.25, tile_sparse=False,
    )
    v_ref, g_ref = obj.value_and_grad(jnp.asarray(w_probe, jnp.float32))
    res_ref = host_lbfgs_minimize(
        obj, np.zeros(d, np.float32),
        OptimizerConfig(max_iterations=4, tolerance=1e-12),
    )
    tron_ref = host_tron_minimize(
        obj, np.zeros(d, np.float32),
        OptimizerConfig(max_iterations=3, tolerance=1e-12),
    )
    sc_ref = np.asarray(
        obj.stream_scores(jnp.asarray(res_ref.w), num_rows=n_rows)
    )

    for nproc, procs in groups.items():
        got = _collect_fe_workers(procs, nproc)
        r0 = got[0]
        for pid, r in got.items():
            tag = f"nproc={nproc} pid={pid}"
            # every process reports IDENTICAL assembled results (the
            # fixed-order reduction makes the combined bits lockstep)
            for field in (
                "probe_value", "grad", "w_lbfgs", "iters_lbfgs",
                "value_lbfgs", "w_tron", "scores_method", "scores_module",
            ):
                assert r[field] == r0[field], tag
            # telemetry rides every process; widths/nnz partition the
            # global feature space and live-nnz total exactly
            assert r["gauges"]["fe_shard.ranges"] == float(nproc), tag
            assert r["gauges"]["fe_shard.nnz_balance"] >= 1.0, tag
        assert sum(
            r["gauges"]["fe_shard.width"] for r in got.values()
        ) == float(d)
        assert sum(r["gauges"]["fe_shard.nnz_local"] for r in got.values()
                   ) == float(sum(int((c["values"] != 0).sum())
                                  for c in chunks))
        # parity vs the single-process reference: gradient segments are
        # exact by construction; values/coefficients/scores sit behind
        # the f32 fixed-order margin reduction (reassociation only)
        tag = f"nproc={nproc}"
        np.testing.assert_allclose(
            r0["probe_value"], float(v_ref), rtol=1e-6, err_msg=tag
        )
        np.testing.assert_allclose(
            r0["grad"], np.asarray(g_ref, np.float64), rtol=1e-5,
            atol=1e-6, err_msg=tag,
        )
        np.testing.assert_allclose(
            r0["w_lbfgs"], np.asarray(res_ref.w, np.float64), rtol=1e-4,
            atol=1e-5, err_msg=tag,
        )
        # TRON's CG inner loop compounds the per-evaluation f32 margin
        # reassociation across hvp calls, so the truncated third iterate
        # sits a few e-4 off the reference (both converge to one optimum)
        np.testing.assert_allclose(
            r0["w_tron"], np.asarray(tron_ref.w, np.float64), rtol=2e-3,
            atol=5e-4, err_msg=tag,
        )
        np.testing.assert_allclose(
            r0["scores_method"], sc_ref, rtol=1e-4, atol=1e-5, err_msg=tag
        )
        np.testing.assert_allclose(
            r0["scores_module"], sc_ref, rtol=1e-4, atol=1e-5, err_msg=tag
        )


@pytest.mark.kernel
def test_fe_range_tiled_matvec_matches_dense_partial(rng):
    """A column-sliced ``fe_range`` layout through the tile-COO kernel (at
    the conftest-installed 8x2 carve): matvec/rmatvec against the dense
    partial over [lo, hi) — the sharded solve's phase A/B kernels consume
    exactly this batch shape. No collectives: one process, one range."""
    from photon_ml_tpu.ops.sparse_tiled import tile_sparse_batch

    d, n, k = 1024, 256, 4
    idx = ((rng.zipf(1.4, size=(n, k)).astype(np.int64) - 1) % d).astype(
        np.int32
    )
    val = rng.standard_normal((n, k)).astype(np.float32)
    val = np.where(val == 0.0, np.float32(0.5), val)
    chunk = {
        "indices": idx, "values": val,
        "labels": np.zeros(n, np.float32),
        "offsets": np.zeros(n, np.float32),
        "weights": np.ones(n, np.float32),
    }
    hist = _fe_nnz_histogram([chunk], d)
    plan = plan_feature_ranges(hist, 2)
    dense = np.zeros((n, d), np.float64)
    np.add.at(dense, (np.arange(n)[:, None], idx), val.astype(np.float64))
    for pid in range(2):
        lo, hi = plan.range_of(pid)
        restricted, _k = _fe_restrict_chunks([chunk], lo, hi)
        b = _to_batch(restricted[0], hi - lo)
        tb = tile_sparse_batch(b, fe_range=(pid, lo, hi, 2))
        assert tb.fe_range == (pid, lo, hi, 2)
        w = rng.standard_normal(hi - lo).astype(np.float32)
        r = rng.standard_normal(n).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(tb.matvec(jnp.asarray(w))),
            dense[:, lo:hi] @ w.astype(np.float64),
            rtol=2e-3, atol=2e-3,
        )
        np.testing.assert_allclose(
            np.asarray(tb.rmatvec(jnp.asarray(r))),
            dense[:, lo:hi].T @ r.astype(np.float64),
            rtol=2e-3, atol=2e-3,
        )
