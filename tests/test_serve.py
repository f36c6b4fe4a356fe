"""Online serving subsystem tests (store / router / refresh / manifest).

Host-side coverage of the serving loop's contracts:

- **hot working set** — the ``HotModelStore``'s byte-budgeted LRU over
  per-entity coefficient shards matches a reference OrderedDict LRU
  step-for-step under a Zipf request trace (hits, misses, evictions,
  byte counters through the PR-4 registry), and padding / out-of-range
  rows never touch it (hit rate stays a deterministic function of the
  trace, independent of window boundaries);
- **micro-window flush edges** — max-wait fires a PARTIAL window
  (injected clock, float-identical deadline expression), a
  single-request window scores correctly, and a burst larger than
  max-batch flushes back-to-back FULL windows during submit;
- **parity** — serve-path window scores are BYTE-identical to the batch
  ``score`` driver (``GameTransformer.transform``) over the same rows,
  and ``refresh_entity`` (the chunked warm-start solve) is BYTE-identical
  to ``solve_entity_offline`` (L-BFGS and OWL-QN arms), with every
  untouched entity's bytes unchanged across a refresh;
- **published-model manifest** — atomic pointer commit
  (crash-simulation: a die-mid-write leaves the previous complete
  manifest + snapshot intact, the test_telemetry.py atomic-writer
  idiom), monotone seq, fingerprint peek, future-schema refusal;
- one slow gloo drill: cross-owner routing over the framed P2P
  (``serve_step_collective``) and a mid-serve peer kill degrading in
  place (PeerLost → roll call → survivor group → re-planned ownership →
  retried step), scores bitwise vs the batch driver throughout.
"""

import json
import os
import socket
import subprocess
import sys
import textwrap
from collections import OrderedDict

import numpy as np
import pytest

import jax.numpy as jnp

from photon_ml_tpu.config import OptimizerConfig
from photon_ml_tpu.game.data import make_game_batch
from photon_ml_tpu.game.models import (
    FixedEffectModel,
    GameModel,
    RandomEffectModel,
)
from photon_ml_tpu.models.glm import Coefficients, GeneralizedLinearModel
from photon_ml_tpu.obs.metrics import REGISTRY
from photon_ml_tpu.serve.loadgen import (
    open_loop_arrivals,
    run_serve_trace,
    zipf_entity_trace,
)
from photon_ml_tpu.serve.refresh import (
    RefreshBuffer,
    entity_event_batch,
    refresh_entity,
    solve_entity_offline,
)
from photon_ml_tpu.serve.router import MicroWindowServer, ScoreRequest
from photon_ml_tpu.serve.store import HotModelStore
from photon_ml_tpu.transformers import GameTransformer


def _u32(a) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(a, np.float32)).view(np.uint32)


def _game_model(E: int = 32, d_fe: int = 4, d_re: int = 3, seed: int = 0):
    """fixed + one per-member random effect, float32, deterministic."""
    rng = np.random.default_rng(seed)
    return GameModel(models={
        "fixed": FixedEffectModel(
            model=GeneralizedLinearModel(Coefficients(
                jnp.asarray((rng.normal(size=d_fe) * 0.5).astype(np.float32))
            )),
            feature_shard_id="global",
        ),
        "per_member": RandomEffectModel(
            coefficients=jnp.asarray(
                (rng.normal(size=(E, d_re)) * 0.5).astype(np.float32)
            ),
            variances=None,
            random_effect_type="member",
            feature_shard_id="member_f",
        ),
    })


def _requests(model, n: int, seed: int, entities=None):
    E = int(np.asarray(model["per_member"].coefficients).shape[0])
    d_fe = int(model["fixed"].coefficient_means.shape[0])
    d_re = int(np.asarray(model["per_member"].coefficients).shape[1])
    rng = np.random.default_rng(seed)
    ents = (
        np.asarray(entities)
        if entities is not None
        else rng.integers(0, E, size=n)
    )
    return [
        ScoreRequest(
            rid=i,
            features={
                "global": rng.normal(size=d_fe).astype(np.float32),
                "member_f": rng.normal(size=d_re).astype(np.float32),
            },
            id_tags={"member": int(ents[i])},
            offset=float((i % 5) * 0.1),
        )
        for i in range(n)
    ]


def _batch_driver_scores(model, reqs) -> np.ndarray:
    """The batch ``score`` driver over the same rows — the serve-path
    parity anchor."""
    batch = make_game_batch(
        labels=np.zeros(len(reqs), np.float32),
        features={
            "global": np.stack([r.features["global"] for r in reqs]),
            "member_f": np.stack([r.features["member_f"] for r in reqs]),
        },
        id_tags={
            "member": np.asarray(
                [r.id_tags["member"] for r in reqs], np.int64
            )
        },
        offsets=np.asarray([r.offset for r in reqs], np.float32),
    )
    return np.asarray(GameTransformer(model).transform(batch), np.float32)


class _FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t


# ---------------------------------------------------------------------------
# hot working set: LRU accounting under a Zipf trace
# ---------------------------------------------------------------------------
class TestHotModelStore:
    def test_zipf_trace_matches_reference_lru(self):
        """Hits/misses/evictions and the registry byte counters agree
        step-for-step with a reference OrderedDict LRU of the same row
        capacity, over a Zipf(1) trace."""
        E, d_re = 64, 4
        model = _game_model(E=E, d_re=d_re, seed=3)
        row_bytes = d_re * 4  # float32
        cap_rows = 12
        store = HotModelStore(model, budget_bytes=cap_rows * row_bytes)
        ids = zipf_entity_trace(E, 2000, rng=np.random.default_rng(7))

        REGISTRY.reset("serve.hot.")
        lru: OrderedDict = OrderedDict()
        hits = misses = evictions = 0
        for e in ids:
            e = int(e)
            got = store.shard_for("per_member", e)
            np.testing.assert_array_equal(
                _u32(got), _u32(store.host_row("per_member", e))
            )
            if e in lru:
                hits += 1
                lru.move_to_end(e)
            else:
                misses += 1
                lru[e] = True
                if len(lru) > cap_rows:
                    lru.popitem(last=False)
                    evictions += 1
        assert (store._hits, store._misses) == (hits, misses)
        assert store.hit_rate() == pytest.approx(hits / (hits + misses))
        counters = REGISTRY.snapshot("serve.hot.")["counters"]
        assert counters["serve.hot.hit_bytes"]["value"] == hits * row_bytes
        assert counters["serve.hot.miss_bytes"]["value"] == misses * row_bytes
        assert counters["serve.hot.evictions"]["value"] == evictions
        # budget held throughout (equal-size rows: exactly cap_rows kept)
        st = store.stats()
        assert st["bytes"] <= store.budget_bytes()
        assert st["entries"] == cap_rows
        assert st["hit_rate"] == store.hit_rate()

    def test_budget_resolution_explicit_env_default(self, monkeypatch):
        model = _game_model(E=16, d_re=4)
        total = 16 * 4 * 4
        monkeypatch.delenv("PHOTON_SERVE_HOT_BYTES", raising=False)
        store = HotModelStore(model)
        assert store.total_re_bytes == total
        # knob unset -> the 25%-of-RE-bytes default
        assert store.budget_bytes() == total // 4
        # env knob wins over the default, read at CALL time
        monkeypatch.setenv("PHOTON_SERVE_HOT_BYTES", "96")
        assert store.budget_bytes() == 96
        # an explicit constructor budget wins over the env
        pinned = HotModelStore(model, budget_bytes=32)
        assert pinned.budget_bytes() == 32

    def test_invalid_rows_bypass_hot_set(self):
        """Window padding and out-of-range ids get the zero row WITHOUT
        touching the hot set — the hit rate stays a deterministic
        function of the request trace."""
        model = _game_model(E=8, d_re=3)
        store = HotModelStore(model, budget_bytes=1 << 20)
        ids = np.asarray([2, 0, 5, 0])
        valid = np.asarray([True, False, True, False])
        rows = np.asarray(store.rows_for("per_member", ids, valid=valid))
        np.testing.assert_array_equal(
            _u32(rows[0]), _u32(store.host_row("per_member", 2))
        )
        np.testing.assert_array_equal(
            _u32(rows[2]), _u32(store.host_row("per_member", 5))
        )
        np.testing.assert_array_equal(rows[1], np.zeros(3, np.float32))
        np.testing.assert_array_equal(rows[3], np.zeros(3, np.float32))
        # only the two valid lanes were counted (both cold: misses)
        assert (store._hits, store._misses) == (0, 2)
        # an out-of-range id through shard_for is a zero row, not a miss
        z = store.shard_for("per_member", 99)
        np.testing.assert_array_equal(z, np.zeros(3, np.float32))
        assert (store._hits, store._misses) == (0, 2)


# ---------------------------------------------------------------------------
# micro-window flush edges
# ---------------------------------------------------------------------------
class TestMicroWindowFlush:
    def _server(self, model, clock, max_batch=8, max_wait_ms=5.0):
        store = HotModelStore(model, budget_bytes=1 << 20)
        flushed = []
        server = MicroWindowServer(
            store,
            on_scores=lambda window, scores: flushed.append(
                (list(window), np.asarray(scores))
            ),
            max_batch=max_batch,
            max_wait_ms=max_wait_ms,
            clock=clock,
        )
        return store, server, flushed

    def test_max_wait_fires_with_partial_batch(self):
        model = _game_model()
        clock = _FakeClock()
        _, server, flushed = self._server(model, clock)
        reqs = _requests(model, 3, seed=1)
        for r in reqs:
            server.submit(r)
        assert server.windows == 0 and not flushed  # 3 < max_batch
        # just before the deadline: nothing fires
        server.poll(now=0.005 - 1e-9)
        assert server.windows == 0
        # exactly at next_deadline(): the float-identity contract — a
        # caller that sleeps to the deadline must observe the flush
        deadline = server.next_deadline()
        assert deadline == 0.0 + 5.0 / 1e3
        server.poll(now=deadline)
        assert server.windows == 1
        window, scores = flushed[0]
        assert [r.rid for r in window] == [0, 1, 2]
        assert scores.shape == (3,)
        assert server.occupancy_mean() == pytest.approx(3 / 8)
        assert server.next_deadline() is None  # queue drained

    def test_single_request_window(self):
        model = _game_model()
        clock = _FakeClock()
        _, server, flushed = self._server(model, clock)
        reqs = _requests(model, 1, seed=2)
        server.submit(reqs[0])
        clock.t = 1.0
        server.poll()
        assert server.windows == 1
        _, scores = flushed[0]
        np.testing.assert_array_equal(
            _u32(scores), _u32(_batch_driver_scores(model, reqs))
        )

    def test_burst_larger_than_max_batch(self):
        """A burst > max-batch flushes back-to-back FULL windows inside
        submit; drain() takes the partial tail. Scores stay in submit
        order and bitwise-match the batch driver."""
        model = _game_model()
        clock = _FakeClock()
        _, server, flushed = self._server(model, clock, max_batch=4)
        reqs = _requests(model, 11, seed=3)
        for r in reqs:
            server.submit(r)
        assert server.windows == 2  # two full windows flushed mid-burst
        assert len(server._pending) == 3
        server.drain()
        assert server.windows == 3 and not server._pending
        assert [len(w) for w, _ in flushed] == [4, 4, 3]
        assert [r.rid for w, _ in flushed for r in w] == list(range(11))
        got = np.concatenate([s for _, s in flushed])
        np.testing.assert_array_equal(
            _u32(got), _u32(_batch_driver_scores(model, reqs))
        )

    def test_window_scores_match_batch_driver_with_out_of_range(self):
        """Serve-path scores over a mixed trace — including out-of-range
        entity ids, whose random-effect contribution must mask to 0
        exactly like ``RandomEffectModel.score`` — are byte-identical to
        the batch driver."""
        model = _game_model(E=16)
        ents = np.random.default_rng(4).integers(0, 16, size=40)
        ents[5] = -1
        ents[17] = 16  # == E: out of range
        ents[23] = 21
        reqs = _requests(model, 40, seed=4, entities=ents)
        clock = _FakeClock()
        _, server, flushed = self._server(model, clock, max_batch=8)
        for r in reqs:
            server.submit(r)
        server.drain()
        got = np.concatenate([s for _, s in flushed])
        np.testing.assert_array_equal(
            _u32(got), _u32(_batch_driver_scores(model, reqs))
        )


# ---------------------------------------------------------------------------
# incremental refresh: bitwise parity + untouched-entity byte identity
# ---------------------------------------------------------------------------
class TestRefreshParity:
    @pytest.mark.parametrize("l1_weight", [0.0, 0.05])
    def test_refresh_bitwise_matches_offline_solve(self, l1_weight):
        """The chunked warm-start refresh reproduces the one-shot offline
        solve of the same bucket BITWISE — both the smooth L-BFGS arm and
        the OWL-QN arm (l1 > 0) — and replaces exactly one row."""
        model = _game_model(E=16, d_re=3, seed=5)
        W0 = np.array(np.asarray(model["per_member"].coefficients))
        entity, k = 6, 12
        rng = np.random.default_rng(6)
        X = rng.normal(size=(k, 3)).astype(np.float32)
        y = (rng.uniform(size=k) < 0.5).astype(np.float32)
        batch = entity_event_batch(X, y)
        cfg = OptimizerConfig(max_iterations=40, tolerance=1e-7)

        updated, res = refresh_entity(
            model, "per_member", entity, batch, cfg,
            l2_weight=1.0, l1_weight=l1_weight,
        )
        offline = solve_entity_offline(
            model["per_member"], entity, batch, cfg,
            l2_weight=1.0, l1_weight=l1_weight,
        )
        np.testing.assert_array_equal(_u32(res.w), _u32(offline.w))
        W1 = np.asarray(updated["per_member"].coefficients)
        np.testing.assert_array_equal(_u32(W1[entity]), _u32(res.w))
        # the refresh moved the row (the events weren't a no-op)...
        assert not np.array_equal(_u32(W1[entity]), _u32(W0[entity]))
        # ...and every OTHER entity's bytes are untouched
        mask = np.arange(16) != entity
        np.testing.assert_array_equal(_u32(W1[mask]), _u32(W0[mask]))

    def test_entity_event_batch_pads_pow2_with_inert_rows(self):
        X = np.ones((5, 3), np.float32)
        y = np.ones((5,), np.float32)
        batch = entity_event_batch(X, y)
        assert batch.X.shape == (8, 3)
        np.testing.assert_array_equal(
            np.asarray(batch.weights), [1, 1, 1, 1, 1, 0, 0, 0]
        )
        np.testing.assert_array_equal(np.asarray(batch.X[5:]), 0.0)

    def test_refresh_buffer_trigger_knob(self, monkeypatch):
        monkeypatch.setenv("PHOTON_SERVE_REFRESH_EVERY", "3")
        buf = RefreshBuffer()
        x = np.ones(3, np.float32)
        assert buf.add("per_member", 4, x, 1.0) is False
        assert buf.add("per_member", 4, x, 0.0) is False
        assert buf.count("per_member", 4) == 2
        assert buf.add("per_member", 4, x, 1.0) is True  # threshold hit
        batch = buf.pop_ready("per_member", 4)
        assert batch is not None and batch.X.shape == (4, 3)
        np.testing.assert_array_equal(
            np.asarray(batch.weights), [1, 1, 1, 0]
        )
        assert buf.count("per_member", 4) == 0
        assert buf.pop_ready("per_member", 4) is None
        # knob 0 disables triggering; events still buffer
        monkeypatch.setenv("PHOTON_SERVE_REFRESH_EVERY", "0")
        for _ in range(5):
            assert buf.add("per_member", 9, x, 1.0) is False
        assert buf.count("per_member", 9) == 5

    def test_install_refreshed_row_drops_stale_hot_shard(self):
        """Publishing a refreshed row into a live store replaces the cold
        row bit-for-bit, drops the stale DEVICE shard (next access
        re-admits the fresh bytes), and leaves every other entity's
        serve-path scores byte-identical."""
        model = _game_model(E=8, d_re=3, seed=7)
        store = HotModelStore(model, budget_bytes=1 << 20)
        stale = np.array(store.host_row("per_member", 2))
        store.shard_for("per_member", 2)  # warm the shard (miss)
        store.shard_for("per_member", 2)  # hit
        assert (store._hits, store._misses) == (1, 1)

        others = _requests(model, 12, seed=8,
                           entities=np.asarray([0, 1, 3, 4, 5, 6, 7] * 2)[:12])
        before = _serve_scores(store, others)

        fresh = np.asarray([1.25, -2.5, 0.5], np.float32)
        store.install_refreshed_row("per_member", 2, fresh)
        np.testing.assert_array_equal(
            _u32(store.host_row("per_member", 2)), _u32(fresh)
        )
        assert not np.array_equal(_u32(stale), _u32(fresh))
        # the stale hot shard was dropped: the next access is a MISS and
        # returns the fresh bytes
        hits0, misses0 = store._hits, store._misses
        got = store.shard_for("per_member", 2)
        np.testing.assert_array_equal(_u32(got), _u32(fresh))
        assert (store._hits, store._misses) == (hits0, misses0 + 1)
        # untouched entities score byte-identically across the refresh
        after = _serve_scores(store, others)
        np.testing.assert_array_equal(_u32(before), _u32(after))
        # the store's model view carries the refreshed row too
        np.testing.assert_array_equal(
            _u32(np.asarray(store.model["per_member"].coefficients)[2]),
            _u32(fresh),
        )


def _serve_scores(store: HotModelStore, reqs) -> np.ndarray:
    out = []
    server = MicroWindowServer(
        store,
        on_scores=lambda w, s: out.append(np.asarray(s)),
        max_batch=4,
        max_wait_ms=1000.0,
        clock=_FakeClock(),
    )
    for r in reqs:
        server.submit(r)
    server.drain()
    return np.concatenate(out)


# ---------------------------------------------------------------------------
# open-loop load generator
# ---------------------------------------------------------------------------
class TestLoadgen:
    def test_zipf_trace_shape_and_range(self):
        ids = zipf_entity_trace(32, 500, rng=np.random.default_rng(0))
        assert ids.shape == (500,)
        assert ids.min() >= 0 and ids.max() < 32
        # Zipf(1): the head entity dominates a uniform draw's share
        top = np.bincount(ids, minlength=32).max()
        assert top > 500 / 32 * 3

    def test_open_loop_arrivals_monotone(self):
        t = open_loop_arrivals(200, 1000.0, rng=np.random.default_rng(1))
        assert t.shape == (200,)
        assert np.all(np.diff(t) >= 0) and t[0] >= 0

    def test_run_serve_trace_summary_contract(self):
        model = _game_model(E=16)
        store = HotModelStore(model, budget_bytes=1 << 20)
        reqs = _requests(model, 64, seed=9)
        arrivals = open_loop_arrivals(
            64, 5000.0, rng=np.random.default_rng(2)
        )
        for r, t in zip(reqs, arrivals):
            r.arrival_s = float(t)
        summary = run_serve_trace(store, reqs, max_batch=8, max_wait_ms=1.0)
        assert summary["requests"] == 64
        assert summary["windows"] >= 64 // 8
        assert len(summary["scores"]) == 64
        for key in ("latency_p50_ms", "latency_p99_ms", "latency_mean_ms",
                    "hot_hit_rate", "window_occupancy_mean", "elapsed_s"):
            assert key in summary, key
        assert summary["latency_p99_ms"] >= summary["latency_p50_ms"] >= 0
        # scores ride the open-loop path bitwise-equal to the batch driver
        got = np.asarray(
            [summary["scores"][r.rid] for r in reqs], np.float32
        )
        np.testing.assert_array_equal(
            _u32(got), _u32(_batch_driver_scores(model, reqs))
        )
        gauges = REGISTRY.snapshot("serve.")["gauges"]
        assert gauges["serve.latency_p50_ms"] == summary["latency_p50_ms"]
        assert gauges["serve.hot.hit_rate"] == summary["hot_hit_rate"]


# ---------------------------------------------------------------------------
# published-model manifest (atomic pointer, crash-simulation)
# ---------------------------------------------------------------------------
class TestPublishedManifest:
    def test_publish_seq_fingerprint_and_load(self, tmp_path):
        from photon_ml_tpu.io.model_io import (
            load_published_model,
            model_fingerprint,
            peek_published_fingerprint,
            publish_game_model,
            read_model_manifest,
        )

        root = str(tmp_path / "pub")
        a = _game_model(seed=11)
        b = _game_model(seed=12)
        snap1 = publish_game_model(a, root)
        m1 = read_model_manifest(root)
        assert m1["seq"] == 1 and m1["schema_version"] == 1
        assert os.path.isdir(snap1)
        assert peek_published_fingerprint(root) == model_fingerprint(a)

        publish_game_model(b, root)
        m2 = read_model_manifest(root)
        assert m2["seq"] == 2
        assert peek_published_fingerprint(root) == model_fingerprint(b)
        loaded, manifest = load_published_model(root)
        assert manifest["seq"] == 2
        # round-trip preserves the coefficient bytes: fingerprints agree
        assert model_fingerprint(loaded) == model_fingerprint(b)
        np.testing.assert_array_equal(
            _u32(np.asarray(loaded["per_member"].coefficients)),
            _u32(np.asarray(b["per_member"].coefficients)),
        )

    def test_crash_mid_commit_never_shadows_previous(
        self, tmp_path, monkeypatch
    ):
        """A publish dying mid-pointer-commit (first fsync of the atomic
        write) leaves the PREVIOUS manifest intact and pointing at a
        complete, loadable snapshot — and no tmp turds. The orphan
        snapshot directory from the failed publish is inert."""
        from photon_ml_tpu.io.model_io import (
            load_published_model,
            model_fingerprint,
            publish_game_model,
            read_model_manifest,
        )

        root = str(tmp_path / "pub")
        a = _game_model(seed=13)
        b = _game_model(seed=14)
        publish_game_model(a, root)

        class Boom(RuntimeError):
            pass

        real_fsync = os.fsync

        def dying_fsync(fd):
            raise Boom()

        monkeypatch.setattr(os, "fsync", dying_fsync)
        with pytest.raises(Boom):
            publish_game_model(b, root)
        monkeypatch.setattr(os, "fsync", real_fsync)

        manifest = read_model_manifest(root)
        assert manifest["seq"] == 1
        assert manifest["fingerprint"] == model_fingerprint(a)
        loaded, _ = load_published_model(root)
        assert model_fingerprint(loaded) == model_fingerprint(a)
        assert [f for f in os.listdir(root) if f.endswith(".tmp")] == []
        # a RE-publish after the crash resumes the seq ladder past the
        # orphan (the orphan snap dir is simply overwritten)
        publish_game_model(b, root)
        assert read_model_manifest(root)["seq"] == 2
        loaded2, _ = load_published_model(root)
        assert model_fingerprint(loaded2) == model_fingerprint(b)

    def test_future_schema_refused_and_unpublished_raises(self, tmp_path):
        from photon_ml_tpu.io.model_io import (
            MODEL_MANIFEST,
            load_published_model,
            peek_published_fingerprint,
            read_model_manifest,
        )

        root = str(tmp_path / "pub")
        os.makedirs(root)
        assert read_model_manifest(root) is None
        assert peek_published_fingerprint(root) is None
        with pytest.raises(FileNotFoundError):
            load_published_model(root)
        with open(os.path.join(root, MODEL_MANIFEST), "w") as f:
            json.dump({"schema_version": 99, "seq": 1,
                       "snapshot": "snapshots/snap-000001"}, f)
        with pytest.raises(ValueError, match="schema v99"):
            read_model_manifest(root)


# ---------------------------------------------------------------------------
# slow gloo drill: cross-owner routing + mid-serve peer kill
# ---------------------------------------------------------------------------
_SERVE_WORKER = textwrap.dedent(
    """
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ.setdefault("PHOTON_P2P_RETRIES", "1")
    os.environ.setdefault("PHOTON_P2P_BACKOFF_S", "0.1")
    os.environ.setdefault("PHOTON_P2P_TIMEOUT_S", "2")
    os.environ.setdefault("PHOTON_ROLLCALL_WINDOW_S", "2")
    # the repo's roll-call tier, not the jax coordination service,
    # decides who is dead — without this the service FATALs the
    # survivor ~100 s after the kill
    os.environ.setdefault("PHOTON_COORD_MAX_MISSING_HEARTBEATS", "360")
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    coordinator, pid = sys.argv[1], int(sys.argv[2])

    import numpy as np
    from photon_ml_tpu.parallel import multihost as mh

    mh.initialize_multihost(coordinator, num_processes=2, process_id=pid)

    import jax.numpy as jnp
    from photon_ml_tpu.game.data import make_game_batch
    from photon_ml_tpu.game.models import (
        FixedEffectModel, GameModel, RandomEffectModel,
    )
    from photon_ml_tpu.models.glm import (
        Coefficients, GeneralizedLinearModel,
    )
    from photon_ml_tpu.obs.metrics import REGISTRY
    from photon_ml_tpu.serve.router import (
        EntityRouter, MicroWindowServer, ScoreRequest,
        serve_step_collective,
    )
    from photon_ml_tpu.serve.store import HotModelStore
    from photon_ml_tpu.transformers import GameTransformer

    E, d_fe, d_re = 32, 4, 3
    rng = np.random.default_rng(0)  # SAME seed on both pids
    model = GameModel(models={
        "fixed": FixedEffectModel(
            model=GeneralizedLinearModel(Coefficients(jnp.asarray(
                (rng.normal(size=d_fe) * 0.5).astype(np.float32)
            ))),
            feature_shard_id="global",
        ),
        "per_member": RandomEffectModel(
            coefficients=jnp.asarray(
                (rng.normal(size=(E, d_re)) * 0.5).astype(np.float32)
            ),
            variances=None, random_effect_type="member",
            feature_shard_id="member_f",
        ),
    })
    store = HotModelStore(model, budget_bytes=1 << 20)
    server = MicroWindowServer(store, max_batch=8, max_wait_ms=0.0)
    # traffic-weighted ownership: identical plan on both pids
    weights = np.ones(E); weights[:4] = 50.0
    router = EntityRouter(weights, 2)
    SHARDS = ("global", "member_f")
    DIMS = {"global": d_fe, "member_f": d_re}

    def make_requests(n, seed, entities):
        r = np.random.default_rng(seed)
        return [
            ScoreRequest(
                rid=pid * 100000 + i,
                features={
                    "global": r.normal(size=d_fe).astype(np.float32),
                    "member_f": r.normal(size=d_re).astype(np.float32),
                },
                id_tags={"member": int(entities[i])},
                offset=float((i % 3) * 0.1),
            )
            for i in range(n)
        ]

    def reference(reqs):
        batch = make_game_batch(
            labels=np.zeros(len(reqs), np.float32),
            features={
                "global": np.stack([q.features["global"] for q in reqs]),
                "member_f": np.stack(
                    [q.features["member_f"] for q in reqs]
                ),
            },
            id_tags={"member": np.asarray(
                [q.id_tags["member"] for q in reqs], np.int64
            )},
            offsets=np.asarray([q.offset for q in reqs], np.float32),
        )
        return np.asarray(
            GameTransformer(model).transform(batch), np.float32
        )

    def u32(a):
        return np.ascontiguousarray(
            np.asarray(a, np.float32)
        ).view(np.uint32)

    # -- step 1 (healthy): cross-owner routing, scores bitwise ---------
    ents1 = np.random.default_rng(10 + pid).integers(0, E, size=24)
    reqs1 = make_requests(24, 20 + pid, ents1)
    scores1 = serve_step_collective(
        server, router, reqs1, "member", SHARDS, shard_dims=DIMS
    )
    mm1 = int((u32(scores1) != u32(reference(reqs1))).sum())
    fwd = REGISTRY.snapshot("serve.")["counters"].get(
        "serve.forwarded", {"value": 0.0}
    )["value"]

    # collective warm-up of the framed P2P mesh: the FIRST link build
    # bootstraps addresses collectively; the post-kill rebuild then
    # runs collective-free from the cached addresses
    mh.allgather_obj_p2p({"pid": pid}, tag="serve_warmup")

    if pid == 1:
        print("RESULT " + json.dumps({
            "pid": pid, "mm1": mm1, "forwarded": fwd,
        }))
        sys.stdout.flush()
        # die INSIDE the collective serving step, after the counts
        # allgather but before the framed exchange — the survivor's
        # recv hardens into PeerLost
        mh._host_p2p_exchange = lambda *a, **k: os._exit(0)

    # -- step 2: heavily-skewed window (forces the framed-P2P
    # transport); pid 1 dies inside it -------------------------------
    owned0 = [e for e in range(E) if router.owner_of(e) == 0]
    n2 = 48 if pid == 0 else 12
    ents2 = np.asarray(
        [owned0[i % len(owned0)] for i in range(n2)], np.int64
    )
    reqs2 = make_requests(n2, 30 + pid, ents2)
    peer_lost = False
    try:
        scores2 = serve_step_collective(
            server, router, reqs2, "member", SHARDS, shard_dims=DIMS
        )
    except mh.PeerLost:
        peer_lost = True
        survivors = mh.roll_call()
        assert survivors == [0], survivors
        mh.set_degraded_group(survivors)
        router.replan(weights, survivors)
        # degrade in place: the SAME step retried on the survivor mesh
        scores2 = serve_step_collective(
            server, router, reqs2, "member", SHARDS, shard_dims=DIMS
        )
    mm2 = int((u32(scores2) != u32(reference(reqs2))).sum())

    print("RESULT " + json.dumps({
        "pid": pid, "mm1": mm1, "forwarded": fwd,
        "peer_lost": peer_lost, "mm2": mm2,
        "survivors": list(mh.degraded_group()["survivors"]),
        "giveups": REGISTRY.snapshot("p2p.")["counters"].get(
            "p2p.giveups", {"value": 0.0}
        )["value"],
    }))
    sys.stdout.flush()
    # skip the jax.distributed shutdown handshake with a dead peer
    os._exit(0)
    """
)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_two_process_serve_routes_cross_owner_and_degrades_on_kill():
    """Cross-owner request routing over the framed P2P, then a mid-serve
    peer kill: the survivor's exchange hardens into PeerLost, it degrades
    in place (roll call → survivor group → re-planned ownership) and
    retries the SAME serving step — scores bitwise vs the batch driver
    before AND after the loss."""
    coordinator = f"127.0.0.1:{_free_port()}"
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
    }
    cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = {
        pid: subprocess.Popen(
            [sys.executable, "-c", _SERVE_WORKER, coordinator, str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=cwd,
        )
        for pid in range(2)
    }
    results = {}
    errs = {}
    for pid, p in procs.items():
        out, err = p.communicate(timeout=300)
        errs[pid] = err
        # pid 1 hard-exits mid-serve BY DESIGN; pid 0 must succeed
        if pid == 0:
            assert p.returncode == 0, (
                f"survivor failed (rc {p.returncode}):\n{out}\n{err[-6000:]}"
            )
        for line in out.splitlines():
            if line.startswith("RESULT "):
                results[pid] = json.loads(line[len("RESULT "):])
    assert set(results) == {0, 1}, errs

    # step 1: both sides scored bitwise vs the batch driver, and real
    # cross-owner traffic rode the exchange
    assert results[0]["mm1"] == 0 and results[1]["mm1"] == 0
    assert results[0]["forwarded"] + results[1]["forwarded"] > 0

    # step 2: the survivor saw the loss, degraded to itself, and the
    # retried step still matches the batch driver bitwise
    survivor = results[0]
    assert survivor["peer_lost"] is True
    assert survivor["survivors"] == [0]
    assert survivor["mm2"] == 0
    # the link layer exhausted its retry budget against the dead peer
    assert survivor["giveups"] >= 1.0
