"""Host-side tests for the owner-segment combine + telemetry-driven
re-planning (ISSUE 12): the raw-ndarray host-collective codec, the ring
allgather schedule over real sockets, owner-segment packing/offsets and
disjoint-row reassembly (empty owner / single bucket / V=None edges),
the PHOTON_RE_COMBINE / PHOTON_RE_REPLAN_IMBALANCE / PHOTON_RE_STRAGGLER
knob parses, measured-cost re-planning, and the report/gate surface for
``re_combine.*`` / ``re_replan.*``. The cross-process bitwise/byte
assertions live in the slow gloo harness (tests/test_multihost.py)."""

from __future__ import annotations

import json
import socket
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.parallel import multihost as mh

from collections import namedtuple

_Pt = namedtuple("_Pt", "a b")  # module-level: pickles by reference


class TestHostPayloadCodec:
    """Raw-ndarray wire format: byte-identical values, no pickle per
    array, writable results (the pickle contract)."""

    def roundtrip(self, obj):
        parts, total = mh._encode_host_payload(obj)
        raw = b"".join(bytes(p) for p in parts)
        assert len(raw) == total
        return mh._decode_host_payload(raw)

    def test_array_container_roundtrip_bitwise(self):
        obj = {
            "f32": np.arange(12, dtype=np.float32).reshape(3, 4),
            "f64": np.linspace(0, 1, 7),
            "i64": np.array([-(2**62), 2**62], np.int64),
            "bool": np.array([True, False]),
            "nested": [
                (np.float32(1.5), np.zeros((2, 0, 3), np.float32)),
                {"k": np.arange(5, dtype=np.int32)},
            ],
            "scalar": 7,
            "s": "text",
        }
        back = self.roundtrip(obj)
        np.testing.assert_array_equal(back["f32"], obj["f32"])
        assert back["f32"].dtype == np.float32
        np.testing.assert_array_equal(back["f64"], obj["f64"])
        np.testing.assert_array_equal(back["i64"], obj["i64"])
        np.testing.assert_array_equal(back["bool"], obj["bool"])
        assert back["nested"][0][1].shape == (2, 0, 3)
        np.testing.assert_array_equal(
            back["nested"][1]["k"], obj["nested"][1]["k"]
        )
        assert back["scalar"] == 7 and back["s"] == "text"

    def test_arrays_come_back_writable(self):
        back = self.roundtrip([np.arange(4, dtype=np.float32)])
        assert back[0].flags.writeable
        back[0][0] = 9.0  # the pickle format allowed in-place writes

    def test_non_contiguous_input(self):
        a = np.arange(20, dtype=np.float32).reshape(4, 5)[:, ::2]
        back = self.roundtrip({"a": a})
        np.testing.assert_array_equal(back["a"], a)

    def test_zero_dim_array_keeps_shape(self):
        # ascontiguousarray promotes 0-d to 1-d; the spec must record
        # the ORIGINAL shape so peers see () like the sender's own rank
        back = self.roundtrip({"x": np.array(3.5), "y": np.arange(2)})
        assert back["x"].shape == ()
        assert float(back["x"]) == 3.5

    def test_namedtuple_survives_array_format(self):
        back = self.roundtrip({"p": _Pt(a=np.arange(2), b=1)})
        assert back["p"].a.tolist() == [0, 1] and back["p"].b == 1
        assert isinstance(back["p"], _Pt)

    def test_structured_dtype_and_subclass_keep_pickle_path(self):
        # structured dtypes (dtype.str is lossy) and ndarray subclasses
        # (MaskedArray carries a mask) must round-trip via pickle even
        # when a plain array rides the raw format alongside them
        rec = np.zeros(3, dtype=[("a", "i4"), ("b", "f8")])
        rec["a"] = [1, 2, 3]
        masked = np.ma.masked_array([1.0, 2.0], mask=[False, True])
        back = self.roundtrip(
            {"rec": rec, "m": masked, "plain": np.arange(4)}
        )
        assert back["rec"].dtype.names == ("a", "b")
        np.testing.assert_array_equal(back["rec"]["a"], [1, 2, 3])
        assert isinstance(back["m"], np.ma.MaskedArray)
        assert back["m"].mask.tolist() == [False, True]
        np.testing.assert_array_equal(back["plain"], np.arange(4))

    def test_no_array_payload_falls_back_to_pickle(self):
        parts, _ = mh._encode_host_payload({"x": 1, "y": ("z", None)})
        assert bytes(parts[0])[0] == mh._PAYLOAD_PICKLE
        assert self.roundtrip({"x": 1}) == {"x": 1}

    def test_object_dtype_array_falls_back_to_pickle(self):
        oarr = np.array([{"k": 1}, None], dtype=object)
        parts, _ = mh._encode_host_payload([oarr])
        assert bytes(parts[0])[0] == mh._PAYLOAD_PICKLE
        back = self.roundtrip([oarr])
        assert back[0][0] == {"k": 1} and back[0][1] is None

    def test_array_payload_uses_raw_format(self):
        parts, _ = mh._encode_host_payload(np.arange(3))
        assert bytes(parts[0])[0] == mh._PAYLOAD_NDARRAY

    def test_unknown_wire_format_raises(self):
        with pytest.raises(RuntimeError, match="unknown wire format"):
            mh._decode_host_payload(b"\x7fjunk")


def _pair_links():
    """Two in-process 'ranks' wired with real sockets: links dicts in
    the exact shape ``_ring_allgather`` consumes."""
    a01, b01 = socket.socketpair()  # 0 -> 1
    a10, b10 = socket.socketpair()  # 1 -> 0
    links0 = {"send": {1: a01}, "recv": {1: b10}, "proto": {}}
    links1 = {"send": {0: a10}, "recv": {0: b01}, "proto": {}}
    return links0, links1, (a01, b01, a10, b10)


class TestRingAllgather:
    """The ring schedule over real sockets (single process, two
    threads): per-rank ordering, array payloads, byte stats."""

    def test_two_rank_ring_and_stats(self):
        links0, links1, socks = _pair_links()
        obj0 = {"w": np.arange(6, dtype=np.float32), "who": 0}
        obj1 = {"w": np.arange(8, dtype=np.float64) * 2, "who": 1}
        out = {}
        stats0, stats1 = {}, {}

        def run1():
            out[1] = mh._ring_allgather(
                links1, [0, 1], 1, obj1, "t", None, stats=stats1
            )

        t = threading.Thread(target=run1)
        t.start()
        out[0] = mh._ring_allgather(
            links0, [0, 1], 0, obj0, "t", None, stats=stats0
        )
        t.join()
        for sock in socks:
            sock.close()
        for rank in (0, 1):
            views = out[rank]
            assert views[0]["who"] == 0 and views[1]["who"] == 1
            np.testing.assert_array_equal(views[0]["w"], obj0["w"])
            np.testing.assert_array_equal(views[1]["w"], obj1["w"])
            assert views[1]["w"].dtype == np.float64
        # stats: one peer -> bytes_sent == payload, recv == peer payload
        assert stats0["bytes_sent"] == stats0["payload_bytes"]
        assert stats0["bytes_recv"] == stats1["payload_bytes"]
        assert stats1["bytes_recv"] == stats0["payload_bytes"]

    def test_single_process_identity_paths(self):
        st = {}
        assert mh.allgather_obj_p2p("x", stats=st) == ["x"]
        assert st == {"payload_bytes": 0, "bytes_sent": 0, "bytes_recv": 0}
        st2 = {}
        h = mh.allgather_obj_p2p_async({"a": 1}, stats=st2)
        assert h.result() == [{"a": 1}]
        assert st2["exchange_s"] == 0.0


# -- owner-segment packing / reassembly --------------------------------------


def _fake_prepared(ent_lists, owners):
    from photon_ml_tpu.game.random_effect import PreparedBucket

    return [
        PreparedBucket(
            entity_ids=np.asarray(ents, np.int64), ids=None, static=None,
            row_idx=None, mask=None, num_real=len(ents), owner=owner,
        )
        for ents, owner in zip(ent_lists, owners)
    ]


def _simulate_combine(ent_lists, owners, P, d=3, with_v=True, seed=0):
    """Emulate the cross-process segment flow host-side: every rank
    packs from its own (partially-solved) matrices, then one rank
    applies all views — compared against the owner-truth reference."""
    from photon_ml_tpu.game import random_effect as re_mod

    rng = np.random.default_rng(seed)
    E = 1 + max((max(e) for e in ent_lists if len(e)), default=0)
    prepared = _fake_prepared(ent_lists, owners)
    # owner-truth: each bucket's rows/diag as solved by its owner
    truth_W = rng.normal(size=(E, d)).astype(np.float32)
    truth_V = rng.normal(size=(E, d)).astype(np.float32) if with_v else None
    truth_diag = [
        (
            rng.normal(size=len(e)).astype(np.float32),
            rng.integers(1, 9, size=len(e)).astype(np.int32),
            rng.integers(0, 3, size=len(e)).astype(np.int32),
        )
        for e in ent_lists
    ]
    wv_views, diag_views = [], []
    per_rank_state = {}
    for rank in range(P):
        owned = [i for i, o in enumerate(owners) if o == rank]
        # this rank's local matrices: correct only on its owned rows
        W_h = np.zeros((E, d), np.float32)
        V_h = np.zeros((E, d), np.float32) if with_v else None
        for i in owned:
            W_h[ent_lists[i]] = truth_W[ent_lists[i]]
            if V_h is not None:
                V_h[ent_lists[i]] = truth_V[ent_lists[i]]
        wv_views.append(
            re_mod._pack_wv_segments(prepared, W_h, V_h, owned)
        )
        diag_views.append(
            re_mod._pack_diag_segments([truth_diag[i] for i in owned])
        )
        per_rank_state[rank] = (W_h, V_h)
    # round-trip every view through the wire codec (what the ring does)
    def wire(v):
        parts, total = mh._encode_host_payload(v)
        return mh._decode_host_payload(b"".join(bytes(p) for p in parts))

    wv_views = [wire(v) for v in wv_views]
    diag_views = [wire(v) for v in diag_views]
    results = {}
    for rank in range(P):
        W_h, V_h = per_rank_state[rank]
        diag = [
            truth_diag[i] if owners[i] == rank else None
            for i in range(len(ent_lists))
        ]
        diag = re_mod._apply_owner_segments(
            prepared, W_h, V_h, diag, wv_views, diag_views, rank
        )
        results[rank] = (W_h, V_h, diag)
    return truth_W, truth_V, truth_diag, results


class TestOwnerSegments:
    def test_disjoint_reassembly_three_ranks(self):
        ents = [[0, 3], [1, 4, 6], [2], [5, 7]]
        owners = [0, 1, 1, 2]
        tw, tv, td, results = _simulate_combine(ents, owners, P=3)
        for rank, (W_h, V_h, diag) in results.items():
            np.testing.assert_array_equal(W_h, tw)
            np.testing.assert_array_equal(V_h, tv)
            for i, e in enumerate(ents):
                f, it, r = diag[i]
                np.testing.assert_array_equal(
                    np.asarray(f, np.float32), td[i][0]
                )
                np.testing.assert_array_equal(np.asarray(it), td[i][1])
                np.testing.assert_array_equal(np.asarray(r), td[i][2])
                if owners[i] != rank:
                    # non-owned diag arrives as the allreduce arm's
                    # dtypes exactly (f32 / i32 / i32 device arrays)
                    assert f.dtype == jnp.float32
                    assert it.dtype == jnp.int32 and r.dtype == jnp.int32

    def test_empty_owner_edge(self):
        # rank 1 owns nothing: ships empty segments, receives everything
        ents = [[0, 1], [2, 3]]
        owners = [0, 0]
        tw, tv, _, results = _simulate_combine(ents, owners, P=2)
        W_h, V_h, _ = results[1]
        np.testing.assert_array_equal(W_h, tw)
        np.testing.assert_array_equal(V_h, tv)

    def test_single_bucket_and_v_none(self):
        ents = [[0, 1, 2]]
        owners = [1]
        tw, tv, _, results = _simulate_combine(
            ents, owners, P=2, with_v=False
        )
        assert tv is None
        W_h, V_h, diag = results[0]
        assert V_h is None
        np.testing.assert_array_equal(W_h, tw)
        assert diag[0][0].dtype == jnp.float32

    def test_duplicate_owner_detected(self):
        from photon_ml_tpu.game import random_effect as re_mod

        prepared = _fake_prepared([[0], [1]], [0, 1])
        W_h = np.zeros((2, 2), np.float32)
        wv = [
            {"buckets": np.array([0, 1]),
             "W": np.zeros((2, 2), np.float32)},
            {"buckets": np.array([1]),
             "W": np.zeros((1, 2), np.float32)},
        ]
        dg = [
            {"F": np.zeros(2), "I": np.zeros(2, np.int64),
             "R": np.zeros(2, np.int64)},
            {"F": np.zeros(1), "I": np.zeros(1, np.int64),
             "R": np.zeros(1, np.int64)},
        ]
        with pytest.raises(RuntimeError, match="two owners"):
            re_mod._apply_owner_segments(
                prepared, W_h, None, [None, None], wv, dg, 0
            )

    def test_missing_owner_detected(self):
        from photon_ml_tpu.game import random_effect as re_mod

        prepared = _fake_prepared([[0], [1]], [0, 1])
        W_h = np.zeros((2, 2), np.float32)
        wv = [{"buckets": np.array([0]),
               "W": np.zeros((1, 2), np.float32)}]
        dg = [{"F": np.zeros(1), "I": np.zeros(1, np.int64),
               "R": np.zeros(1, np.int64)}]
        with pytest.raises(RuntimeError, match="no owner"):
            re_mod._apply_owner_segments(
                prepared, W_h, None, [None, None], wv, dg, 0
            )

    def test_pack_matches_allreduce_dtype_flow(self):
        """The segment payload's F/I/R dtypes are the dense arm's
        accumulator dtypes (f64/i64) — the float32 cast at reassembly
        is then bit-for-bit the allreduce arm's."""
        from photon_ml_tpu.game import random_effect as re_mod

        diag = [(np.float32([1.5]), np.int32([3]), np.int32([1]))]
        p = re_mod._pack_diag_segments(diag)
        assert p["F"].dtype == np.float64
        assert p["I"].dtype == np.int64 and p["R"].dtype == np.int64


class TestGatherUnaddressable:
    def test_single_process_reassembles_from_local_shards(self):
        from photon_ml_tpu.game.random_effect import (
            _gather_refs_host,
            _gather_unaddressable,
        )

        x = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
        (full,) = _gather_unaddressable([x])
        np.testing.assert_array_equal(full, np.asarray(x))
        refs = [(x[:, 0], jnp.arange(3, dtype=jnp.int32),
                 jnp.zeros(3, jnp.int32))]
        host = _gather_refs_host(refs)
        np.testing.assert_array_equal(host[0][0], np.asarray(x[:, 0]))


# -- knobs -------------------------------------------------------------------


class TestKnobs:
    def test_re_combine_default_and_env(self, monkeypatch):
        from photon_ml_tpu.game import random_effect as re_mod

        monkeypatch.delenv("PHOTON_RE_COMBINE", raising=False)
        assert re_mod.re_combine_mode() == "allreduce"
        monkeypatch.setenv("PHOTON_RE_COMBINE", "segments")
        assert re_mod.re_combine_mode() == "segments"
        monkeypatch.setenv("PHOTON_RE_COMBINE", "ring")
        with pytest.raises(ValueError, match="PHOTON_RE_COMBINE"):
            re_mod.re_combine_mode()

    def test_re_combine_module_global(self, monkeypatch):
        from photon_ml_tpu.game import random_effect as re_mod

        monkeypatch.delenv("PHOTON_RE_COMBINE", raising=False)
        monkeypatch.setattr(re_mod, "RE_COMBINE", "segments")
        assert re_mod.re_combine_mode() == "segments"

    def test_replan_threshold(self, monkeypatch):
        from photon_ml_tpu.parallel import placement

        monkeypatch.delenv("PHOTON_RE_REPLAN_IMBALANCE", raising=False)
        assert placement.replan_imbalance_threshold() == 0.0
        monkeypatch.setenv("PHOTON_RE_REPLAN_IMBALANCE", "1.4")
        assert placement.replan_imbalance_threshold() == 1.4
        monkeypatch.setenv("PHOTON_RE_REPLAN_IMBALANCE", "-1")
        assert placement.replan_imbalance_threshold() == 0.0
        monkeypatch.setenv("PHOTON_RE_REPLAN_IMBALANCE", "fast")
        with pytest.raises(ValueError):
            placement.replan_imbalance_threshold()

    def test_straggler_spec(self, monkeypatch):
        from photon_ml_tpu.parallel import faults

        monkeypatch.delenv("PHOTON_RE_STRAGGLER", raising=False)
        assert faults.straggler_spec() is None
        assert faults.maybe_straggle() == 0.0
        monkeypatch.setenv("PHOTON_RE_STRAGGLER", "1:0.25")
        assert faults.straggler_spec() == (1, 0.25)
        # this test runs as process 0 -> no sleep
        assert faults.maybe_straggle() == 0.0
        monkeypatch.setenv("PHOTON_RE_STRAGGLER", "nope")
        with pytest.raises(ValueError, match="PHOTON_RE_STRAGGLER"):
            faults.straggler_spec()

    def test_straggler_sleeps_on_named_process(self, monkeypatch):
        from photon_ml_tpu.parallel import faults

        monkeypatch.setenv("PHOTON_RE_STRAGGLER", "0:0.01")
        slept = faults.maybe_straggle()
        assert slept == 0.01


class TestMeasuredCosts:
    def test_straggler_shard_inflates_its_entities(self):
        from photon_ml_tpu.parallel.placement import measured_entity_costs

        counts = np.array([10, 10, 10, 10])
        owner = np.array([0, 0, 1, 1])
        walls = np.array([1.0, 3.0])  # shard 1 measured 3x slower
        costs = measured_entity_costs(counts, owner, walls)
        np.testing.assert_allclose(costs, [0.5, 0.5, 1.5, 1.5])

    def test_zero_wall_falls_back_to_mean_rate(self):
        from photon_ml_tpu.parallel.placement import measured_entity_costs

        counts = np.array([10, 10])
        owner = np.array([0, 1])
        costs = measured_entity_costs(counts, owner, np.array([2.0, 0.0]))
        # shard 1's rate falls back to shard 0's (the only measured one)
        np.testing.assert_allclose(costs, [2.0, 2.0])

    def test_replan_excluding_healthy_fleet_migrates(self):
        from photon_ml_tpu.parallel.placement import (
            PlacementPlan,
            measured_entity_costs,
            replan_excluding,
        )

        counts = np.array([8, 8, 8, 8])
        owner = np.array([0, 0, 0, 1])  # imbalanced by construction
        loads = np.array([24.0, 8.0])
        plan = PlacementPlan(owner=owner, loads=loads, num_shards=2)
        costs = measured_entity_costs(
            counts, owner, np.array([3.0, 1.0])
        )
        new_plan, migrated = replan_excluding(
            plan, [], costs, survivors=range(2)
        )
        assert migrated.sum() > 0
        assert new_plan.balance < plan.balance


# -- report / gate surface ---------------------------------------------------


def _write_shard(d, pidx, shard, extra_records=(), counters=None,
                 gauges=None, timers=None, knobs=None, fleet=2):
    from photon_ml_tpu.obs.sink import TelemetrySink

    t0 = 1000.0
    s = TelemetrySink(d, run_id="RC", shard_index=shard)
    s.emit({"event": "run_start", "t": t0, "schema_version": 1,
            "run_id": "RC", "pid": pidx, "process_index": pidx,
            "knobs": knobs or {}, "fleet": {"process_count": fleet},
            "metrics_baseline": {}})
    s.emit({"event": "span", "t": t0 + 0.1, "name": "descent/iter",
            "span_id": 1, "parent_id": None, "tid": 1, "thread": "M",
            "dur_s": 1.0})
    for r in extra_records:
        s.emit(dict(r, t=t0 + 0.5))
    s.emit({"event": "run_end", "t": t0 + 2.0, "run_id": "RC",
            "metrics": {"counters": counters or {}, "gauges": gauges or {},
                        "histograms": {}, "timers": timers or {}}})
    s.close()
    return s.path


class TestReportSurface:
    COUNTERS = {
        "re_combine.exchanges": {"value": 2.0},
        "re_combine.bytes_sent": {"value": 4096.0},
        "re_replan.checks": {"value": 1.0},
        "re_replan.count": {"value": 1.0},
        "re_replan.migrations": {"value": 12.0},
    }
    TIMERS = {
        "re_combine.exchange_s": {"seconds": 0.5, "count": 2},
        "re_combine.wait_s": {"seconds": 0.1, "count": 2},
    }
    REPLAN_EVENT = {
        "event": "re_replan", "iteration": 0, "coordinate": "per_entity",
        "imbalance": 2.5, "threshold": 1.3, "migrated": 12,
        "old_balance": 2.1, "new_balance": 1.1,
    }

    def test_summary_blocks_and_gate_metrics(self, tmp_path):
        from photon_ml_tpu.obs.report import (
            format_summary,
            gate_metrics_from_summary,
            summarize_run,
        )

        p = _write_shard(
            str(tmp_path), 0, None, extra_records=[self.REPLAN_EVENT],
            counters=self.COUNTERS, timers=self.TIMERS,
            gauges={"re_replan.last_imbalance": 2.5},
            knobs={"re_combine": "segments"},
        )
        s = summarize_run(p)
        assert s["re_combine"]["bytes_sent"] == 4096.0
        assert s["re_combine"]["mode"] == "segments"
        assert s["re_combine"]["exchange_s"] == 0.5
        assert s["re_replan"]["migrations"] == 12.0
        assert s["re_replan"]["events"][0]["coordinate"] == "per_entity"
        m = gate_metrics_from_summary(s)
        assert m["re_combine/bytes_sent"] == 4096.0
        assert m["re_replan/migrations"] == 12.0
        txt = format_summary(s)
        assert "re-combine:" in txt and "re-plan:" in txt

    def test_summary_without_combine_has_no_new_keys(self, tmp_path):
        from photon_ml_tpu.obs.report import summarize_run

        p = _write_shard(str(tmp_path), 0, None)
        s = summarize_run(p)
        assert "re_combine" not in s and "re_replan" not in s

    def test_gate_tiers(self):
        from photon_ml_tpu.obs.report import (
            DEFAULT_GATE_THRESHOLDS,
            resolve_threshold,
        )

        assert resolve_threshold(
            "re_combine/bytes_sent", DEFAULT_GATE_THRESHOLDS
        ) == {"rel": 0.05}
        assert resolve_threshold(
            "re_replan/migrations", DEFAULT_GATE_THRESHOLDS
        ) == {"rel": 0.0, "abs": 0.0}

    def test_gate_fails_on_byte_and_migration_regressions(self):
        from photon_ml_tpu.obs.report import gate_run

        base = {"re_combine/bytes_sent": 1000.0,
                "re_replan/migrations": 0.0}
        ok, _ = gate_run(dict(base), base)
        assert not ok
        fail_bytes, _ = gate_run(
            {"re_combine/bytes_sent": 1100.0,
             "re_replan/migrations": 0.0}, base
        )
        assert any(
            f["metric"] == "re_combine/bytes_sent" for f in fail_bytes
        )
        fail_mig, _ = gate_run(
            {"re_combine/bytes_sent": 1000.0,
             "re_replan/migrations": 1.0}, base
        )
        assert any(
            f["metric"] == "re_replan/migrations" for f in fail_mig
        )

    def test_fleet_merge_and_gate_metrics(self, tmp_path):
        from photon_ml_tpu.obs.report import (
            fleet_run_paths,
            format_fleet,
            gate_metrics_from_fleet,
            summarize_fleet,
        )

        _write_shard(
            str(tmp_path), 0, None, extra_records=[self.REPLAN_EVENT],
            counters=self.COUNTERS, timers=self.TIMERS,
            knobs={"re_combine": "segments"},
        )
        _write_shard(
            str(tmp_path), 1, 1,
            counters={
                "re_combine.exchanges": {"value": 2.0},
                "re_combine.bytes_sent": {"value": 1024.0},
                "re_replan.migrations": {"value": 12.0},
            },
            knobs={"re_combine": "segments"},
        )
        fs = summarize_fleet(fleet_run_paths(str(tmp_path)))
        assert fs["re_combine"]["bytes_sent_total"] == 5120.0
        assert fs["re_combine"]["per_process"] == {"0": 4096.0,
                                                   "1": 1024.0}
        assert fs["replans"][0]["migrated"] == 12
        m = gate_metrics_from_fleet(fs)
        assert m["re_combine/bytes_sent"] == 5120.0
        assert m["re_replan/migrations"] == 12.0
        txt = format_fleet(fs)
        assert "re-combine:" in txt and "re-plan:" in txt


class TestBenchKnobParse:
    def test_knob_snapshot_carries_combine_and_replan(self, monkeypatch):
        from photon_ml_tpu.obs.sink import _knob_snapshot

        monkeypatch.setenv("PHOTON_RE_COMBINE", "segments")
        monkeypatch.setenv("PHOTON_RE_REPLAN_IMBALANCE", "1.5")
        k = _knob_snapshot()
        assert k["re_combine"] == "segments"
        assert k["re_replan_imbalance"] == 1.5
