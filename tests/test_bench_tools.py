"""The bench harness's honesty machinery (guards + output contract)."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

_SPEC = importlib.util.spec_from_file_location(
    "bench_module",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "bench.py"),
)
bench = importlib.util.module_from_spec(_SPEC)
sys.modules.setdefault("bench_module", bench)
_SPEC.loader.exec_module(bench)


class TestGuards:
    def test_guard_marginal_rejects_impossible(self):
        bytes_per_pass = 1e9
        # implies 10 TB/s > roofline -> rejected
        assert bench._guard_marginal(bytes_per_pass, 1e-4) is None
        # implies 100 GB/s -> kept
        assert bench._guard_marginal(bytes_per_pass, 1e-2) == 1e-2
        assert bench._guard_marginal(bytes_per_pass, None) is None

    def test_timed_solves_rejects_impossible(self):
        class R:
            w = np.zeros(3)
            value = 0.0

        with pytest.raises(RuntimeError, match="timing artifact"):
            bench._timed_solves(lambda: R(), bytes_lower_bound_per_run=1e18)

    def test_median_of_runs(self):
        vals = iter([5.0, 1.0, 100.0])
        assert bench._median_of_runs(lambda: next(vals)) == 5.0


class TestQuickMode:
    """--quick is the cheap perf regression gate: same single-JSON-line
    stdout contract, A/A2/F subset, NO artifact writes (toy numbers must
    never overwrite the measured table)."""

    FAKE = {
        "A_sparse_logistic": {"samples_per_sec": 1.0, "quality_ok": True},
        "A2_sparse_highdim": {
            "samples_per_sec": 2.0,
            "quality_ok": True,
            "implied_hbm_fraction": 0.1,
            "kernel_constants": {
                "groups_per_run": 2,
                "segments_per_dma": 4,
                "kernel_dtype": "int8",
            },
            "packed_stream_bytes_per_pass": 196608,
            "quality_parity": {
                "kernel_dtype": "int8",
                "auc": 0.995066,
                "auc_f32": 0.995074,
                "auc_delta": -9e-06,
                "final_loss": 983.320618,
                "final_loss_f32": 983.277466,
                "loss_rel_delta": 4.4e-05,
                "margins_rmse_vs_f32": 0.003478,
            },
            "telemetry": {
                "schema_version": 1,
                "metrics": {
                    "counters": {}, "gauges": {}, "histograms": {},
                    "timers": {},
                },
                "knobs": {"kernel_dtype": "int8", "groups_per_run": 2},
                "quality_parity": {
                    "kernel_dtype": "int8",
                    "auc_delta": -9e-06,
                },
            },
        },
        "R_re_skew": {
            "sec_solve": 0.5,
            "quality_ok": True,
            "re_executed_entity_iterations": 1200.0,
            "re_useful_entity_iterations": 450.0,
            "re_wasted_lane_fraction": 0.625,
            "re_launches": 1.0,
            "re_knobs": {
                "compact_every": 0, "fuse_buckets": 0,
                "re_shard": 0, "re_split": 0,
                "re_device_split": 0, "re_split_weight": "rows",
            },
            "telemetry": {
                "schema_version": 1,
                "metrics": {
                    "counters": {
                        "re_solve.executed_entity_iterations": {
                            "value": 1200.0, "calls": 1,
                        },
                        "re_solve.useful_entity_iterations": {
                            "value": 450.0, "calls": 1,
                        },
                        "re_solve.launches": {"value": 1.0, "calls": 1},
                    },
                    "gauges": {"re_solve.active_lane_fraction": 0.375},
                    "histograms": {}, "timers": {},
                },
                "knobs": {"re_compact_every": 0, "re_fuse_buckets": 0},
            },
        },
        "F_streaming": {
            "samples_per_sec": 3.0,
            "quality_ok": True,
            "hostpack_overlap_ratio": 1.4,
            "prefetch": {
                "prefetch_depth": 2,
                "chunk_cache_budget_bytes": 6_000_000_000,
            },
            "telemetry": {
                "schema_version": 1,
                "metrics": {
                    "counters": {
                        "prefetch.cache.miss_bytes": {
                            "value": 123.0, "calls": 3,
                        }
                    },
                    "gauges": {}, "histograms": {},
                    "timers": {
                        "prefetch.host_pack_s": {"seconds": 0.5, "calls": 6},
                    },
                },
                "knobs": {"prefetch_depth": 2},
            },
        },
        "S_serve_zipf": {
            "sec_trace": 1.2,
            "quality_ok": True,
            "offered_rate_hz": 3000.0,
            "achieved_rate_hz": 2900.0,
            "serve_requests": 2400,
            "serve_windows": 120,
            "serve_latency_p50_ms": 2.0,
            "serve_latency_p99_ms": 5.5,
            "serve_latency_mean_ms": 2.4,
            "serve_hot_hit_rate": 0.74,
            "serve_window_occupancy_mean": 0.5,
            "serve_hot_budget_bytes": 1152,
            "serve_total_re_bytes": 4608,
            "score_parity_mismatches": 0,
            "refresh_parity_mismatches": 0,
            "telemetry": {
                "schema_version": 1,
                "metrics": {
                    "counters": {
                        "serve.requests": {"value": 2400.0, "calls": 2400},
                    },
                    "gauges": {"serve.hot.hit_rate": 0.74},
                    "histograms": {}, "timers": {},
                },
                "knobs": {"serve_max_batch": 32},
            },
        },
    }

    def _run_main(self, monkeypatch, capsys, results, quick=True):
        calls = []
        monkeypatch.setattr(
            bench, "_run_config_subprocess",
            lambda name, quick=False: (calls.append((name, quick)),
                                       results[name])[1],
        )
        detail_writes = []
        monkeypatch.setattr(
            bench.json, "dump",
            lambda *a, **k: detail_writes.append(a),
        )
        bench.main(quick=quick)
        return calls, detail_writes, capsys.readouterr()

    def test_quick_keeps_single_json_line_contract(self, monkeypatch, capsys):
        calls, detail_writes, cap = self._run_main(
            monkeypatch, capsys, self.FAKE
        )
        lines = [l for l in cap.out.splitlines() if l.strip()]
        assert len(lines) == 1, f"stdout must be ONE JSON line, got {lines}"
        payload = json.loads(lines[0])
        assert payload["quick"] is True
        assert set(payload["configs"]) == set(bench.QUICK_CONFIGS)
        assert [c for c, _ in calls] == list(bench.QUICK_CONFIGS)
        assert all(q for _, q in calls)
        # the retune surface round-trips through the contract: A2's
        # kernel_constants appear verbatim in the single JSON line, so a
        # sweep is auditable from stdout alone
        constants = payload["configs"]["A2_sparse_highdim"]["kernel_constants"]
        assert constants["segments_per_dma"] == 4
        assert constants["groups_per_run"] == 2
        # the precision-ladder knob rides the same contract: kernel_dtype
        # in kernel_constants, the per-rung streamed bytes, and the
        # quality-parity block (AUC/loss deltas vs the f32 anchor) both
        # at top level and inside the telemetry block — a dtype sweep is
        # auditable (speed AND quality gate) from stdout alone
        assert constants["kernel_dtype"] == "int8"
        a2 = payload["configs"]["A2_sparse_highdim"]
        assert a2["packed_stream_bytes_per_pass"] == 196608
        assert a2["quality_parity"]["auc_delta"] == -9e-06
        assert a2["quality_parity"]["kernel_dtype"] == "int8"
        assert a2["telemetry"]["knobs"]["kernel_dtype"] == "int8"
        assert a2["telemetry"]["quality_parity"]["auc_delta"] == -9e-06
        # the host-ingest pipeline knobs round-trip the same way: F's
        # prefetch depth + chunk-cache budget (and the measured host-pack
        # overlap ratio) appear verbatim in the single JSON line
        f_cfg = payload["configs"]["F_streaming"]
        assert f_cfg["prefetch"]["prefetch_depth"] == 2
        assert f_cfg["prefetch"]["chunk_cache_budget_bytes"] == 6_000_000_000
        assert f_cfg["hostpack_overlap_ratio"] == 1.4
        # the telemetry block (registry snapshot incl. the stage counters
        # as metrics.timers + knob values, the same dict a --telemetry-dir
        # run_end embeds) round-trips the contract verbatim
        tel = f_cfg["telemetry"]
        assert tel == self.FAKE["F_streaming"]["telemetry"]
        assert (
            tel["metrics"]["timers"]["prefetch.host_pack_s"]["calls"] == 6
        )
        assert (
            tel["metrics"]["counters"]["prefetch.cache.miss_bytes"]["value"]
            == 123.0
        )
        # the random-effect bucket-solve knobs + lane accounting round-trip
        # the same way: R_re_skew's knob block and its re_solve.* registry
        # counters appear verbatim in the single JSON line, so the
        # compaction/fusion sweep is auditable from stdout alone
        r_cfg = payload["configs"]["R_re_skew"]
        assert r_cfg["re_knobs"] == {
            "compact_every": 0, "fuse_buckets": 0,
            "re_shard": 0, "re_split": 0,
            "re_device_split": 0, "re_split_weight": "rows",
        }
        r_tel = r_cfg["telemetry"]
        assert (
            r_tel["metrics"]["counters"][
                "re_solve.executed_entity_iterations"
            ]["value"] == 1200.0
        )
        assert (
            r_tel["metrics"]["counters"][
                "re_solve.useful_entity_iterations"
            ]["value"] == 450.0
        )
        assert r_tel["knobs"]["re_compact_every"] == 0
        # the serving config rides the same contract: latency percentiles,
        # hit rate and the parity counts appear verbatim in the single
        # JSON line (the --serve doc and gate leg consume these fields)
        s_cfg = payload["configs"]["S_serve_zipf"]
        assert s_cfg["serve_latency_p50_ms"] == 2.0
        assert s_cfg["serve_latency_p99_ms"] == 5.5
        assert s_cfg["serve_hot_hit_rate"] == 0.74
        assert s_cfg["score_parity_mismatches"] == 0
        assert s_cfg["refresh_parity_mismatches"] == 0
        assert s_cfg["telemetry"]["metrics"]["gauges"][
            "serve.hot.hit_rate"
        ] == 0.74
        # quick writes NO artifacts (BENCH_DETAIL.json)
        assert not detail_writes

    def test_quick_quality_failure_exits_nonzero_with_contract(
        self, monkeypatch, capsys
    ):
        results = {
            k: dict(v) for k, v in self.FAKE.items()
        }
        results["A2_sparse_highdim"]["quality_ok"] = False
        with pytest.raises(SystemExit) as exc:
            self._run_main(monkeypatch, capsys, results)
        assert exc.value.code == 1
        lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
        assert len(lines) == 1 and json.loads(lines[0])["quick"] is True

    def test_quick_telemetry_dir_round_trips_contract(
        self, monkeypatch, capsys, tmp_path
    ):
        """--telemetry-dir reaches every config child and rides the
        single-JSON-line contract (top-level telemetry_dir + the child's
        archived run path inside its telemetry block)."""
        tdir = str(tmp_path / "tel")
        calls = []

        def fake_child(name, quick=False, telemetry_dir=None):
            calls.append((name, quick, telemetry_dir))
            r = {k: dict(v) for k, v in self.FAKE.items()}[name]
            r = dict(r)
            tel = dict(r.get("telemetry") or {"schema_version": 1})
            tel["telemetry_dir"] = telemetry_dir
            tel["run_path"] = os.path.join(
                telemetry_dir, f"run-{name}.jsonl"
            )
            r["telemetry"] = tel
            return r

        orig_child = bench._run_config_subprocess
        monkeypatch.setattr(bench, "_run_config_subprocess", fake_child)
        bench.main(quick=True, telemetry_dir=tdir)
        lines = [l for l in capsys.readouterr().out.splitlines()
                 if l.strip()]
        assert len(lines) == 1
        payload = json.loads(lines[0])
        assert payload["telemetry_dir"] == tdir
        assert all(td == tdir for _, _, td in calls)
        a2_tel = payload["configs"]["A2_sparse_highdim"]["telemetry"]
        assert a2_tel["telemetry_dir"] == tdir
        assert a2_tel["run_path"].endswith("run-A2_sparse_highdim.jsonl")
        # the child argv carries the flag (subprocess contract)
        import subprocess as sp

        seen_argv = {}

        def fake_run(argv, **kw):
            seen_argv["argv"] = argv

            class P:
                returncode = 0
                stdout = json.dumps({"ok": True})
                stderr = ""

            return P()

        monkeypatch.setattr(sp, "run", fake_run)
        orig_child("A2_sparse_highdim", quick=True, telemetry_dir=tdir)
        assert "--telemetry-dir" in seen_argv["argv"]
        assert seen_argv["argv"][
            seen_argv["argv"].index("--telemetry-dir") + 1
        ] == tdir

    def test_full_mode_still_writes_artifacts(self, monkeypatch, capsys):
        results = {
            name: {"samples_per_sec": 1.0, "quality_ok": True}
            for name in bench.CONFIGS
        }
        monkeypatch.setattr(
            bench, "_run_config_subprocess",
            lambda name, quick=False: results[name],
        )
        detail_writes = []
        monkeypatch.setattr(
            bench.json, "dump", lambda *a, **k: detail_writes.append(a)
        )
        bench.main(quick=False)
        lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
        assert len(lines) == 1 and json.loads(lines[0])["quick"] is False
        assert detail_writes  # full mode DOES write

    def test_retune_env_reaches_kernel_constants(self, monkeypatch):
        import photon_ml_tpu.ops.sparse_tiled as st

        monkeypatch.setattr(st, "GROUPS_PER_RUN", 2)
        monkeypatch.setattr(st, "GROUPS_PER_STEP", 32)
        monkeypatch.setattr(st, "SEGMENTS_PER_DMA", 4)
        monkeypatch.setattr(st, "KERNEL_DTYPE", "f32")
        monkeypatch.setenv("PHOTON_GROUPS_PER_RUN", "4")
        monkeypatch.setenv("PHOTON_GROUPS_PER_STEP", "16")
        monkeypatch.setenv("PHOTON_SEGMENTS_PER_DMA", "2")
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "int8")
        bench._apply_retune_env()
        assert st.GROUPS_PER_RUN == 4
        assert st.GROUPS_PER_STEP == 16
        assert st.SEGMENTS_PER_DMA == 2
        # the one string knob parses as a validated string, not an int
        assert st.KERNEL_DTYPE == "int8"
        # knob snapshot (telemetry block / run_start) reflects it
        from photon_ml_tpu.obs.sink import _knob_snapshot

        assert _knob_snapshot()["kernel_dtype"] == "int8"

    def test_telemetry_block_shape(self, monkeypatch):
        """The block every config subprocess attaches: the typed registry
        snapshot (stage counters = metrics.timers, one source of truth)
        and the knob values — coherent and JSON-serializable."""
        from photon_ml_tpu.obs.metrics import REGISTRY
        from photon_ml_tpu.utils import profiling

        profiling.add_seconds("benchtest.stage_s", 0.25)
        REGISTRY.counter_inc("benchtest.bytes", 42)
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "3")
        block = bench._telemetry_block()
        json.dumps(block)
        assert block["schema_version"] == 1
        # the legacy stage-counter view and the block's timers agree
        assert (
            block["metrics"]["timers"]["benchtest.stage_s"]
            == profiling.counter_snapshot("benchtest.")["benchtest.stage_s"]
        )
        assert block["metrics"]["counters"]["benchtest.bytes"]["value"] == 42
        # knobs read at call time (env wins), same as the prefetch block
        assert block["knobs"]["prefetch_depth"] == 3
        assert "groups_per_run" in block["knobs"]
        REGISTRY.reset("benchtest.")

    def test_retune_env_reaches_re_knobs(self, monkeypatch):
        import photon_ml_tpu.game.random_effect as re_mod

        monkeypatch.setattr(re_mod, "COMPACT_EVERY", 0)
        monkeypatch.setattr(re_mod, "FUSE_BUCKETS", 0)
        monkeypatch.setenv("PHOTON_RE_COMPACT_EVERY", "4")
        monkeypatch.setenv("PHOTON_RE_FUSE_BUCKETS", "1")
        bench._apply_retune_env()
        assert re_mod.COMPACT_EVERY == 4
        assert re_mod.FUSE_BUCKETS == 1
        # the call-time readers agree (env wins either way)
        assert re_mod.compact_every() == 4
        assert re_mod.fuse_buckets() is True
        # knob snapshot (telemetry block / run_start) reflects them
        from photon_ml_tpu.obs.sink import _knob_snapshot

        knobs = _knob_snapshot()
        assert knobs["re_compact_every"] == 4
        assert knobs["re_fuse_buckets"] == 1

    def test_retune_env_reaches_shard_knobs(self, monkeypatch):
        """PHOTON_RE_SPLIT rides the RETUNE_ENV_SHARD surface next to
        RE_SHARD: env → module global, call-time readers agree, and the
        knob snapshot (telemetry block / run_start / devcost key)
        reflects it."""
        import photon_ml_tpu.parallel.placement as pl

        monkeypatch.setattr(pl, "RE_SHARD", 0)
        monkeypatch.setattr(pl, "RE_SPLIT", 0)
        monkeypatch.setattr(pl, "RE_DEVICE_SPLIT", 0)
        monkeypatch.setattr(pl, "RE_SPLIT_WEIGHT", "rows")
        monkeypatch.setenv("PHOTON_RE_SHARD", "1")
        monkeypatch.setenv("PHOTON_RE_SPLIT", "16")
        monkeypatch.setenv("PHOTON_RE_DEVICE_SPLIT", "1")
        monkeypatch.setenv("PHOTON_RE_SPLIT_WEIGHT", "bytes")
        bench._apply_retune_env()
        assert pl.RE_SHARD == 1
        assert pl.RE_SPLIT == 16
        assert pl.RE_DEVICE_SPLIT == 1
        assert pl.RE_SPLIT_WEIGHT == "bytes"
        assert pl.re_shard_enabled() is True
        assert pl.re_split_factor() == 16
        assert pl.re_device_split_enabled() is True
        assert pl.re_split_weight() == "bytes"
        from photon_ml_tpu.obs.sink import _knob_snapshot

        knobs = _knob_snapshot()
        assert knobs["re_shard"] == 1
        assert knobs["re_split"] == 16
        assert knobs["re_device_split"] == 1
        assert knobs["re_split_weight"] == "bytes"
        # the devcost capture key tracks the knob too (a split flip
        # must re-capture, not reuse the unsplit executable's costs)
        from photon_ml_tpu.obs import devcost

        assert devcost.knob_key()["re_split"] == 16
        monkeypatch.setenv("PHOTON_RE_SPLIT", "0")
        assert devcost.knob_key()["re_split"] == 0
        assert devcost.knob_key()["re_device_split"] == 1
        monkeypatch.setenv("PHOTON_RE_DEVICE_SPLIT", "0")
        assert devcost.knob_key()["re_device_split"] == 0
        assert devcost.knob_key()["re_split_weight"] == "bytes"
        monkeypatch.setenv("PHOTON_RE_SPLIT_WEIGHT", "rows")
        assert devcost.knob_key()["re_split_weight"] == "rows"

    def test_split_weight_retune_rejects_unknown_mode(self, monkeypatch):
        monkeypatch.setenv("PHOTON_RE_SPLIT_WEIGHT", "lanes")
        with pytest.raises(ValueError, match="PHOTON_RE_SPLIT_WEIGHT"):
            bench._apply_retune_env()

    def test_retune_env_reaches_fe_shard_knobs(self, monkeypatch):
        """PHOTON_FE_SHARD / PHOTON_FE_SPLIT_WEIGHT ride the
        RETUNE_ENV_SHARD surface: env → module global (index_map — the
        partitioner owns them), call-time readers agree, and the knob
        snapshot (telemetry block / run_start / devcost key) reflects
        them."""
        import photon_ml_tpu.data.index_map as im

        monkeypatch.setattr(im, "FE_SHARD", 0)
        monkeypatch.setattr(im, "FE_SPLIT_WEIGHT", "nnz")
        monkeypatch.setenv("PHOTON_FE_SHARD", "1")
        monkeypatch.setenv("PHOTON_FE_SPLIT_WEIGHT", "width")
        bench._apply_retune_env()
        assert im.FE_SHARD == 1
        assert im.FE_SPLIT_WEIGHT == "width"
        assert im.fe_shard_enabled() is True
        assert im.fe_split_weight() == "width"
        from photon_ml_tpu.obs.sink import _knob_snapshot

        knobs = _knob_snapshot()
        assert knobs["fe_shard"] == 1
        assert knobs["fe_split_weight"] == "width"
        # the devcost capture key tracks both (a shard flip reshapes the
        # packed streams — costs must re-capture, never reuse)
        from photon_ml_tpu.obs import devcost

        assert devcost.knob_key()["fe_shard"] == 1
        assert devcost.knob_key()["fe_split_weight"] == "width"
        monkeypatch.setenv("PHOTON_FE_SHARD", "0")
        assert devcost.knob_key()["fe_shard"] == 0
        monkeypatch.setenv("PHOTON_FE_SPLIT_WEIGHT", "nnz")
        assert devcost.knob_key()["fe_split_weight"] == "nnz"

    def test_fe_split_weight_retune_rejects_unknown_mode(self, monkeypatch):
        monkeypatch.setenv("PHOTON_FE_SPLIT_WEIGHT", "rows")
        with pytest.raises(ValueError, match="PHOTON_FE_SPLIT_WEIGHT"):
            bench._apply_retune_env()

    def test_retune_env_reaches_prefetch_knobs(self, monkeypatch):
        import photon_ml_tpu.ops.prefetch as pf

        monkeypatch.setattr(pf, "PREFETCH_DEPTH", 2)
        monkeypatch.setattr(pf, "CHUNK_CACHE_BUDGET", None)
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "0")
        monkeypatch.setenv("PHOTON_CHUNK_CACHE_BUDGET", "123456")
        bench._apply_retune_env()
        assert pf.PREFETCH_DEPTH == 0
        assert pf.CHUNK_CACHE_BUDGET == 123456
        # the call-time accessors agree (env wins, so child processes
        # track even without _apply_retune_env)
        assert pf.prefetch_depth() == 0
        assert pf.chunk_cache_budget_bytes() == 123456


class TestServeContract:
    """``bench.py --serve`` (run_serve_r13) rides the same single-JSON-line
    stdout contract as ``--quick``: the latency / hit-rate fields the
    gate_quick serve leg and ``BASELINE_serve_cpu.json`` consume must all
    be present, and acceptance problems must still print the doc BEFORE
    raising (the driver's failure diagnosis is the doc itself)."""

    FAKE = {
        "sec_trace": 1.5,
        "offered_rate_hz": 2000.0,
        "achieved_rate_hz": 1900.0,
        "serve_requests": 2400,
        "serve_windows": 120,
        "serve_latency_p50_ms": 2.25,
        "serve_latency_p99_ms": 6.5,
        "serve_latency_mean_ms": 2.75,
        "serve_hot_hit_rate": 0.91,
        "serve_window_occupancy_mean": 0.55,
        "serve_hot_budget_bytes": 250,
        "serve_total_re_bytes": 1000,
        "score_parity_mismatches": 0,
        "refresh_parity_mismatches": 0,
        "quality_ok": True,
        "shape": {"E_m": 128, "E_i": 16},
    }

    def _stub_child(self, monkeypatch, result):
        calls = []
        monkeypatch.setattr(
            bench, "_run_config_subprocess",
            lambda name, quick=False, telemetry_dir=None: (
                calls.append((name, quick, telemetry_dir)), dict(result)
            )[1],
        )
        return calls

    def test_serve_quick_single_json_line_with_required_fields(
        self, monkeypatch, capsys
    ):
        calls = self._stub_child(monkeypatch, self.FAKE)
        doc = bench.run_serve_r13(quick=True)
        assert calls == [("S_serve_zipf", True, None)]
        lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
        assert len(lines) == 1, f"stdout must be ONE JSON line, got {lines}"
        payload = json.loads(lines[0])
        assert payload == doc
        assert payload["round"] == 13 and payload["quick"] is True
        for key in (
            "latency_p50_ms", "latency_p99_ms", "latency_mean_ms",
            "hot_hit_rate", "window_occupancy_mean", "hot_budget_bytes",
            "requests", "windows", "offered_rate_hz", "achieved_rate_hz",
        ):
            assert key in payload["trace"], key
        acc = payload["acceptance"]
        assert acc["score_parity_bitwise"] is True
        assert acc["refresh_parity_bitwise"] is True
        assert acc["hot_budget_fraction_of_re_bytes"] == 0.25
        assert set(payload["gate_metrics"]) == {
            "serve/latency_p50_ms", "serve/latency_p99_ms",
            "serve/hot_hit_rate", "serve/window_occupancy",
            "serve/refresh_parity", "serve/score_parity",
        }
        assert payload["problems"] == []

    def test_serve_parity_mismatch_prints_doc_then_raises(
        self, monkeypatch, capsys
    ):
        bad = dict(self.FAKE, score_parity_mismatches=3)
        self._stub_child(monkeypatch, bad)
        with pytest.raises(RuntimeError, match="acceptance violated"):
            bench.run_serve_r13(quick=True)
        lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
        assert len(lines) == 1
        payload = json.loads(lines[0])
        assert payload["problems"], "doc must carry the failure"
        assert payload["acceptance"]["score_parity_bitwise"] is False
        assert payload["gate_metrics"]["serve/score_parity"] == 3.0

    def test_serve_full_mode_gates_hit_rate_floor(self, monkeypatch, capsys):
        low = dict(self.FAKE, serve_hot_hit_rate=0.5)
        self._stub_child(monkeypatch, low)
        # quick mode: the floor is NOT asserted (reduced shape)
        bench.run_serve_r13(quick=True)
        capsys.readouterr()
        # full mode: below-floor hit rate is an acceptance violation
        with pytest.raises(RuntimeError, match="hit rate"):
            bench.run_serve_r13(quick=False)
        payload = json.loads(capsys.readouterr().out.splitlines()[0])
        assert payload["acceptance"]["hit_rate_ge_required"] is False

    def test_serve_full_mode_writes_artifact(
        self, monkeypatch, capsys, tmp_path
    ):
        self._stub_child(monkeypatch, self.FAKE)
        out = str(tmp_path / "SERVE_r13.json")
        doc = bench.run_serve_r13(out_path=out, quick=False)
        capsys.readouterr()
        with open(out) as f:
            assert json.load(f) == doc

    def test_committed_serve_artifact_matches_contract(self):
        """The committed SERVE_r13.json carries the gated fields and its
        acceptance flags all hold (the gate_quick serve leg's contract)."""
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(here, "SERVE_r13.json")) as f:
            doc = json.load(f)
        acc = doc["acceptance"]
        assert acc["score_parity_bitwise"] and acc["refresh_parity_bitwise"]
        assert acc["hot_hit_rate"] >= acc["required_hit_rate"]
        with open(os.path.join(here, "BASELINE_serve_cpu.json")) as f:
            base = json.load(f)
        assert set(base) == set(doc["gate_metrics"])
        assert base["serve/refresh_parity"] == 0.0
        assert base["serve/score_parity"] == 0.0


class TestNarrativeNumberDiscipline:
    """Every 'Nx'/'N×' multiplier in README prose must be backed by a
    committed artifact or be an explicitly reviewed protocol constant —
    rounds 3 and 4 each shipped a prose perf claim matching NO artifact
    (README's 6.8x A2 row)."""

    # Reviewed non-claim constants. Each entry documents WHY the number is
    # allowed to live in prose without appearing in a committed artifact.
    # Perf claims about THIS framework's kernels/configs never belong here.
    ALLOWED = {
        "10x": "north-star TARGET from BASELINE.json, not a measurement",
        "1000x": "hypothetical under-report bound in the guard rationale",
        "2x": "padding allowance in the exchange traffic test",
    }

    def _numbers(self, text: str) -> list[str]:
        import re

        return [
            m.group(1).replace("×", "x")
            for m in re.finditer(r"(\d+(?:\.\d+)?\s?[x×])(?![a-zA-Z0-9])", text)
        ]

    def test_prose_multipliers_are_artifact_backed(self):
        import glob

        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        # Union of the session artifact (BENCH_DETAIL.json, gitignored — may
        # not exist on a fresh checkout) and every COMMITTED capture:
        # BENCH_r*.json, the MULTICHIP_r* harness captures, and the gate
        # baseline. A prose claim backed by any of them survives.
        pieces = []
        for pattern in (
            "BENCH_*.json", "MULTICHIP_*.json", "BASELINE_cost_cpu.json"
        ):
            for path in sorted(glob.glob(os.path.join(here, pattern))):
                with open(path) as f:
                    pieces.append(f.read())
        assert pieces, "no committed JSON artifact found to audit against"
        artifact = "\n".join(pieces)
        offenders = []
        for name in ("README.md",):
            with open(os.path.join(here, name)) as f:
                text = f.read()
            for hit in self._numbers(text):
                token = hit.replace(" ", "").rstrip("x")
                if hit.replace(" ", "") in self.ALLOWED:
                    continue
                if token in artifact:
                    continue  # the claim cites a committed measurement
                offenders.append(f"{name}: {hit!r}")
        assert not offenders, (
            "prose multiplier claims matching no committed artifact "
            f"(add to BENCH_DETAIL.json via the bench, or delete): {offenders}"
        )
