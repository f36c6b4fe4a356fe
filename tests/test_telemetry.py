"""Run-telemetry subsystem (``photon_ml_tpu/obs``): span nesting (incl.
across prefetch worker threads), the disabled-sink fast path, JSONL schema
round-trip, Perfetto export, report summarize/diff, the shared atomic
write helper's crash behavior, the PhotonLogger event hook, and the
end-to-end GAME training span tree. All host-side, unmarked (no ``kernel``
marker — tier-1 sits near the wall-clock budget)."""

from __future__ import annotations

import json
import os
import threading
import time

import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.obs import metrics as obs_metrics
from photon_ml_tpu.obs.export import chrome_trace, export_chrome_trace
from photon_ml_tpu.obs.report import (
    diff_summaries,
    format_summary,
    load_run,
    summarize_run,
    validate_run,
)


@pytest.fixture
def telemetry(tmp_path):
    """An enabled sink in a temp dir; always shut down (the sink is
    process-global state — a leak would redirect other tests' spans)."""
    path = obs.configure(str(tmp_path / "telemetry"))
    try:
        yield path
    finally:
        obs.shutdown()


def _records(path):
    return [json.loads(line) for line in open(path) if line.strip()]


class TestSpans:
    def test_nesting_parent_ids(self, telemetry):
        with obs.span("a/outer") as outer:
            with obs.span("a/inner", k=1) as inner:
                assert inner.parent_id == outer.span_id
            with obs.span("a/inner2") as inner2:
                assert inner2.parent_id == outer.span_id
        obs.shutdown()
        spans = {r["name"]: r for r in _records(telemetry)
                 if r["event"] == "span"}
        assert spans["a/inner"]["parent_id"] == spans["a/outer"]["span_id"]
        assert spans["a/outer"]["parent_id"] is None
        assert spans["a/inner"]["attrs"] == {"k": 1}

    def test_no_cross_thread_parent_leakage(self, telemetry):
        """Spans opened on prefetch worker threads must root in THEIR
        thread, not under whatever the consumer thread has open."""
        from photon_ml_tpu.ops import prefetch

        def prepare(i):
            with obs.span("worker/prepare", item=i):
                return i

        with obs.span("consumer/run"):
            out = list(prefetch.prefetch_iter(4, prepare, depth=2))
        assert out == [0, 1, 2, 3]
        obs.shutdown()
        spans = [r for r in _records(telemetry) if r["event"] == "span"]
        consumer = next(s for s in spans if s["name"] == "consumer/run")
        workers = [s for s in spans if s["name"] == "worker/prepare"]
        assert len(workers) == 4
        for w in workers:
            assert w["parent_id"] is None, (
                "worker span adopted a cross-thread parent"
            )
            assert w["tid"] != consumer["tid"]

    def test_disabled_sink_is_shared_noop(self, tmp_path):
        """The name is from when a span without a sink was one shared
        no-op. Now it is live on its two clocks and nothing else: the
        registry timer ``span.<name>`` moves by one call, no id is made,
        nothing is emitted."""
        obs.shutdown()
        obs.REGISTRY.reset_timers("span")
        assert not hasattr(obs, "NOOP_SPAN")
        with obs.span("x/quiet", k=2):
            assert obs.current_span_id() is None
            obs.emit_event("nothing", k=1)
        timers = obs.REGISTRY.timer_snapshot("span.")
        assert timers["span.x/quiet"]["calls"] == 1
        assert timers["span.x/quiet"]["seconds"] >= 0.0
        # a sink configured afterwards holds no trace of it
        path = obs.configure(str(tmp_path))
        obs.shutdown()
        assert [r["event"] for r in _records(path)] == ["run_start", "run_end"]

    def test_exception_still_emits_and_unwinds(self, telemetry):
        with pytest.raises(RuntimeError):
            with obs.span("a/raises"):
                raise RuntimeError("boom")
        assert obs.current_span_id() is None
        obs.shutdown()
        rec = next(r for r in _records(telemetry)
                   if r["event"] == "span" and r["name"] == "a/raises")
        assert rec["error"] == "RuntimeError"


class TestSinkAndSchema:
    def test_jsonl_schema_round_trip(self, telemetry):
        with obs.span("phase/work", tag="v"):
            obs.emit_event("optim_iter", it=1, loss=0.5, grad_norm=0.1)
        obs.REGISTRY.counter_inc("test.counter", 3)
        obs.shutdown()
        records = load_run(telemetry)
        assert validate_run(records) == []
        assert records[0]["event"] == "run_start"
        assert records[0]["schema_version"] == obs.SCHEMA_VERSION
        assert records[-1]["event"] == "run_end"
        snap = records[-1]["metrics"]
        assert snap["counters"]["test.counter"]["value"] == 3
        ev = next(r for r in records if r["event"] == "optim_iter")
        # events are attributed to the enclosing span
        sp = next(r for r in records if r["event"] == "span")
        assert ev["span_id_ref"] == sp["span_id"]

    def test_nonfinite_floats_stay_strict_json(self, telemetry):
        """A diverged solve's NaN loss must not poison the file: strict
        parsers (the Perfetto UI, non-Python consumers) reject bare
        NaN/Infinity for the WHOLE document."""
        with obs.span("optim/diverged", loss=float("nan")):
            obs.emit_event(
                "optim_iter", it=1, loss=float("nan"),
                grad_norm=float("inf"), step=-float("inf"),
            )
        obs.shutdown()
        text = open(telemetry).read()
        json.loads(f"[{','.join(text.splitlines())}]",
                   parse_constant=self._reject)  # strict: bare NaN raises
        ev = next(r for r in _records(telemetry)
                  if r["event"] == "optim_iter")
        assert (ev["loss"], ev["grad_norm"], ev["step"]) == (
            "NaN", "Infinity", "-Infinity",
        )
        trace = chrome_trace(_records(telemetry))
        json.dumps(trace, allow_nan=False)  # export inherits strictness

    @staticmethod
    def _reject(const):
        raise AssertionError(f"non-strict JSON constant in sink output: {const}")

    def test_rotation_keeps_file_complete_prefix(self, tmp_path):
        """Every on-disk state of the sink parses as a complete run
        prefix (the atomic rotate never exposes a torn tail)."""
        from photon_ml_tpu.obs.sink import TelemetrySink

        sink = TelemetrySink(str(tmp_path))
        for i in range(300):  # crosses the first rotate threshold (128)
            sink.emit({"event": "tick", "t": float(i), "i": i})
            if os.path.exists(sink.path):
                for line in open(sink.path):
                    json.loads(line)  # parseable at every observed state
        sink.close()
        lines = [json.loads(l) for l in open(sink.path)]
        assert [r["i"] for r in lines] == list(range(300))

    def test_multihost_nonzero_process_does_not_write(self, tmp_path, monkeypatch):
        import photon_ml_tpu.obs.sink as sink_mod

        monkeypatch.setattr(sink_mod, "_process_index", lambda: 1)
        assert obs.configure(str(tmp_path / "t")) is None
        assert not obs.enabled()
        obs.shutdown()

    def test_disabled_logger_hook_and_enabled_capture(self, telemetry):
        from photon_ml_tpu.utils import PhotonLogger

        log = PhotonLogger(stream=open(os.devnull, "w"))
        log.warn("dropped rows", tag="uid", fraction=0.6)
        log.error("bad shard", shard="g")
        log.info("quiet")  # INFO lines never become events
        obs.shutdown()
        logs = [r for r in _records(telemetry) if r["event"] == "log"]
        assert {(r["level"], r["message"]) for r in logs} == {
            ("WARN", "dropped rows"), ("ERROR", "bad shard"),
        }
        warn = next(r for r in logs if r["level"] == "WARN")
        assert warn["fields"] == {"tag": "uid", "fraction": 0.6}

    def test_logger_hook_opt_out_and_custom(self):
        from photon_ml_tpu.utils import PhotonLogger

        seen = []
        log = PhotonLogger(
            stream=open(os.devnull, "w"),
            event_hook=lambda lvl, msg, fields: seen.append((lvl, msg, fields)),
        )
        log.warn("w", a=1)
        assert seen == [("WARN", "w", {"a": 1})]
        off = PhotonLogger(stream=open(os.devnull, "w"), event_hook=False)
        off.warn("silent")  # no sink, no hook, no crash


class TestAtomicIO:
    def test_crash_simulation_partial_never_shadows_complete(self, tmp_path, monkeypatch):
        """A failed rewrite must leave the previous COMPLETE file intact
        and no tmp turds — for both byte payloads (JSONL rotation) and
        npz payloads (checkpoint shards)."""
        from photon_ml_tpu.utils.atomic_io import (
            atomic_replace_bytes,
            atomic_savez,
        )

        d = str(tmp_path)
        final = os.path.join(d, "run.jsonl")
        atomic_replace_bytes(d, final, b'{"event":"run_start"}\n')

        class Boom(RuntimeError):
            pass

        calls = {"n": 0}
        real_fsync = os.fsync

        def dying_fsync(fd):
            calls["n"] += 1
            if calls["n"] == 1:
                raise Boom()  # die mid-write, before the rename
            return real_fsync(fd)

        monkeypatch.setattr(os, "fsync", dying_fsync)
        with pytest.raises(Boom):
            atomic_replace_bytes(d, final, b"x" * (1 << 20))
        assert open(final, "rb").read() == b'{"event":"run_start"}\n'
        assert [f for f in os.listdir(d) if f.endswith(".tmp")] == []

        monkeypatch.setattr(os, "fsync", real_fsync)
        npz = os.path.join(d, "shard.npz")
        atomic_savez(d, npz, {"w": np.arange(3.0)})
        monkeypatch.setattr(
            np, "savez", lambda f, **kw: (_ for _ in ()).throw(Boom())
        )
        with pytest.raises(Boom):
            atomic_savez(d, npz, {"w": np.arange(9.0)})
        with np.load(npz) as z:
            np.testing.assert_array_equal(z["w"], np.arange(3.0))
        assert [f for f in os.listdir(d) if f.endswith(".tmp")] == []

    def test_sink_rotation_survives_one_failed_rotate(self, tmp_path, monkeypatch):
        from photon_ml_tpu.obs.sink import TelemetrySink

        sink = TelemetrySink(str(tmp_path))
        sink.emit({"event": "run_start", "t": 0.0})
        sink.flush()
        good = open(sink.path).read()
        import photon_ml_tpu.utils.atomic_io as aio

        real = aio.atomic_replace_bytes
        monkeypatch.setattr(
            aio, "atomic_replace_bytes",
            lambda *a: (_ for _ in ()).throw(OSError("disk full")),
        )
        with pytest.raises(OSError):
            sink.flush()
        assert open(sink.path).read() == good  # prior complete file intact
        monkeypatch.setattr(aio, "atomic_replace_bytes", real)
        sink.emit({"event": "tick", "t": 1.0})
        sink.close()
        assert len(open(sink.path).readlines()) == 2


class TestMetricsRegistry:
    def test_typed_instruments_snapshot(self):
        r = obs_metrics.MetricsRegistry()
        r.counter_inc("c.bytes", 10)
        r.counter_inc("c.bytes", 5)
        r.gauge_set("g.frac", 0.25)
        for v in (1, 2, 8):
            r.histogram_observe("h.iters", v)
        r.timer_add("t.pack_s", 0.5)
        snap = r.snapshot()
        assert snap["counters"]["c.bytes"] == {"value": 15.0, "calls": 2}
        assert snap["gauges"]["g.frac"] == 0.25
        h = snap["histograms"]["h.iters"]
        assert (h["count"], h["sum"], h["min"], h["max"]) == (3, 11.0, 1, 8)
        assert h["log2_buckets"] == {"0": 1, "1": 1, "3": 1}
        assert snap["timers"]["t.pack_s"]["calls"] == 1
        json.dumps(snap)  # JSON-plain by construction
        r.reset("c.")
        assert r.snapshot()["counters"] == {}
        assert r.snapshot()["gauges"] != {}

    def test_profiling_shim_is_a_view_of_the_registry(self):
        from photon_ml_tpu.utils import profiling

        profiling.reset_counters("shimtest.")
        with profiling.stage_timer("shimtest.stage"):
            pass
        snap = profiling.counter_snapshot("shimtest.")
        assert snap["shimtest.stage"]["calls"] == 1
        # same numbers through the registry's own snapshot
        reg = obs_metrics.REGISTRY.snapshot("shimtest.")
        assert reg["timers"] == snap
        profiling.reset_counters("shimtest.")
        assert profiling.counter_snapshot("shimtest.") == {}

    def test_optimization_result_telemetry_record(self):
        import jax.numpy as jnp

        from photon_ml_tpu.optim.common import (
            ConvergenceReason,
            OptimizationResult,
        )

        res = OptimizationResult(
            w=jnp.zeros(2), value=jnp.asarray(1.5),
            grad_norm=jnp.asarray(1e-4),
            iterations=jnp.asarray(7, jnp.int32),
            reason=jnp.asarray(
                int(ConvergenceReason.GRADIENT_CONVERGED), jnp.int32
            ),
            loss_history=jnp.zeros(8), grad_norm_history=jnp.zeros(8),
        )
        rec = res.telemetry_record(coordinate="fixed")
        # the enum NAME and the iteration count, verbatim
        assert rec["reason"] == "GRADIENT_CONVERGED"
        assert rec["iterations"] == 7
        assert rec["coordinate"] == "fixed"
        s = res.summary()
        assert "GRADIENT_CONVERGED" in s and "iterations=7" in s


class TestExportAndReport:
    def _make_run(self, tmp_path, name, extra_span=None, depth=2):
        path = obs.configure(str(tmp_path), run_id=name)
        with obs.span("ingest/read", files=1):
            pass
        with obs.span("descent/iter", iteration=0):
            with obs.span("descent/visit", coordinate="fixed"):
                obs.emit_event(
                    "optim_result", reason="GRADIENT_CONVERGED",
                    iterations=3, value=1.0, grad_norm=1e-5,
                )
            with obs.span("descent/validation", coordinate="fixed"):
                pass
        if extra_span:
            with obs.span(extra_span):
                pass
        obs.shutdown()
        return path

    def test_perfetto_export_is_valid_chrome_trace(self, tmp_path):
        run = self._make_run(tmp_path / "t", "runA")
        out = str(tmp_path / "trace.json")
        trace = export_chrome_trace(run, out)
        with open(out) as f:
            loaded = json.load(f)
        assert loaded == json.loads(json.dumps(trace))
        events = loaded["traceEvents"]
        assert isinstance(events, list) and events
        complete = [e for e in events if e["ph"] == "X"]
        names = {e["name"] for e in complete}
        assert {"ingest/read", "descent/iter", "descent/visit",
                "descent/validation"} <= names
        for e in complete:  # the chrome trace contract per complete event
            assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
            assert e["ts"] >= 0 and e["dur"] >= 0
        # instant events carry the optimizer markers onto the timeline
        assert any(e["ph"] == "i" for e in events)

    def test_report_summarizes_phases(self, tmp_path):
        run = self._make_run(tmp_path / "t", "runA")
        s = summarize_run(run)
        assert s["run_id"] == "runA" and s["complete"]
        assert set(s["phases"]) == {"ingest", "descent"}
        # nested visit/validation spans must not double-count the phase
        assert s["phases"]["descent"]["spans"] == 3
        assert s["optim"]["solves"] == 1
        assert s["optim"]["reasons"] == {"GRADIENT_CONVERGED": 1}
        text = format_summary(s)
        assert "descent" in text and "ingest" in text

    def test_phase_wall_unions_concurrent_worker_spans(self, tmp_path):
        """Overlapping phase-entry spans (concurrent prefetch workers)
        must union, not sum — a phase's wall can never exceed real
        wall-clock coverage of that phase."""
        from photon_ml_tpu.obs.report import _union_seconds

        assert _union_seconds([(0.0, 2.0), (1.0, 3.0), (10.0, 11.0)]) == 4.0
        path = obs.configure(str(tmp_path), run_id="conc")
        barrier = threading.Barrier(2)

        def worker():
            with obs.span("ingest/worker"):
                barrier.wait(timeout=10)  # both spans are now open...
                time.sleep(0.05)  # ...and overlap for a dominant stretch

        ts = [threading.Thread(target=worker) for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        obs.shutdown()
        s = summarize_run(path)
        spans_total = sum(
            r["dur_s"] for r in load_run(path)
            if r["event"] == "span" and r["name"] == "ingest/worker"
        )
        assert s["phases"]["ingest"]["spans"] == 2
        # summed durations ≈ 2× the unioned wall (the spans fully overlap)
        assert s["phases"]["ingest"]["wall_s"] < 0.75 * spans_total

    def test_report_wasted_lane_accounting(self, tmp_path):
        """The re_solve.* lane counters surface as a wasted-lane readout:
        run_start-baselined deltas in summarize, a rendered line in
        format_summary, and the wasted-lane column in diff — the sweep
        readout for PHOTON_RE_COMPACT_EVERY / PHOTON_RE_FUSE_BUCKETS."""
        path_a = obs.configure(str(tmp_path / "a"), run_id="runOFF")
        obs_metrics.REGISTRY.counter_inc("re_solve.launches", 2)
        obs_metrics.REGISTRY.counter_inc(
            "re_solve.executed_entity_iterations", 1000.0
        )
        obs_metrics.REGISTRY.counter_inc(
            "re_solve.useful_entity_iterations", 600.0
        )
        obs.shutdown()
        path_b = obs.configure(str(tmp_path / "b"), run_id="runON")
        obs_metrics.REGISTRY.counter_inc("re_solve.launches", 9)
        obs_metrics.REGISTRY.counter_inc(
            "re_solve.executed_entity_iterations", 660.0
        )
        obs_metrics.REGISTRY.counter_inc(
            "re_solve.useful_entity_iterations", 600.0
        )
        obs.shutdown()
        a, b = summarize_run(path_a), summarize_run(path_b)
        # deltas against the run_start baseline (the registry is process-
        # cumulative: run B must NOT inherit run A's 1000)
        assert a["re_solve"]["executed_entity_iterations"] == 1000.0
        assert a["re_solve"]["useful_entity_iterations"] == 600.0
        assert abs(a["re_solve"]["wasted_lane_fraction"] - 0.4) < 1e-9
        assert b["re_solve"]["executed_entity_iterations"] == 660.0
        assert b["re_solve"]["wasted_lane_fraction"] == 1.0 - 600.0 / 660.0
        text = format_summary(a)
        assert "wasted-lane 40.0%" in text
        d = diff_summaries(a, b)
        assert "wasted-lane" in d and "exec-entity-it" in d
        assert "1000" in d and "660" in d

    def test_report_quality_parity_section(self, tmp_path, monkeypatch):
        """A quality_parity event (emitted by a reduced-precision bench
        run) surfaces in summarize, format_summary and diff — the
        precision ladder's quality gate reads from the same report as the
        wall numbers."""
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "int8")
        path_b = obs.configure(str(tmp_path / "b"), run_id="runINT8")
        obs.emit_event(
            "quality_parity", kernel_dtype="int8",
            auc=0.9951, auc_f32=0.9950, auc_delta=0.0001,
            final_loss=983.32, final_loss_f32=983.28,
            loss_rel_delta=4.4e-05, margins_rmse_vs_f32=0.0035,
        )
        obs.shutdown()
        monkeypatch.delenv("PHOTON_KERNEL_DTYPE")
        path_a = obs.configure(str(tmp_path / "a"), run_id="runF32")
        obs.shutdown()
        b = summarize_run(path_b)
        assert b["quality_parity"]["kernel_dtype"] == "int8"
        assert b["quality_parity"]["auc_delta"] == 0.0001
        assert b["knobs"]["kernel_dtype"] == "int8"
        text = format_summary(b)
        assert "quality-parity" in text and "kernel_dtype=int8" in text
        assert "auc_delta=+0.000100" in text
        a = summarize_run(path_a)
        assert a["quality_parity"] is None
        d = diff_summaries(a, b)
        assert "quality-parity" in d
        assert "(unrecorded)" in d  # run A recorded no parity block
        assert "kernel_dtype: 'f32' -> 'int8'" in d  # the knob delta too

    def test_report_diff_renders_asymmetric_retune_knobs(self, tmp_path):
        """A RETUNE knob recorded by only ONE run (an older-schema run,
        or a pre-knob baseline) must still render in the knob-delta table
        as '(unrecorded)' instead of being silently dropped."""
        path_a = obs.configure(str(tmp_path / "a"), run_id="oldRun")
        obs.shutdown()
        path_b = obs.configure(str(tmp_path / "b"), run_id="newRun")
        obs.shutdown()
        a, b = summarize_run(path_a), summarize_run(path_b)
        # simulate an old run that predates the kernel_dtype knob (and
        # one knob recorded nowhere at all — absent from the table)
        a["knobs"] = {k: v for k, v in a["knobs"].items()
                      if k not in ("kernel_dtype", "re_compact_every")}
        b["knobs"] = {k: v for k, v in b["knobs"].items()
                      if k != "re_compact_every"}
        d = diff_summaries(a, b)
        assert "kernel_dtype: '(unrecorded)' -> 'f32'" in d
        assert "re_compact_every" not in d

    def test_report_diffs_two_synthetic_runs(self, tmp_path, monkeypatch):
        run_a = self._make_run(tmp_path / "a", "runA")
        monkeypatch.setenv("PHOTON_PREFETCH_DEPTH", "0")
        run_b = self._make_run(tmp_path / "b", "runB", extra_span="score/pass")
        monkeypatch.delenv("PHOTON_PREFETCH_DEPTH")
        a, b = summarize_run(run_a), summarize_run(run_b)
        text = diff_summaries(a, b)
        assert "runA" in text and "runB" in text
        assert "score" in text  # phase present in B only still renders
        # knob deltas surface (run B executed under depth 0)
        assert "prefetch_depth" in text

    def test_report_cli_main(self, tmp_path, capsys):
        from photon_ml_tpu.cli.report import main as report_main

        run_a = self._make_run(tmp_path / "a", "runA")
        run_b = self._make_run(tmp_path / "b", "runB")
        report_main([run_a])
        out = capsys.readouterr().out
        assert "runA" in out and "descent" in out
        # directory form resolves to the newest run; --diff + --export
        trace_out = str(tmp_path / "tr.json")
        report_main([str(tmp_path / "a"), "--diff", run_b,
                     "--export-trace", trace_out])
        out = capsys.readouterr().out
        assert "runB" in out
        assert json.load(open(trace_out))["traceEvents"]
        report_main([run_a, "--json"])
        assert json.loads(capsys.readouterr().out)["run_id"] == "runA"

    def test_validate_rejects_foreign_files(self, tmp_path):
        p = tmp_path / "x.jsonl"
        p.write_text('{"not": "telemetry"}\n')
        assert validate_run(load_run(str(p)))
        p2 = tmp_path / "y.jsonl"
        p2.write_text("not json\n")
        with pytest.raises(ValueError):
            load_run(str(p2))


class TestDriverFlag:
    def test_train_cli_telemetry_dir_wires_configure_and_shutdown(
        self, tmp_path, monkeypatch
    ):
        """--telemetry-dir: the sink is LIVE during run() (spans emitted by
        the training stack land in the file) and durably finalized after —
        without the flag, telemetry stays disabled. run() itself is
        stubbed: the full driver path is covered by test_drivers; this
        pins the flag → configure → shutdown wiring."""
        from photon_ml_tpu.cli import train

        cfg_path = tmp_path / "cfg.json"
        cfg_path.write_text(json.dumps({
            "task_type": "LOGISTIC_REGRESSION",
            "coordinate_update_sequence": ["fixed"],
            "fixed_effect_coordinates": {
                "fixed": {"feature_shard_id": "global"}
            },
        }))
        states = []

        def fake_run(*a, **kw):
            states.append(obs.enabled())
            with obs.span("train/grid-fit"):
                pass

        monkeypatch.setattr(train, "run", fake_run)
        tel = tmp_path / "tel"
        train.main([
            "--config", str(cfg_path), "--train-data", str(tmp_path),
            "--output-dir", str(tmp_path / "out"), "--no-auto-streaming",
            "--telemetry-dir", str(tel),
        ])
        assert states == [True]
        assert not obs.enabled()  # shutdown ran in the finally
        runs = [f for f in os.listdir(tel) if f.endswith(".jsonl")]
        assert len(runs) == 1
        records = load_run(str(tel / runs[0]))
        assert validate_run(records) == []
        assert any(
            r["event"] == "span" and r["name"] == "train/grid-fit"
            for r in records
        )
        # without the flag: disabled throughout
        train.main([
            "--config", str(cfg_path), "--train-data", str(tmp_path),
            "--output-dir", str(tmp_path / "out2"), "--no-auto-streaming",
        ])
        assert states == [True, False]


class TestEndToEndGame:
    def _fit(self, tmp_path, rng, name, iters=2):
        from photon_ml_tpu.config import (
            FixedEffectCoordinateConfig,
            GameTrainingConfig,
            OptimizationConfig,
            OptimizerConfig,
            RandomEffectCoordinateConfig,
            RegularizationContext,
        )
        from photon_ml_tpu.game.streaming import (
            StreamedGameData,
            StreamedGameTrainer,
        )
        from photon_ml_tpu.types import RegularizationType, TaskType

        n, d, E, dr = 240, 5, 6, 3
        X = rng.normal(size=(n, d)).astype(np.float32)
        Xr = rng.normal(size=(n, dr)).astype(np.float32)
        ids = rng.integers(0, E, size=n).astype(np.int32)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        opt = OptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=8, tolerance=1e-6),
            regularization=RegularizationContext(RegularizationType.L2),
            regularization_weight=1.0,
        )
        cfg = GameTrainingConfig(
            task_type=TaskType.LOGISTIC_REGRESSION,
            coordinate_update_sequence=("fixed", "user"),
            coordinate_descent_iterations=iters,
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
            evaluators=("AUC",),
        )
        data = StreamedGameData(
            labels=y, features={"g": X, "r": Xr}, id_tags={"uid": ids}
        )
        val = StreamedGameData(
            labels=y[:80], features={"g": X[:80], "r": Xr[:80]},
            id_tags={"uid": ids[:80]},
        )
        path = obs.configure(str(tmp_path), run_id=name)
        try:
            StreamedGameTrainer(
                cfg, chunk_rows=96, evaluators=("AUC",)
            ).fit(data, validation=val)
        finally:
            obs.shutdown()
        return path

    def test_game_run_produces_schema_valid_span_tree(self, tmp_path, rng):
        """The acceptance contract: a GAME training run with telemetry on
        yields a schema-valid JSONL whose span tree covers ingest →
        per-coordinate descent iterations → validation; `report`
        summarizes and diffs it; the Perfetto export is valid."""
        run_a = self._fit(tmp_path / "a", rng, "gameA", iters=2)
        records = load_run(run_a)
        assert validate_run(records) == []

        spans = [r for r in records if r["event"] == "span"]
        by_id = {s["span_id"]: s for s in spans}
        names = {s["name"] for s in spans}
        assert {"game/fit", "ingest/re-shard", "descent/iter",
                "descent/visit", "descent/validation"} <= names

        # span TREE: visit → iter → game/fit, and ingest under game/fit
        visit = next(s for s in spans if s["name"] == "descent/visit")
        it_span = by_id[visit["parent_id"]]
        assert it_span["name"] == "descent/iter"
        assert by_id[it_span["parent_id"]]["name"] == "game/fit"
        ingest = next(s for s in spans if s["name"] == "ingest/re-shard")
        assert by_id[ingest["parent_id"]]["name"] == "game/fit"
        val_span = next(s for s in spans if s["name"] == "descent/validation")
        assert by_id[val_span["parent_id"]]["name"] == "descent/iter"

        # per-coordinate coverage: 2 iterations × 2 coordinates
        visits = [s for s in spans if s["name"] == "descent/visit"]
        assert {
            (s["attrs"]["iteration"], s["attrs"]["coordinate"])
            for s in visits
        } == {(0, "fixed"), (0, "user"), (1, "fixed"), (1, "user")}

        # the host solver's per-iteration and final records are present
        assert any(r["event"] == "optim_iter" for r in records)
        opt_res = [r for r in records if r["event"] == "optim_result"]
        assert opt_res and all(
            isinstance(r["reason"], str) and "iterations" in r
            for r in opt_res
        )
        assert any(r["event"] == "visit_result" for r in records)

        # run_end carries the registry (stream pass counters included)
        end = records[-1]
        assert end["event"] == "run_end"
        assert end["metrics"]["counters"]["stream.passes"]["value"] > 0

        # report + diff + Perfetto export on the real artifact
        s_a = summarize_run(run_a)
        assert {"game", "ingest", "descent"} <= set(s_a["phases"])
        run_b = self._fit(tmp_path / "b", rng, "gameB", iters=1)
        text = diff_summaries(s_a, summarize_run(run_b))
        assert "gameA" in text and "gameB" in text
        trace = chrome_trace(records)
        json.dumps(trace)
        assert any(
            e["name"] == "descent/visit" for e in trace["traceEvents"]
        )


# -- fleet telemetry: per-process sink shards + the merged fleet view -------


def _write_fleet_fixture(directory, run_id="F1", unmatched=False,
                         missing_shard=False):
    """A synthetic 2-process fleet run: canonical file + one .p1 shard,
    with correlated p2p_send/p2p_recv pairs on both links (frame-set
    semantics matching parallel/multihost's correlation contract)."""
    from photon_ml_tpu.obs.sink import TelemetrySink

    t0 = 1_000.0

    def run_start(pidx):
        return {
            "event": "run_start", "t": t0 + 0.01 * pidx,
            "schema_version": obs.SCHEMA_VERSION, "run_id": run_id,
            "pid": 100 + pidx, "process_index": pidx,
            "knobs": {"re_shard": 1},
            "fleet": {"process_count": 2},
            "metrics_baseline": {},
        }

    def run_end(pidx, overlap):
        return {
            "event": "run_end", "t": t0 + 4.0 + pidx, "run_id": run_id,
            "metrics": {
                "counters": {}, "histograms": {},
                "timers": {
                    "re_exchange.exchange_s": {"seconds": 0.5, "calls": 2},
                    "re_exchange.wait_s": {"seconds": 0.1, "calls": 2},
                },
                "gauges": {
                    "re_shard.shards": 2.0,
                    "re_shard.balance": 1.05,
                    "re_shard.rows_max": 120.0,
                    "re_shard.exchange_overlap_ratio": overlap,
                },
            },
        }

    s0 = TelemetrySink(str(directory), run_id=run_id)
    s0.emit(run_start(0))
    s0.emit({"event": "span", "t": t0 + 0.1, "name": "descent/iter",
             "span_id": 1, "parent_id": None, "tid": 1, "thread": "Main",
             "dur_s": 1.0})
    s0.emit({"event": "p2p_send", "t": t0 + 0.21, "peer": 1, "bytes": 400,
             "rows": 10, "dur_s": 0.01, "t_start": t0 + 0.2,
             "corr": "p2p:0>1#1", "tag": "offsets",
             "transport": "p2p_host_async"})
    s0.emit({"event": "p2p_recv", "t": t0 + 0.52, "peer": 1, "bytes": 240,
             "rows": 6, "dur_s": 0.02, "t_start": t0 + 0.5,
             "corr": "p2p:1>0#1", "tag": "offsets",
             "transport": "p2p_host_async"})
    s0.emit(run_end(0, 0.9))
    s0.close()
    if missing_shard:
        return
    s1 = TelemetrySink(str(directory), run_id=run_id, shard_index=1)
    s1.emit(run_start(1))
    s1.emit({"event": "span", "t": t0 + 0.1, "name": "descent/iter",
             "span_id": 1, "parent_id": None, "tid": 7, "thread": "Main",
             "dur_s": 3.0})
    s1.emit({"event": "p2p_recv", "t": t0 + 0.31, "peer": 0, "bytes": 400,
             "rows": 10, "dur_s": 0.02, "t_start": t0 + 0.3,
             "corr": "p2p:0>1#1", "tag": "offsets",
             "transport": "p2p_host_async"})
    if not unmatched:
        s1.emit({"event": "p2p_send", "t": t0 + 0.36, "peer": 0,
                 "bytes": 240, "rows": 6, "dur_s": 0.01,
                 "t_start": t0 + 0.35, "corr": "p2p:1>0#1",
                 "tag": "offsets", "transport": "p2p_host_async"})
    s1.emit(run_end(1, 0.6))
    s1.close()


class TestFleetSink:
    def test_shard_sink_filename_and_schema(self, tmp_path):
        from photon_ml_tpu.obs.sink import TelemetrySink

        s = TelemetrySink(str(tmp_path), run_id="X", shard_index=3)
        assert s.path.endswith("run-X.p3.jsonl")
        s.emit({"event": "run_start", "t": 1.0,
                "schema_version": obs.SCHEMA_VERSION, "run_id": "X",
                "process_index": 3})
        s.close()
        assert validate_run(load_run(s.path)) == []

    def test_configure_single_process_never_shards(self, tmp_path,
                                                   monkeypatch):
        """Fleet telemetry is a MULTI-process behavior: on one process
        the knob changes nothing — canonical filename, no fleet field
        in run_start (the byte-for-byte compatibility contract)."""
        monkeypatch.setenv("PHOTON_TELEMETRY_FLEET", "1")
        path = obs.configure(str(tmp_path / "t"), run_id="solo")
        obs.shutdown()
        assert path.endswith("run-solo.jsonl")
        records = load_run(path)
        assert "fleet" not in records[0]

    def test_fleet_knob_parses_and_follows_re_shard(self, monkeypatch):
        from photon_ml_tpu.obs.sink import fleet_telemetry_enabled

        monkeypatch.delenv("PHOTON_TELEMETRY_FLEET", raising=False)
        monkeypatch.delenv("PHOTON_RE_SHARD", raising=False)
        assert fleet_telemetry_enabled() is False
        monkeypatch.setenv("PHOTON_RE_SHARD", "1")
        assert fleet_telemetry_enabled() is True
        # explicit fleet knob wins over the re-shard default
        monkeypatch.setenv("PHOTON_TELEMETRY_FLEET", "0")
        assert fleet_telemetry_enabled() is False
        monkeypatch.setenv("PHOTON_TELEMETRY_FLEET", "junk")
        with pytest.raises(ValueError):
            fleet_telemetry_enabled()


class TestFleetReport:
    def test_latest_run_skips_shards(self, tmp_path):
        from photon_ml_tpu.obs.report import latest_run

        _write_fleet_fixture(tmp_path)
        # the shard is the newest file on disk; latest_run must still
        # resolve the canonical run (single-process consumers unchanged)
        os.utime(tmp_path / "run-F1.p1.jsonl")
        assert latest_run(str(tmp_path)).endswith("run-F1.jsonl")

    def test_fleet_run_paths_from_dir_file_and_shard(self, tmp_path):
        from photon_ml_tpu.obs.report import fleet_run_paths

        _write_fleet_fixture(tmp_path)
        expect = [str(tmp_path / "run-F1.jsonl"),
                  str(tmp_path / "run-F1.p1.jsonl")]
        assert fleet_run_paths(str(tmp_path)) == expect
        assert fleet_run_paths(expect[0]) == expect
        assert fleet_run_paths(expect[1]) == expect  # a shard walks back
        assert fleet_run_paths(str(tmp_path), run_id="F1") == expect
        with pytest.raises(ValueError, match="no run-NOPE"):
            fleet_run_paths(str(tmp_path), run_id="NOPE")

    def test_summarize_fleet_joins_links_and_names_straggler(
        self, tmp_path
    ):
        from photon_ml_tpu.obs.report import (
            fleet_run_paths,
            format_fleet,
            summarize_fleet,
        )

        _write_fleet_fixture(tmp_path)
        fs = summarize_fleet(fleet_run_paths(str(tmp_path)))
        assert fs["process_count"] == 2 and fs["missing_shards"] == 0
        # per-process phase walls + straggler: p1's descent is 3s vs 1s
        ph = fs["phases"]["descent"]
        assert ph["per_process"] == {"0": 1.0, "1": 3.0}
        assert ph["slowest"] == 1 and abs(ph["imbalance"] - 1.5) < 1e-9
        assert fs["straggler"]["slowest_process"] == 1
        # both links joined, zero unmatched; one-sided wait =
        # recv-start − send-start (0.3−0.2 and 0.5−0.35)
        p2p = fs["p2p"]
        assert p2p["matched"] == 2 and p2p["unmatched"] == 0
        l01 = p2p["links"]["0->1"]
        assert l01["bytes"] == 400 and l01["tags"] == ["offsets"]
        assert abs(l01["one_sided_wait_s"] - 0.1) < 1e-9
        assert abs(p2p["links"]["1->0"]["one_sided_wait_s"] - 0.15) < 1e-9
        # per-process overlap/exchange accounting surfaced
        assert fs["overlap"] == {"0": 0.9, "1": 0.6}
        assert fs["exchange"]["1"]["wait_s"] == pytest.approx(0.1)
        text = format_fleet(fs)
        assert "slowest process p1" in text
        assert "0->1" in text and "0 unmatched" in text
        json.dumps(fs)  # JSON-plain contract

    def test_unmatched_and_missing_shard_are_health_signals(
        self, tmp_path
    ):
        from photon_ml_tpu.obs.report import (
            fleet_run_paths,
            format_fleet,
            summarize_fleet,
        )

        _write_fleet_fixture(tmp_path / "u", unmatched=True)
        fs = summarize_fleet(fleet_run_paths(str(tmp_path / "u")))
        # p0's recv of the missing send stays unmatched — and surfaces
        assert fs["p2p"]["unmatched"] == 1
        assert "unmatched correlated events" in format_fleet(fs)
        _write_fleet_fixture(tmp_path / "m", missing_shard=True)
        fs2 = summarize_fleet(fleet_run_paths(str(tmp_path / "m")))
        assert fs2["missing_shards"] == 1  # run_start said 2 processes
        assert "MISSING" in format_fleet(fs2)

    def test_fleet_gate_metrics_and_gate(self, tmp_path):
        from photon_ml_tpu.obs.report import (
            fleet_run_paths,
            gate_metrics_from_fleet,
            gate_run,
            summarize_fleet,
        )

        _write_fleet_fixture(tmp_path / "a")
        good = gate_metrics_from_fleet(
            summarize_fleet(fleet_run_paths(str(tmp_path / "a")))
        )
        assert good["fleet/unmatched_p2p"] == 0.0
        assert good["fleet/p2p_bytes_total"] == 640.0
        # the overlap gauge gates as the fleet MINIMUM (worst process)
        assert good["re_shard/exchange_overlap_ratio"] == 0.6
        assert good["re_shard/balance"] == 1.05
        failures, _ = gate_run(good, good)  # self-gate passes
        assert not failures
        # an unmatched event (exact tier) and a lost shard both FAIL
        _write_fleet_fixture(tmp_path / "b", unmatched=True)
        bad = gate_metrics_from_fleet(
            summarize_fleet(fleet_run_paths(str(tmp_path / "b")))
        )
        failures, _ = gate_run(bad, good)
        assert any(f["metric"] == "fleet/unmatched_p2p" for f in failures)
        _write_fleet_fixture(tmp_path / "c", missing_shard=True)
        lost = gate_metrics_from_fleet(
            summarize_fleet(fleet_run_paths(str(tmp_path / "c")))
        )
        failures, _ = gate_run(lost, good)
        assert any(
            f["metric"] == "fleet/missing_shards" for f in failures
        )

    def test_fleet_export_merges_pids(self, tmp_path):
        from photon_ml_tpu.obs.report import fleet_run_paths

        _write_fleet_fixture(tmp_path)
        out = tmp_path / "trace.json"
        export_chrome_trace(str(tmp_path), str(out))  # dir form
        trace = json.load(open(out))
        pids = {e.get("pid") for e in trace["traceEvents"]}
        assert pids == {0, 1}
        names = {
            e["args"]["name"] for e in trace["traceEvents"]
            if e["name"] == "process_name"
        }
        assert names == {"process 0", "process 1"}
        # explicit shard-list form matches the dir form
        trace2 = export_chrome_trace(fleet_run_paths(str(tmp_path)))
        assert trace2 == trace
        # single-file export behavior unchanged (no shard merge)
        solo = chrome_trace(load_run(str(tmp_path / "run-F1.jsonl")))
        assert {e.get("pid") for e in solo["traceEvents"]} == {0}


class TestFleetCLI:
    def _main(self, argv):
        from photon_ml_tpu.cli import report as cli

        try:
            cli.main(argv)
        except SystemExit as e:
            return int(e.code or 0)
        return 0

    def test_report_fleet_renders_and_exports(self, tmp_path, capsys):
        _write_fleet_fixture(tmp_path)
        trace_out = tmp_path / "fleet-trace.json"
        rc = self._main(
            ["fleet", str(tmp_path), "--export-trace", str(trace_out)]
        )
        out = capsys.readouterr().out
        assert rc == 0
        assert "fleet run F1" in out and "slowest process p1" in out
        assert "0 unmatched" in out
        trace = json.load(open(trace_out))
        assert {e.get("pid") for e in trace["traceEvents"]} == {0, 1}
        rc = self._main(["fleet", str(tmp_path), "--json"])
        out = capsys.readouterr().out
        assert rc == 0
        fs = json.loads(out)
        assert fs["process_count"] == 2
        # load errors exit 2 (path typo ≠ fleet-health failure)
        rc = self._main(["fleet", str(tmp_path / "nope")])
        capsys.readouterr()
        assert rc == 2

    def test_gate_fleet_baseline_round_trip(self, tmp_path, capsys):
        _write_fleet_fixture(tmp_path / "run")
        base = tmp_path / "fleet-base.json"
        # write a fresh fleet baseline, then gate the same run against
        # it: PASS. The baseline file records kind "fleet".
        rc = self._main(
            ["gate", "--fleet", str(tmp_path / "run"),
             "--write-baseline", str(base)]
        )
        capsys.readouterr()
        assert rc == 0
        doc = json.load(open(base))
        assert doc["source_kind"] == "fleet"
        assert doc["metrics"]["fleet/unmatched_p2p"] == 0.0
        rc = self._main(
            ["gate", "--fleet", str(tmp_path / "run"),
             "--baseline", str(base)]
        )
        out = capsys.readouterr().out
        assert rc == 0 and "gate PASS" in out
        # a run that LOST its shard regresses the merged view
        _write_fleet_fixture(tmp_path / "lost", missing_shard=True)
        rc = self._main(
            ["gate", "--fleet", str(tmp_path / "lost"),
             "--baseline", str(base)]
        )
        out = capsys.readouterr().out
        assert rc == 1 and "fleet/missing_shards" in out


class TestElasticFleetNarrative:
    """ISSUE 14: the recovery narrative renders in-memory degrades and
    rejoins next to the existing peer_lost/roll_call/recovery lines,
    and ``gate --fleet`` grows the exact ``fleet/degraded_descents`` /
    ``fleet/rejoins`` tiers."""

    def _write(self, directory, degrade=True, rejoin=True):
        from photon_ml_tpu.obs.sink import TelemetrySink

        _write_fleet_fixture(directory)
        # append the elastic events to the canonical file's process
        # view via a second mini-run? No — rewrite a dedicated run with
        # the events inline (simplest valid shard)
        import json as _json

        path = os.path.join(str(directory), "run-F1.jsonl")
        recs = [
            _json.loads(line) for line in open(path) if line.strip()
        ]
        extra = []
        if degrade:
            extra.append({
                "event": "degraded_descent", "t": 1_001.0,
                "iteration": 1, "survivors": [0], "lost": [1],
            })
        if rejoin:
            extra.append({
                "event": "rejoin", "t": 1_002.0, "iteration": 2,
                "rejoined": [1], "group": [0, 1],
                "migrated": {"per_entity": 7}, "role": "survivor",
            })
        out = recs[:-1] + extra + [recs[-1]]
        with open(path, "w") as f:
            for r in out:
                f.write(_json.dumps(r) + "\n")

    def test_narrative_renders_degrade_and_rejoin(self, tmp_path):
        from photon_ml_tpu.obs.report import (
            fleet_run_paths,
            format_fleet,
            summarize_fleet,
        )

        self._write(tmp_path)
        fs = summarize_fleet(fleet_run_paths(str(tmp_path)))
        rec = fs["recovery"]
        assert rec["degraded_descents"] == [{
            "process": 0, "iteration": 1, "survivors": [0], "lost": [1],
        }]
        assert rec["rejoins"][0]["rejoined"] == [1]
        assert rec["rejoins"][0]["migrated"] == {"per_entity": 7}
        text = format_fleet(fs)
        assert "degraded_descent: p0 degraded IN PLACE at iteration 1" in text
        assert "rejoin: p0 (survivor) — [1] rejoined" in text
        assert "migrated back: per_entity:7" in text
        # an in-place degrade warns like a checkpoint-anchored recovery
        assert "degraded mid-flight" in text
        json.dumps(fs)

    def test_gate_tiers_are_exact(self, tmp_path):
        from photon_ml_tpu.obs.report import (
            fleet_run_paths,
            gate_metrics_from_fleet,
            gate_run,
            summarize_fleet,
        )

        _write_fleet_fixture(tmp_path / "clean")
        clean = gate_metrics_from_fleet(
            summarize_fleet(fleet_run_paths(str(tmp_path / "clean")))
        )
        assert clean["fleet/degraded_descents"] == 0.0
        assert clean["fleet/rejoins"] == 0.0
        self._write(tmp_path / "elastic")
        elastic = gate_metrics_from_fleet(
            summarize_fleet(fleet_run_paths(str(tmp_path / "elastic")))
        )
        assert elastic["fleet/degraded_descents"] == 1.0
        assert elastic["fleet/rejoins"] == 1.0
        # self-gate passes; a spontaneous degrade/rejoin against the
        # clean baseline trips the exact tier
        failures, _ = gate_run(elastic, elastic)
        assert not failures
        failures, _ = gate_run(elastic, clean)
        names = {f["metric"] for f in failures}
        assert "fleet/degraded_descents" in names
        assert "fleet/rejoins" in names
