"""Analytic device-cost layer (``obs/devcost``) + the report roofline
table and the ``report gate``/``report validate`` CLI. All host-side,
unmarked (no ``kernel`` marker — tier-1 sits near the wall-clock budget;
no Pallas kernel is traced here: the capture machinery is exercised on
small plain jits and the gate on synthetic artifacts)."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import obs
from photon_ml_tpu.obs import devcost
from photon_ml_tpu.obs.metrics import REGISTRY
from photon_ml_tpu.obs.report import (
    DEFAULT_GATE_THRESHOLDS,
    gate_metrics_from_summary,
    gate_run,
    load_gate_metrics,
    resolve_threshold,
    summarize_run,
)


@pytest.fixture
def telemetry(tmp_path):
    """An enabled sink + a clean capture seen-set; always shut down (both
    are process-global — a leak would redirect other tests' records).
    Clears the conftest-pinned ``PHOTON_DEVCOST=0`` (suite-runtime guard)
    so capture follows its production default: on while a sink is
    active."""
    devcost.reset()
    REGISTRY.reset(prefix="devcost.")
    REGISTRY.reset(prefix="hbm.")
    pinned = os.environ.pop("PHOTON_DEVCOST", None)
    path = obs.configure(str(tmp_path / "telemetry"))
    try:
        yield path
    finally:
        obs.shutdown()
        devcost.reset()
        if pinned is not None:
            os.environ["PHOTON_DEVCOST"] = pinned


def _records(path):
    return [json.loads(line) for line in open(path) if line.strip()]


@jax.jit
def _small_prog(x):
    return jnp.dot(x, x.T).sum()


class TestCapture:
    def test_capture_on_compile_only(self, telemetry):
        """First (label, knobs, signature) captures; the repeat — the
        jit-cache-hit shadow — emits NOTHING."""
        x = jnp.ones((16, 16), jnp.float32)
        rec = devcost.capture("t.prog", _small_prog, (x,))
        assert rec is not None
        assert rec["flops"] > 0 and rec["bytes_accessed"] > 0
        assert rec["peak_bytes"] is not None
        assert devcost.capture("t.prog", _small_prog, (x,)) is None
        # a DIFFERENT signature is a fresh executable -> captured
        y = jnp.ones((8, 8), jnp.float32)
        assert devcost.capture("t.prog", _small_prog, (y,)) is not None
        obs.shutdown()
        recs = [r for r in _records(telemetry)
                if r["event"] == "executable_cost"]
        assert len(recs) == 2
        assert recs[0]["label"] == "t.prog"
        assert recs[0]["cost_schema_version"] == devcost.COST_SCHEMA_VERSION
        # registry gauges ride along (every registry snapshot carries them)
        snap = REGISTRY.snapshot(prefix="devcost")
        assert snap["gauges"]["devcost.t.prog.flops"] > 0
        assert snap["counters"]["devcost.captures"]["value"] == 2

    def test_knob_tuple_keying_across_dtype_rungs(self, telemetry,
                                                  monkeypatch):
        """The SAME program/signature re-captures when the knob tuple
        changes — the dtype ladder's rungs are distinct executables."""
        x = jnp.ones((4, 4), jnp.float32)
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "f32")
        r32 = devcost.capture("t.knob", _small_prog, (x,))
        assert r32 is not None and r32["knobs"]["kernel_dtype"] == "f32"
        assert devcost.capture("t.knob", _small_prog, (x,)) is None
        monkeypatch.setenv("PHOTON_KERNEL_DTYPE", "int8")
        rbf = devcost.capture("t.knob", _small_prog, (x,))
        assert rbf is not None and rbf["knobs"]["kernel_dtype"] == "int8"

    def test_knob_memo_invalidates_on_combine_and_replan_flips(
        self, telemetry, monkeypatch
    ):
        """Regression for the lint-found fingerprint gap
        (knob-devcost-missing): ``_knob_raw_state`` did not cover
        ``PHOTON_RE_COMBINE`` / ``PHOTON_RE_REPLAN_IMBALANCE``, so a
        mid-process flip of only one of them reused a stale memoized
        snapshot in capture keys. The memo must now re-key on both."""
        # the snapshot only reports re_combine once the module is loaded
        import photon_ml_tpu.game.random_effect  # noqa: F401

        monkeypatch.delenv("PHOTON_RE_COMBINE", raising=False)
        monkeypatch.delenv("PHOTON_RE_REPLAN_IMBALANCE", raising=False)
        base = devcost.knob_key()
        assert base["re_combine"] == "allreduce"
        monkeypatch.setenv("PHOTON_RE_COMBINE", "segments")
        flipped = devcost.knob_key()
        assert flipped["re_combine"] == "segments"
        monkeypatch.setenv("PHOTON_RE_REPLAN_IMBALANCE", "1.5")
        assert devcost.knob_key()["re_replan_imbalance"] == 1.5

    def test_capture_skips_under_trace(self, telemetry):
        """Tracer leaves skip capture — the enclosing executable is the
        one that gets captured, at its own boundary."""
        before = REGISTRY.snapshot(prefix="devcost")["counters"].get(
            "devcost.captures", {"value": 0.0}
        )["value"]

        @jax.jit
        def outer(x):
            devcost.capture("t.traced", _small_prog, (x,))
            return x * 2

        outer(jnp.ones((4,)))
        after = REGISTRY.snapshot(prefix="devcost")["counters"].get(
            "devcost.captures", {"value": 0.0}
        )["value"]
        assert after == before

    def test_gating_env_overrides_sink(self, tmp_path, monkeypatch):
        devcost.reset()
        x = jnp.ones((3, 3))
        # no sink, no env -> disabled
        monkeypatch.delenv("PHOTON_DEVCOST", raising=False)
        assert not devcost.capture_enabled()
        assert devcost.capture("t.off", _small_prog, (x,)) is None
        # env force-on works sink-less (registry only)
        monkeypatch.setenv("PHOTON_DEVCOST", "1")
        assert devcost.capture("t.on", _small_prog, (x,)) is not None
        # env force-off wins over an active sink
        monkeypatch.setenv("PHOTON_DEVCOST", "0")
        obs.configure(str(tmp_path / "t"))
        try:
            assert not devcost.capture_enabled()
        finally:
            obs.shutdown()
        devcost.reset()

    def test_malformed_env_degrades_to_off_not_crash(self, monkeypatch):
        """The gate check runs on every wired production boundary, so a
        telemetry env-var typo must disable capture, never raise."""
        monkeypatch.setenv("PHOTON_DEVCOST", "true")
        monkeypatch.setattr(devcost, "_warned_bad_env", [False])
        with pytest.warns(UserWarning, match="PHOTON_DEVCOST"):
            assert devcost.capture_enabled() is False
        # warned ONCE; the production call path stays silent and alive
        assert devcost.capture("t.bad", _small_prog,
                               (jnp.ones((2, 2)),)) is None

    def test_captured_wrapper_is_memoized_and_transparent(self):
        w1 = devcost.captured("t", _small_prog)
        w2 = devcost.captured("t", _small_prog)
        assert w1 is w2 and w1 is not _small_prog
        x = jnp.ones((4, 4))
        np.testing.assert_array_equal(
            np.asarray(w1(x)), np.asarray(_small_prog(x))
        )
        # non-lowerable callables (host solver twins) pass through
        def host_fn(a):
            return a

        assert devcost.captured("t", host_fn) is host_fn

    def test_streamed_consumer_captures_once_per_program(self, telemetry):
        """The streamed objective's per-chunk programs capture on the
        FIRST chunk of the first pass only (uniform chunks; passes 2..N
        re-enter the same executable)."""
        from photon_ml_tpu.ops.losses import loss_for_task
        from photon_ml_tpu.ops.streaming import (
            StreamingGLMObjective,
            dense_chunks,
        )
        from photon_ml_tpu.types import TaskType

        rng = np.random.default_rng(0)
        X = rng.normal(size=(64, 6)).astype(np.float32)
        y = (rng.uniform(size=64) < 0.5).astype(np.float32)
        sobj = StreamingGLMObjective(
            chunks=dense_chunks(X, y, chunk_rows=16),
            loss=loss_for_task(TaskType.LOGISTIC_REGRESSION),
            num_features=6,
        )
        w = np.zeros(6, np.float32)
        sobj.value_and_grad(w)
        sobj.value_and_grad(w)  # second pass: same executable, no record
        obs.shutdown()
        recs = [r for r in _records(telemetry)
                if r["event"] == "executable_cost"
                and r["label"] == "streaming.chunk_value_grad"]
        assert len(recs) == 1
        assert recs[0]["bytes_accessed"] > 0


class TestHbmAxes:
    def test_budget_event_records_fallback_source(self, telemetry):
        from photon_ml_tpu.ops.streaming import device_hbm_budget_bytes

        b = device_hbm_budget_bytes(default=123.0)
        assert b == 123.0  # CPU backend exposes no memory stats
        device_hbm_budget_bytes(default=123.0)  # event is once-per-run
        obs.shutdown()
        evs = [r for r in _records(telemetry) if r["event"] == "hbm_budget"]
        assert len(evs) == 1
        assert evs[0]["source"] == "fallback_default"
        assert evs[0]["budget_bytes"] == 123.0
        snap = REGISTRY.snapshot(prefix="hbm")
        assert snap["gauges"]["hbm.budget_queried"] == 0.0

    def test_watermark_sampled_at_root_span_exit(self, telemetry):
        from photon_ml_tpu.obs.spans import span

        with span("fit/root"):
            with span("fit/inner"):
                pass
        obs.shutdown()
        wm = [r for r in _records(telemetry)
              if r["event"] == "hbm_watermark"]
        # CPU: one explicit unavailability record per run, never more
        # (inner spans are not roots; repeats are deduped per sink)
        assert len(wm) == 1
        assert wm[0]["available"] is False
        assert wm[0]["root_span"] == "fit/root"


def _write_cost_run(directory, run_id, labels_to_cost, wall_records=()):
    """A schema-valid synthetic run with executable_cost records."""
    path = obs.configure(str(directory), run_id=run_id)
    from photon_ml_tpu.obs.spans import emit_event, span

    with span("fit/root"):
        for label, (flops, bytes_accessed) in labels_to_cost.items():
            emit_event(
                "executable_cost",
                cost_schema_version=devcost.COST_SCHEMA_VERSION,
                label=label, knobs={"kernel_dtype": "f32"},
                arg_sig="deadbeef", flops=flops,
                bytes_accessed=bytes_accessed,
                arith_intensity=flops / bytes_accessed,
                memory={}, peak_bytes=int(bytes_accessed // 2),
                peak_is_estimate=True, capture_s=0.01,
            )
        for ev in wall_records:
            emit_event(**ev)
    obs.shutdown()
    return path


class TestReportRoofline:
    def test_summary_aggregates_and_renders_roofline(self, tmp_path):
        devcost.reset()
        run = _write_cost_run(
            tmp_path, "roofrun",
            {"optim.lbfgs_minimize": (1000.0, 500.0),
             "streaming.chunk_value_grad": (2000.0, 100.0)},
        )
        s = summarize_run(run)
        dc = s["devcost"]
        assert dc["optim.lbfgs_minimize"]["arith_intensity"] == 2.0
        assert dc["streaming.chunk_value_grad"]["captures"] == 1
        assert s["hbm"]["memory_stats_available"] is False
        from photon_ml_tpu.obs.report import format_summary

        text = format_summary(s)
        assert "analytic device cost" in text
        assert "optim.lbfgs_minimize" in text
        assert "memory_stats unavailable" in text

    def test_mixed_knob_tuples_split_into_per_rung_rows(self, tmp_path):
        """One run capturing a label under TWO knob tuples (the reduced-
        rung + anchor pattern) must not merge the rungs' bytes into one
        row. Naming is GATE-STABLE: the variant matching the run's own
        knobs keeps the bare label (what a single-variant baseline run
        produced); only the off-run variant is suffixed."""
        devcost.reset()
        path = obs.configure(str(tmp_path), run_id="mixed")
        from photon_ml_tpu.obs.spans import emit_event
        from photon_ml_tpu.ops.sparse_tiled import kernel_dtype

        native = kernel_dtype()  # the run_start snapshot records this
        other = "int8" if native != "int8" else "f32"
        for rung, b in ((native, 1000.0), (other, 500.0)):
            emit_event(
                "executable_cost", label="sparse_tiled.tiled_apply",
                knobs={"kernel_dtype": rung}, arg_sig="x",
                flops=100.0, bytes_accessed=b,
                memory={}, peak_bytes=1, peak_is_estimate=True,
                capture_s=0.0,
            )
        obs.shutdown()
        dc = summarize_run(path)["devcost"]
        assert set(dc) == {
            "sparse_tiled.tiled_apply",
            f"sparse_tiled.tiled_apply[kernel_dtype={other}]",
        }
        assert dc["sparse_tiled.tiled_apply"]["bytes_accessed"] == 1000.0
        assert dc[f"sparse_tiled.tiled_apply[kernel_dtype={other}]"][
            "bytes_accessed"
        ] == 500.0

    def test_diff_renders_bytes_delta(self, tmp_path):
        devcost.reset()
        a = _write_cost_run(tmp_path / "a", "runA",
                            {"optim.lbfgs_minimize": (1000.0, 400.0)})
        b = _write_cost_run(tmp_path / "b", "runB",
                            {"optim.lbfgs_minimize": (1000.0, 200.0)})
        from photon_ml_tpu.obs.report import diff_summaries

        text = diff_summaries(summarize_run(a), summarize_run(b))
        assert "analytic bytes-accessed" in text
        assert "0.50" in text  # the halving is the readout


class TestGate:
    BASE = {"devcost/x/bytes_accessed": 1000.0, "wall_s": 10.0}

    def test_pass_fail_and_threshold_edges(self):
        # identical -> pass
        failures, _ = gate_run(dict(self.BASE), dict(self.BASE))
        assert not failures
        # devcost tier is tight (rel 0.02): exactly at the limit passes,
        # just above fails
        cur = dict(self.BASE, **{"devcost/x/bytes_accessed": 1020.0})
        assert not gate_run(cur, self.BASE)[0]
        cur["devcost/x/bytes_accessed"] = 1020.1
        failures, lines = gate_run(cur, self.BASE)
        assert [f["metric"] for f in failures] == [
            "devcost/x/bytes_accessed"
        ]
        assert any("FAIL" in ln for ln in lines)
        # wall tier is loose: 10 -> 19.9 is within rel 1.0 + abs 10
        assert not gate_run(dict(self.BASE, wall_s=19.9), self.BASE)[0]
        # improvement is never a regression
        assert not gate_run(
            {"devcost/x/bytes_accessed": 1.0, "wall_s": 0.1}, self.BASE
        )[0]

    def test_missing_metric_fails_unless_allowed(self):
        cur = {"wall_s": 10.0}
        failures, _ = gate_run(cur, self.BASE)
        assert any(f["problem"] == "missing" for f in failures)
        assert not gate_run(cur, self.BASE, allow_missing=True)[0]

    def test_threshold_resolution_and_overrides(self):
        assert resolve_threshold(
            "A2/devcost/x/flops", DEFAULT_GATE_THRESHOLDS
        )["rel"] == 0.02
        assert resolve_threshold(
            "cfg/wall_s", DEFAULT_GATE_THRESHOLDS
        )["rel"] == 1.0
        # custom override wins by longest match
        th = {"devcost/x/": {"rel": 5.0}}
        cur = dict(self.BASE, **{"devcost/x/bytes_accessed": 4000.0})
        assert gate_run(cur, self.BASE)[0]
        assert not gate_run(cur, self.BASE, thresholds=th)[0]

    def test_empty_baseline_raises(self):
        with pytest.raises(ValueError):
            gate_run({"a": 1.0}, {})

    def test_metrics_from_summary_and_bench(self, tmp_path):
        """A telemetry run's summary flattens into gateable metrics."""
        devcost.reset()
        run = _write_cost_run(tmp_path, "g",
                              {"optim.lbfgs_minimize": (10.0, 5.0)})
        m = gate_metrics_from_summary(summarize_run(run))
        assert m["devcost/optim.lbfgs_minimize/bytes_accessed"] == 5.0
        assert "wall_s" in m

    def test_load_gate_metrics_detects_formats(self, tmp_path):
        devcost.reset()
        run = _write_cost_run(tmp_path / "t", "fmt",
                              {"l": (10.0, 5.0)})
        kind, m = load_gate_metrics(run)
        assert kind == "telemetry" and "devcost/l/bytes_accessed" in m
        # telemetry DIR resolves to the newest run
        kind, m2 = load_gate_metrics(str(tmp_path / "t"))
        assert kind == "telemetry" and m2 == m
        # a JSON document that is neither a run nor a gate baseline (the
        # per-config format of a retired harness) is refused, not guessed
        doc_path = tmp_path / "doc.json"
        doc_path.write_text(json.dumps(
            {"configs": {"A": {"sec_per_solve": 1.0, "telemetry": {}}}}
        ))
        with pytest.raises(ValueError, match="invalid telemetry run"):
            load_gate_metrics(str(doc_path))
        base_path = tmp_path / "base.json"
        base_path.write_text(json.dumps(
            {"gate_baseline": 1, "metrics": {"x": 2.0}}
        ))
        kind, gm = load_gate_metrics(str(base_path))
        assert kind == "baseline" and gm == {"x": 2.0}


class TestCli:
    def _run(self, argv):
        from photon_ml_tpu.cli.report import main

        with pytest.raises(SystemExit) as e:
            main(argv)
        return e.value.code

    def test_gate_cli_exit_codes(self, tmp_path, capsys):
        devcost.reset()
        run = _write_cost_run(tmp_path / "r", "cli",
                              {"l": (100.0, 50.0)})
        # a run gates clean against its own baseline
        base = str(tmp_path / "base.json")
        assert self._run(["gate", run, "--write-baseline", base]) == 0
        assert self._run(["gate", run, "--baseline", base]) == 0
        assert "gate PASS" in capsys.readouterr().out
        # a threshold-violating synthetic run exits nonzero
        devcost.reset()
        worse = _write_cost_run(tmp_path / "w", "cliworse",
                                {"l": (100.0, 80.0)})
        assert self._run(["gate", worse, "--baseline", base]) == 1
        assert "gate FAIL" in capsys.readouterr().out
        # --json shape
        assert self._run(["gate", worse, "--baseline", base,
                          "--json"]) == 1
        out = json.loads(capsys.readouterr().out)
        assert out["pass"] is False and out["failures"]

    def test_gate_cli_rejects_incomparable_kinds(self, tmp_path, capsys):
        devcost.reset()
        run = _write_cost_run(tmp_path / "r", "k", {"l": (1.0, 1.0)})
        # a baseline of a fleet view (the fleet-only metrics every merged
        # view carries) shares no metric name with one run's summary: the
        # gate refuses to compare them
        fleet_base = tmp_path / "fleet.json"
        fleet_base.write_text(json.dumps({
            "gate_baseline": 1, "source_kind": "fleet",
            "metrics": {"fleet/processes": 2.0, "fleet/missing_shards": 0.0,
                        "fleet/unmatched_p2p": 0.0},
        }))
        code = self._run(["gate", run, "--baseline", str(fleet_base)])
        assert code == 2
        assert "no comparable metrics" in capsys.readouterr().out

    def test_gate_cli_update_and_verify_never_persists_a_failure(
        self, tmp_path, capsys
    ):
        """--baseline + --write-baseline gates against the PREVIOUS
        baseline and writes the new one only on PASS — even when both
        point at the SAME path."""
        devcost.reset()
        good = _write_cost_run(tmp_path / "g", "uv1", {"l": (100.0, 50.0)})
        base = str(tmp_path / "base.json")
        assert self._run(["gate", good, "--write-baseline", base]) == 0
        before = json.load(open(base))
        devcost.reset()
        worse = _write_cost_run(tmp_path / "w", "uv2", {"l": (100.0, 80.0)})
        # same-path update-and-verify with a regressed run: FAILS against
        # the OLD baseline and leaves the file untouched
        assert self._run(["gate", worse, "--baseline", base,
                          "--write-baseline", base]) == 1
        out = capsys.readouterr().out
        assert "NOT writing" in out
        assert json.load(open(base)) == before
        # a passing run DOES refresh the baseline
        assert self._run(["gate", good, "--baseline", base,
                          "--write-baseline", base]) == 0
        assert json.load(open(base))["source_kind"] == "telemetry"

    def test_gate_cli_load_errors_exit_2(self, tmp_path, capsys):
        """Unreadable artifacts exit 2 with a message — a CI script must
        distinguish 'could not load' from a genuine regression (1)."""
        devcost.reset()
        run = _write_cost_run(tmp_path / "r", "le", {"l": (1.0, 1.0)})
        assert self._run(["gate", str(tmp_path / "nope.jsonl"),
                          "--baseline", run]) == 2
        assert "cannot load run" in capsys.readouterr().out
        empty = tmp_path / "emptydir"
        empty.mkdir()
        assert self._run(["gate", run, "--baseline", str(empty)]) == 2
        assert "cannot load baseline" in capsys.readouterr().out
        # --json keeps its contract on the error path too
        assert self._run(["gate", run, "--baseline", str(empty),
                          "--json"]) == 2
        out = json.loads(capsys.readouterr().out)
        assert out["pass"] is False and "cannot load" in out["error"]

    def test_validate_cli_exit_codes(self, tmp_path, capsys):
        devcost.reset()
        run = _write_cost_run(tmp_path / "v", "val", {"l": (1.0, 1.0)})
        assert self._run(["validate", run]) == 0
        assert "valid" in capsys.readouterr().out
        bad = tmp_path / "bad.jsonl"
        bad.write_text('{"not": "telemetry"}\n')
        assert self._run(["validate", str(bad)]) == 1
        assert "INVALID" in capsys.readouterr().out
        assert self._run(["validate", str(bad), "--json"]) == 1
        assert json.loads(capsys.readouterr().out)["problems"]
