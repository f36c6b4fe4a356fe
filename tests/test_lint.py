"""The AST invariant checker (photon_ml_tpu/analysis) — analyzer tests.

One seeded-violation fixture per pass (bad parse, missing static key,
unlocked cache mutation, swallowed except, dangling telemetry consumer),
a clean fixture asserting zero false positives, a suppression-file
round-trip, and the tier-1 drift tests: the checker runs over THIS
installed package (so knob/telemetry drift fails the suite, not just
``scripts/gate_quick.sh``), and a knob injected into a copy of the real
``bench.py`` RETUNE_ENV without registry wiring is demonstrably caught.

All host-side stdlib-ast work — no jax tracing, no markers.
"""

from __future__ import annotations

import json
import os
import textwrap

import pytest

from photon_ml_tpu.analysis import (
    concurrency_pass, exceptions_pass, jit_keys_pass, knobs_pass,
    telemetry_pass,
)
from photon_ml_tpu.analysis.core import (
    Project, apply_waivers, load_baseline, write_baseline,
)
from photon_ml_tpu.analysis.registry import (
    KNOBS, Knob, check_retune_tables, render_knob_table,
)
from photon_ml_tpu.analysis.runner import discover_root, lint


def _write(root, relpath: str, source: str) -> None:
    path = os.path.join(root, relpath)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        f.write(textwrap.dedent(source))


def _project(tmp_path, **kw) -> Project:
    kw.setdefault("package_dirs", ("pkg",))
    return Project(root=str(tmp_path), **kw)


MINI_REGISTRY = (
    Knob(
        name="PHOTON_TEST_INT", kind="int", parse="strict_int",
        default="0", owner="pkg/mod.py", doc="test int knob",
        accessors=("test_int_knob",), retune_global="TEST_INT",
        exempt=(("retune", "test"), ("sink", "test")),
    ),
    Knob(
        name="PHOTON_TEST_PATH", kind="path", parse="raw",
        default="unset", owner="pkg/mod.py", doc="test path knob",
        exempt=(("retune", "test"), ("sink", "test")),
    ),
)


# -- pass 1: knob discipline -------------------------------------------------


class TestKnobPass:
    def test_unregistered_env_read_is_caught(self, tmp_path):
        _write(tmp_path, "pkg/mod.py", """
            import os

            def f():
                return os.environ.get("PHOTON_TOTALLY_NEW")
        """)
        fs = knobs_pass.scan_env_reads(
            _project(tmp_path), registry=MINI_REGISTRY
        )
        assert [f.code for f in fs] == ["knob-unregistered"]
        assert fs[0].scope == "PHOTON_TOTALLY_NEW"

    def test_truthy_parse_of_numeric_knob_is_caught(self, tmp_path):
        # the PHOTON_DISABLE_FUSED bug shape: '0' is truthy, =0 inverts
        _write(tmp_path, "pkg/mod.py", """
            import os

            def f():
                return not os.environ.get("PHOTON_TEST_INT")
        """)
        fs = knobs_pass.scan_env_reads(
            _project(tmp_path), registry=MINI_REGISTRY
        )
        assert [f.code for f in fs] == ["knob-truthy-parse"]

    def test_strict_parse_and_path_truthiness_are_clean(self, tmp_path):
        _write(tmp_path, "pkg/mod.py", """
            import os

            def f():
                env = os.environ.get("PHOTON_TEST_INT")
                if env is not None and env != "":
                    return int(env) != 0
                return False

            def g():
                # truthiness on a path knob is fine by design
                return os.environ.get("PHOTON_TEST_PATH") or "/tmp/x"
        """)
        fs = knobs_pass.scan_env_reads(
            _project(tmp_path), registry=MINI_REGISTRY
        )
        assert fs == []

    def test_retune_table_drift_both_directions(self, tmp_path):
        registry = MINI_REGISTRY + (Knob(
            name="PHOTON_TEST_SWEPT", kind="int", parse="strict_int",
            default="1", owner="pkg/mod.py", doc="swept knob",
            retune_global="TEST_SWEPT", retune_table="RETUNE_ENV",
            exempt=(("sink", "test"),),
        ),)
        _write(tmp_path, "bench.py", """
            RETUNE_ENV = {
                "PHOTON_NOT_IN_REGISTRY": "NOT_IN_REGISTRY",
            }
        """)
        fs = knobs_pass.check_surfaces(
            _project(tmp_path), registry=registry
        )
        codes = sorted(f.code for f in fs)
        assert codes == [
            "knob-retune-missing", "knob-retune-unregistered",
        ]
        by_code = {f.code: f for f in fs}
        assert by_code["knob-retune-missing"].scope == "PHOTON_TEST_SWEPT"
        assert by_code["knob-retune-unregistered"].scope == \
            "PHOTON_NOT_IN_REGISTRY"


# -- pass 2: jit cache keys --------------------------------------------------


class TestJitKeysPass:
    def test_accessor_call_inside_jit_is_caught(self, tmp_path):
        # the PR-2 class: knob read under trace = baked-in stale value
        _write(tmp_path, "pkg/mod.py", """
            import jax

            @jax.jit
            def f(x):
                return x * (2 if kernel_dtype() == "f32" else 1)
        """)
        fs = jit_keys_pass.run(_project(tmp_path))
        assert [f.code for f in fs] == ["jit-knob-accessor"]

    def test_retune_global_and_env_read_inside_jit(self, tmp_path):
        _write(tmp_path, "pkg/mod.py", """
            import os
            from functools import partial
            import jax

            @partial(jax.jit, static_argnames=("n",))
            def f(x, n):
                k = os.environ.get("PHOTON_GROUPS_PER_RUN")
                return x + GROUPS_PER_RUN

            def g(x):
                return x

            _G = jax.jit(g)
        """)
        fs = jit_keys_pass.run(_project(tmp_path))
        codes = sorted(f.code for f in fs)
        assert codes == ["jit-env-read", "jit-retune-global"]

    def test_static_arg_discipline_is_clean(self, tmp_path):
        # the repo idiom: read at call site, pass as static argument
        _write(tmp_path, "pkg/mod.py", """
            from functools import partial
            import jax

            @partial(jax.jit, static_argnames=("groups_per_run",))
            def _apply(x, groups_per_run):
                return x * groups_per_run

            def apply(x):
                return _apply(x, groups_per_run=kernel_dtype_outside())
        """)
        assert jit_keys_pass.run(_project(tmp_path)) == []


# -- pass 3: concurrency -----------------------------------------------------


class TestConcurrencyPass:
    def test_unlocked_mutation_in_pool_module_is_caught(self, tmp_path):
        # the PR-3 _FP_MEMO class: a worker pool + a bare module cache
        _write(tmp_path, "pkg/mod.py", """
            import threading
            from concurrent.futures import ThreadPoolExecutor

            _CACHE = {}
            _POOL = ThreadPoolExecutor(2)

            def remember(k, v):
                _CACHE[k] = v
        """)
        fs = concurrency_pass.run(_project(tmp_path))
        assert [f.code for f in fs] == ["conc-unlocked-mutation"]
        assert "_CACHE" in fs[0].scope

    def test_locked_and_locked_helper_and_waiver_are_clean(self, tmp_path):
        _write(tmp_path, "pkg/mod.py", """
            import threading

            _LOCK = threading.Lock()
            _CACHE = {}
            _MEMO = []

            def remember(k, v):
                with _LOCK:
                    _CACHE[k] = v

            def _evict_over_limits_locked():
                _CACHE.clear()

            def memoize(v):
                # lint: waive(conc-unlocked-mutation) single-writer memo
                _MEMO.append(v)
        """)
        project = _project(tmp_path)
        fs, waived = apply_waivers(
            project, concurrency_pass.run(project)
        )
        assert fs == []
        assert waived == 1

    def test_threadless_module_is_out_of_scope(self, tmp_path):
        _write(tmp_path, "pkg/mod.py", """
            _CACHE = {}

            def remember(k, v):
                _CACHE[k] = v
        """)
        assert concurrency_pass.run(_project(tmp_path)) == []


# -- pass 4: exception discipline --------------------------------------------


class TestExceptionsPass:
    def test_swallow_in_scoped_module_is_caught(self, tmp_path):
        _write(tmp_path, "photon_ml_tpu/parallel/bad.py", """
            def drain():
                try:
                    risky()
                except OSError:
                    pass
        """)
        fs = exceptions_pass.run(Project(
            root=str(tmp_path), package_dirs=("photon_ml_tpu",)
        ))
        assert [f.code for f in fs] == ["except-swallow"]

    def test_raise_emit_and_counter_are_clean(self, tmp_path):
        _write(tmp_path, "photon_ml_tpu/parallel/ok.py", """
            def a():
                try:
                    risky()
                except OSError as e:
                    raise PeerLost(1) from e

            def b():
                try:
                    risky()
                except OSError:
                    emit_event("exchange_drain_error", tag="x")

            def c():
                try:
                    risky()
                except OSError:
                    REGISTRY.counter_inc("p2p.drain_errors")
        """)
        fs = exceptions_pass.run(Project(
            root=str(tmp_path), package_dirs=("photon_ml_tpu",)
        ))
        assert fs == []

    def test_out_of_scope_module_swallows_freely(self, tmp_path):
        _write(tmp_path, "photon_ml_tpu/obs/guard.py", """
            def sample():
                try:
                    risky()
                except Exception:
                    pass  # telemetry must never take down the run
        """)
        fs = exceptions_pass.run(Project(
            root=str(tmp_path), package_dirs=("photon_ml_tpu",)
        ))
        assert fs == []


# -- pass 5: telemetry surfaces ----------------------------------------------


class TestTelemetryPass:
    def _tree(self, tmp_path, report_body: str, emitter_body: str):
        _write(
            tmp_path, "photon_ml_tpu/obs/report.py", report_body
        )
        _write(tmp_path, "photon_ml_tpu/obs/__init__.py", "")
        _write(tmp_path, "photon_ml_tpu/__init__.py", "")
        _write(tmp_path, "photon_ml_tpu/emitter.py", emitter_body)
        return Project(
            root=str(tmp_path), package_dirs=("photon_ml_tpu",)
        )

    def test_dangling_consumer_is_caught(self, tmp_path):
        project = self._tree(
            tmp_path,
            report_body="""
                def summarize(records):
                    return [r for r in records
                            if r["event"] == "ghost_event"]
            """,
            emitter_body="""
                def run():
                    emit_event("real_event", x=1)
            """,
        )
        fs = telemetry_pass.run(project)
        codes = {f.code for f in fs}
        assert "telem-dangling-consumer" in codes
        assert any(f.scope == "event:ghost_event" for f in fs)

    def test_unrendered_emission_is_caught(self, tmp_path):
        project = self._tree(
            tmp_path,
            report_body="""
                def summarize(records):
                    return [r for r in records
                            if r["event"] == "real_event"]
            """,
            emitter_body="""
                def run():
                    emit_event("real_event", x=1)
                    emit_event("orphan_event", x=2)
            """,
        )
        fs = telemetry_pass.run(project)
        assert [f.scope for f in fs] == ["event:orphan_event"]
        assert fs[0].code == "telem-unrendered-emission"

    def test_agreeing_surfaces_are_clean(self, tmp_path):
        project = self._tree(
            tmp_path,
            report_body="""
                def summarize(records, metrics):
                    spans = [r for r in records
                             if r["event"] == "real_event"]
                    counters = metrics.get("counters", {})
                    hits = counters.get("cache.hits", {})
                    return spans, hits
            """,
            emitter_body="""
                def run():
                    emit_event("real_event", x=1)
                    REGISTRY.counter_inc("cache.hits")
            """,
        )
        assert telemetry_pass.run(project) == []


# -- suppression baseline ----------------------------------------------------


class TestSuppression:
    def test_baseline_round_trip(self, tmp_path):
        _write(tmp_path, "photon_ml_tpu/__init__.py", "")
        _write(tmp_path, "photon_ml_tpu/mod.py", """
            import os

            def f():
                return os.environ.get("PHOTON_NOT_REGISTERED")
        """)
        root = str(tmp_path)
        doc = lint(root)
        assert doc["exit"] == 1
        assert [f.code for f in doc["_active"]] == ["knob-unregistered"]

        bp = os.path.join(root, "lint_baseline.json")
        write_baseline(bp, doc["_active"], reason="triaged for the test")
        keys, entries = load_baseline(bp)
        assert len(keys) == len(entries) == 1
        assert entries[0]["reason"] == "triaged for the test"

        doc2 = lint(root)
        assert doc2["exit"] == 0
        assert doc2["suppressed"] == 1
        assert doc2["findings"] == []

    def test_baseline_does_not_cover_new_findings(self, tmp_path):
        _write(tmp_path, "photon_ml_tpu/__init__.py", "")
        _write(tmp_path, "photon_ml_tpu/mod.py", """
            import os

            def f():
                return os.environ.get("PHOTON_NOT_REGISTERED")
        """)
        root = str(tmp_path)
        write_baseline(
            os.path.join(root, "lint_baseline.json"),
            lint(root)["_active"],
        )
        # a SECOND unregistered knob appears: baseline must not absorb it
        _write(tmp_path, "photon_ml_tpu/mod2.py", """
            import os

            def g():
                return os.environ.get("PHOTON_ALSO_NEW")
        """)
        doc = lint(root)
        assert doc["exit"] == 1
        assert [f.scope for f in doc["_active"]] == ["PHOTON_ALSO_NEW"]


# -- the CLI contract --------------------------------------------------------


class TestCli:
    def test_json_contract_and_exit_codes(self, tmp_path, capsys):
        from photon_ml_tpu.cli import lint as lint_cli

        _write(tmp_path, "photon_ml_tpu/__init__.py", "")
        _write(tmp_path, "photon_ml_tpu/mod.py", """
            import os

            def f():
                return os.environ.get("PHOTON_NOT_REGISTERED")
        """)
        with pytest.raises(SystemExit) as exc:
            lint_cli.main(["--root", str(tmp_path), "--json"])
        assert exc.value.code == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["lint_schema_version"] == 1
        assert doc["exit"] == 1
        assert doc["findings"][0]["code"] == "knob-unregistered"
        assert doc["findings"][0]["scope"] == "PHOTON_NOT_REGISTERED"


# -- the registry itself -----------------------------------------------------


class TestRegistry:
    def test_every_knob_requires_or_exempts_each_surface(self):
        for k in KNOBS:
            assert k.retune_table or k.exempt_reason("retune"), k.name
            assert k.sink_key or k.exempt_reason("sink"), k.name

    def test_render_knob_table_covers_registry(self):
        table = render_knob_table()
        for k in KNOBS:
            assert f"`{k.name}`" in table, k.name

    def test_46_knobs_none_for_a_segment_schedule_and_the_readme_has_them(self):
        """The tile-COO kernel has one schedule (PR 33): no knob, retune
        global or snapshot key selects one, and the README's table is the
        registry's, row for row."""
        assert len(KNOBS) == 46
        for k in KNOBS:
            for text in (k.name, k.retune_global, k.sink_key, k.doc):
                assert "pipeline_segments" not in (text or "").lower(), k.name
        readme = os.path.join(
            discover_root(os.path.dirname(__file__)), "README.md"
        )
        with open(readme) as f:
            assert render_knob_table() in f.read()

    def test_check_retune_tables_raises_on_drift(self):
        good = {
            t: {k.name: k.retune_global for k in KNOBS
                if k.retune_table == t}
            for t in ("RETUNE_ENV", "RETUNE_ENV_PREFETCH",
                      "RETUNE_ENV_RE", "RETUNE_ENV_SHARD")
        }
        check_retune_tables(good)  # the committed wiring passes
        with pytest.raises(ValueError, match="PHOTON_SURPRISE"):
            bad = {k: dict(v) for k, v in good.items()}
            bad["RETUNE_ENV"]["PHOTON_SURPRISE"] = "SURPRISE"
            check_retune_tables(bad)
        with pytest.raises(ValueError, match="PHOTON_KERNEL_DTYPE"):
            bad = {k: dict(v) for k, v in good.items()}
            del bad["RETUNE_ENV"]["PHOTON_KERNEL_DTYPE"]
            check_retune_tables(bad)


# -- tier-1 drift gates over the INSTALLED package ---------------------------


class TestRepoDrift:
    """The acceptance tests: the real repo lints clean, and seeded drift
    in the real bench.py is caught."""

    def test_repo_lints_clean(self):
        root = discover_root(os.path.dirname(__file__))
        doc = lint(root)
        assert doc["findings"] == [], (
            "photon-ml-tpu lint found non-suppressed findings — fix, "
            "waive inline with a reason, or triage into "
            "lint_baseline.json:\n"
            + "\n".join(
                f"{f['file']}:{f['line']} [{f['code']}] {f['message']}"
                for f in doc["findings"]
            )
        )
        assert doc["exit"] == 0

    def test_knob_added_to_bench_without_wiring_is_caught(self, tmp_path):
        # the ISSUE-15 acceptance demo: inject an unwired knob into a
        # copy of the REAL bench RETUNE_ENV; the knob pass must convict
        root = discover_root(os.path.dirname(__file__))
        with open(os.path.join(root, "bench.py"), encoding="utf-8") as f:
            src = f.read()
        marker = "RETUNE_ENV = {"
        assert marker in src
        src = src.replace(
            marker,
            marker + '\n    "PHOTON_TOTALLY_NEW_KNOB": "TOTALLY_NEW",',
            1,
        )
        bench_copy = tmp_path / "bench_drifted.py"
        bench_copy.write_text(src)
        project = Project(root=root, bench_path=str(bench_copy))
        fs = knobs_pass.run(project)
        drift = [
            f for f in fs
            if f.code == "knob-retune-unregistered"
            and f.scope == "PHOTON_TOTALLY_NEW_KNOB"
        ]
        assert drift, "injected RETUNE_ENV knob was not caught"

    def test_stale_jit_key_seeded_into_real_kernel_is_caught(self):
        # move a retune-global read INSIDE the real jitted kernel entry
        # (the PR-2 stale-executable shape) and assert conviction
        from photon_ml_tpu.analysis.core import ModuleInfo

        root = discover_root(os.path.dirname(__file__))
        rel = "photon_ml_tpu/ops/sparse_tiled.py"
        with open(os.path.join(root, rel), encoding="utf-8") as f:
            lines = f.read().splitlines()
        for i, ln in enumerate(lines):
            if ln.startswith("def _tiled_apply_jit("):
                j = i
                while not lines[j].rstrip().endswith(":"):
                    j += 1
                lines.insert(j + 1, "    _bad = KERNEL_DTYPE")
                break
        else:
            pytest.fail("jitted kernel entry _tiled_apply_jit not found")
        project = Project(root=root)
        project._modules[rel] = ModuleInfo(
            "<mutated>", rel, "\n".join(lines)
        )
        fs = jit_keys_pass.run(project)
        assert any(
            f.code == "jit-retune-global"
            and f.scope == "_tiled_apply_jit:KERNEL_DTYPE"
            for f in fs
        ), "seeded stale-jit-key read was not caught"

    def test_sink_snapshot_key_removal_is_caught(self, tmp_path):
        # drift in the OTHER direction: a knob snapshot key disappears
        root = discover_root(os.path.dirname(__file__))
        sink_rel = os.path.join("photon_ml_tpu", "obs", "sink.py")
        with open(os.path.join(root, sink_rel), encoding="utf-8") as f:
            src = f.read()
        assert 'knobs["kernel_dtype"]' in src
        src = src.replace('knobs["kernel_dtype"]', 'knobs["kernel_dtypo"]')
        from photon_ml_tpu.analysis.core import ModuleInfo

        project = Project(root=root)
        # seed the module cache with the drifted sink so only it differs
        project._modules["photon_ml_tpu/obs/sink.py"] = ModuleInfo(
            str(tmp_path / "sink_drifted.py"),
            "photon_ml_tpu/obs/sink.py",
            src,
        )
        fs = knobs_pass.check_surfaces(project)
        assert any(
            f.code == "knob-sink-missing"
            and f.scope == "PHOTON_KERNEL_DTYPE"
            for f in fs
        )
        assert any(
            f.code == "knob-sink-unregistered"
            and f.scope == "kernel_dtypo"
            for f in fs
        )
