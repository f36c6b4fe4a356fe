"""The stage reader (``benchmark/stages.py``) on a small TPU trace with
stage names (``data/small_tpu_stages.xplane.pb``, see
``record_stage_trace.py``): the decoder against ``ProfileData``, the
arithmetic against ``reduce_trace``, and the readers' rules for what is
unstaged and what is not reported."""

import os
import warnings
from types import SimpleNamespace

import pytest

from benchmark import stages, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "small_tpu_stages.xplane.pb")
UNSTAGED_TPU = os.path.join(DATA, "small_tpu.xplane.pb")  # PR 22: no stage
CPU = os.path.join(DATA, "small_cpu.xplane.pb")
# PR 24 found no free chip to record it on (PERF.md section 6)
needs_recording = pytest.mark.skipif(
    not os.path.exists(TRACE),
    reason="small_tpu_stages.xplane.pb is not recorded yet: run record_stage_trace.py on a TPU",
)


@pytest.fixture(scope="module")
def sl():
    if not os.path.exists(TRACE):
        pytest.skip("small_tpu_stages.xplane.pb is not recorded yet")
    return stages.read_slice(TRACE)


@pytest.fixture(scope="module")
def reduced():
    if not os.path.exists(TRACE):
        pytest.skip("small_tpu_stages.xplane.pb is not recorded yet")
    return trace_reduce.reduce_trace(TRACE, slice_span="bench.slice", devices=1)


@pytest.mark.parametrize("path", [
    pytest.param(TRACE, marks=needs_recording), UNSTAGED_TPU, CPU,
])
def test_the_decoder_reads_what_profiledata_reads(path):
    """Plane, line and event names, and every event's start and duration,
    are exactly ``jax.profiler.ProfileData``'s."""
    from jax.profiler import ProfileData

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        want = [
            (plane.name, [
                (line.name, [(e.name, e.start_ns, e.duration_ns) for e in line.events])
                for line in plane.lines
            ])
            for plane in ProfileData.from_file(path).planes
        ]
    got = [
        (p.name, [
            (name, [(p.event_names.get(m, ""), s, d) for m, s, d in rows])
            for name, rows in p.lines.items()
        ])
        for p in stages.read_planes(path)
    ]
    assert got == want


def test_window_and_busy_are_reduce_traces(sl, reduced):
    assert sl.window_s == reduced.window_s
    assert sl.busy_s == pytest.approx(reduced.busy_by_device[0], abs=1e-9)
    assert sl.busy_s > 0


@pytest.mark.parametrize("family", [stages.DESCENT, stages.FIT])
def test_a_familys_parts_add_up_to_busy(sl, reduced, family):
    parts = [
        sl.seconds(within=(s,), outside=family[:i]) for i, s in enumerate(family)
    ]
    assert all(p > 0 for p in parts), parts
    total = sum(parts) + sl.seconds(outside=family)
    assert total == pytest.approx(reduced.busy_by_device[0], abs=1e-9)


def test_stages_are_whole_segments_and_nest(sl):
    solve = [op for op in sl.ops if op.within(("re.solve",))]
    assert solve and all(op.within(("visit.re",)) for op in solve)
    assert all(op.within(("coord.",)) for op in solve)  # a prefix family
    assert not any(op.within(("re.sol", "solve", "re")) for op in sl.ops)
    objective = sl.seconds(within=("glm.objective",))
    search = sl.seconds(within=("lbfgs.line_search",))
    assert 0 < objective <= search  # the objective sits inside the search
    optimizer = sl.seconds(within=("lbfgs.",), outside=("glm.objective",))
    assert optimizer > 0
    assert optimizer + objective == pytest.approx(
        sl.seconds(within=("lbfgs.", "glm.objective")), abs=1e-12
    )


def test_an_operation_without_tf_op_is_unstaged(sl):
    bare = [op for op in sl.ops if not op.path]
    assert bare and sum(op.self_s for op in bare) > 0
    everything = stages.DESCENT + stages.FIT + ("coord.", "visit.re")
    loose = sl.seconds(outside=everything)
    assert loose >= sum(op.self_s for op in bare)
    assert not any(op.within(everything) for op in bare)


def test_same_named_instructions_of_two_programs_are_kept_apart(sl, reduced):
    by_name = {}
    for op in sl.ops:
        by_name.setdefault(op.name, set()).add(op.program)
    shared = {name for name, programs in by_name.items() if len(programs) > 1}
    assert shared, by_name
    assert {op.program for op in sl.ops} == {
        "jit_descent_program", "jit_fit_program", "jit_plain_program"
    }
    for name in shared:  # reduce_trace merges them under the one name
        mine = sum(op.self_s for op in sl.ops if op.name == name)
        assert mine == pytest.approx(reduced.ops[name].self_s, abs=1e-9)
    plain = [op for op in sl.ops if op.program == "jit_plain_program"]
    assert not any(stages._STAGE.match(s) for op in plain for s in op.path)


def test_on_pr_22s_tpu_recording_the_slice_is_reduce_traces():
    """``small_tpu.xplane.pb`` has ``tf_op`` paths and no stage: the window,
    the busy union and every operation's self time are ``reduce_trace``'s,
    a path is split into its segments, and ``while`` has none."""
    got = stages.read_slice(UNSTAGED_TPU)
    want = trace_reduce.reduce_trace(UNSTAGED_TPU, slice_span="bench.slice", devices=1)
    assert got.window_s == want.window_s
    assert got.busy_s == pytest.approx(want.busy_by_device[0], abs=1e-9)
    assert sum(op.self_s for op in got.ops) == pytest.approx(got.busy_s, abs=1e-9)
    assert {op.name for op in got.ops} == set(want.ops)
    for op in got.ops:
        assert op.program == "jit_small_program"
        assert op.count == want.ops[op.name].count
        assert op.self_s == pytest.approx(want.ops[op.name].self_s, abs=1e-9)
    paths = {op.name: op.path for op in got.ops}
    assert paths["fusion.8"] == (
        "jit(small_program)", "while", "body", "closed_call", "dot_general"
    )
    assert paths["while"] == ()
    assert got.seconds(within=("while",)) > 0  # a segment, whatever its name
    assert got.seconds(outside=("while",)) > 0
    assert got.seconds(within=("whil", "dot")) == 0.0  # never part of one


def test_a_trace_without_a_tpu_plane_has_no_slice():
    assert stages.read_slice(CPU) is None


def _obs(work=2.0, ops=()):
    return SimpleNamespace(
        counters={"work": work} if work else {},
        trace=SimpleNamespace(ops={
            name: SimpleNamespace(self_s=s) for name, s in ops
        }),
    )


@pytest.fixture
def traced(monkeypatch):
    """Point the readers at a recorded trace, as if a run had left it."""
    def at(path):
        stages._observed.cache_clear()
        stages._family.cache_clear()
        monkeypatch.setattr(stages, "trace_path", lambda: path)
        monkeypatch.setattr(stages, "_program_has_stages", lambda: True)
    yield at
    stages._observed.cache_clear()
    stages._family.cache_clear()


def test_readers_divide_by_work_and_log_one_table(traced, sl, capsys):
    traced(TRACE)
    obs = _obs(work=2.0)
    got = {
        name: stages.part(obs, stages.DESCENT, name)
        for name in stages.DESCENT + (stages.UNSTAGED,)
    }
    assert sum(got.values()) == pytest.approx(sl.busy_s / 2.0, abs=1e-9)
    assert got["re.solve"] == pytest.approx(
        sl.seconds(within=("re.solve",)) / 2.0
    )
    stages.part(obs, stages.FIT, "glm.objective")
    err = capsys.readouterr().err
    assert err.count("coordinate | stages | seconds") == 1  # one parse, one table
    assert "coord.per_user | visit.re>re.solve" in err
    assert err.count("add up to") == 2  # one identity a family


def test_no_stage_in_a_tpu_trace_reports_nothing(traced, capsys):
    """Executables from a compile cache older than the stage names (or a
    program without them): nothing is better than all-unstaged."""
    traced(UNSTAGED_TPU)
    assert stages.part(_obs(), stages.FIT, "glm.objective") is None
    assert stages.part(_obs(), stages.FIT, stages.UNSTAGED) is None
    assert "no operation of this trace carries a stage" in capsys.readouterr().err


@needs_recording
def test_a_program_without_stages_reports_nothing(traced, monkeypatch):
    traced(TRACE)
    monkeypatch.setattr(stages, "_program_has_stages", lambda: False)
    assert stages.part(_obs(), stages.DESCENT, "re.solve") is None
    monkeypatch.setattr(stages, "_program_has_stages", lambda: True)
    assert stages.part(_obs(work=None), stages.DESCENT, "re.solve") is None


def test_the_cpu_rehearsal_is_all_unstaged(traced):
    traced(None)
    obs = _obs(work=4.0, ops=(("dot_general.0", 0.25), ("copy.6", 0.15)))
    assert stages.part(obs, stages.FIT, stages.UNSTAGED) == pytest.approx(0.1)
    assert stages.part(obs, stages.FIT, "glm.objective") == 0.0

