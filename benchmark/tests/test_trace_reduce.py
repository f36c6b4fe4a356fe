"""The trace reduction on a small recorded trace
(``data/small_cpu.xplane.pb``, see ``record_small_trace.py``) and on
intervals made by hand."""

import os

import pytest

from benchmark import trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small_cpu.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_trace(
        TRACE, slice_span="bench.slice", devices=1, spans=("fit", "fence")
    )


def test_window_is_the_slice_span_and_busy_fits_inside(reduced):
    # three launches of a ~2.5 ms program and one 50 ms pause
    assert 0.05 < reduced.window_s < 0.2
    assert 0.0 < reduced.busy_s < reduced.window_s
    assert reduced.busy_by_device == [reduced.busy_s]


def test_programs_and_operations_are_counted(reduced):
    assert reduced.programs["jit_small_program"][0] == 3
    assert reduced.program_launches() == 3
    assert reduced.op_count(r"^dot_general") == 12  # 4 loop trips x 3 launches
    dots = reduced.op_seconds(r"^dot_general")
    assert 0.5 * reduced.busy_s < dots <= reduced.busy_s
    assert reduced.op_seconds(r"no-such-op") == 0.0


def test_a_loop_is_not_counted_twice(reduced):
    loop = next(op for name, op in reduced.ops.items() if name.startswith("while"))
    assert loop.total_s > 10 * loop.self_s  # its body's time is its children's
    total_self = sum(op.self_s for op in reduced.ops.values())
    assert total_self == pytest.approx(reduced.busy_s, rel=0.05)


def test_the_pause_is_the_longest_gap_and_gaps_are_labelled(reduced):
    assert 0.045 < reduced.longest_gap_s < 0.08
    idle = sum(reduced.idle_by_span.values())
    assert idle == pytest.approx(reduced.window_s - reduced.busy_s, rel=1e-6)
    assert reduced.idle_by_span["(no span)"] >= reduced.longest_gap_s
    assert {"fit", "fence"} & set(reduced.idle_by_span)
    b = reduced.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0].startswith("dot_general")


def test_a_tpu_trace_names_operations_by_their_hlo_text():
    """``data/small_tpu.xplane.pb``: the same recording on a TPU v5e (my chip
    run, PR 22). The program takes 2.7 microseconds there and the device's
    clock runs 1.2 ms behind the host's, so only the launch after the pause
    falls inside the slice span."""
    tpu = trace_reduce.reduce_trace(
        os.path.join(os.path.dirname(TRACE), "small_tpu.xplane.pb"),
        slice_span="bench.slice", devices=1, spans=("fit", "fence"),
    )
    assert tpu.programs == {"jit_small_program": [1, pytest.approx(2.66e-6, rel=0.01)]}
    assert "fusion.8" in tpu.ops and tpu.ops["fusion.8"].count == 4
    assert tpu.ops["fusion.8"].text.startswith("fusion.8  = f32[256,256]")
    assert tpu.op_count(r"\bfusion\(") == 4  # patterns search the HLO text
    # ... without its operands: the reduce consumes the loop's result
    assert "while" not in tpu.ops["reduce_sum.7"].text
    loop = tpu.ops["while"]
    assert loop.self_s < 0.1 * loop.total_s
    assert tpu.busy_s == pytest.approx(2.65e-6, rel=0.02)
    assert tpu.longest_gap_s == pytest.approx(0.0508, rel=0.02)


def test_union_and_self_times_by_hand():
    assert trace_reduce._union([(0, 2), (1, 3), (5, 6), (5.5, 5.8)]) == [(0, 3), (5, 6)]
    assert trace_reduce._clip([(0, 3), (5, 6)], 1, 5.5) == [(1, 3), (5, 5.5)]
    #           a loop [0, 10) holding two operations, then a leaf after it
    events = [(0, 10, "while", ""), (1, 4, "a", ""), (5, 9, "b", ""), (11, 12, "c", "")]
    assert trace_reduce._self_times(events) == [3, 3, 4, 1]


def test_a_trace_without_device_operations_is_an_error(tmp_path):
    empty = tmp_path / "empty.xplane.pb"
    empty.write_bytes(b"")
    with pytest.raises(Exception):
        trace_reduce.reduce_trace(str(empty), slice_span="bench.slice", devices=1)
