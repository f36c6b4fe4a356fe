"""A bucket's residual offsets in either staged form (``game/random_effect.
_bucket_offsets``): by one index a slot, or, where ``_run_starts`` finds
every lane of the bucket to be one run of consecutive rows, by one run start
a lane. The two forms are one result bit for bit, zero signs included; the
choice is read from the data, bucket by bucket."""

import re

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.config import OptimizerConfig
from photon_ml_tpu.game import (
    DenseFeatures,
    bucket_entities,
    group_by_entity,
    train_random_effects,
)
from photon_ml_tpu.game import random_effect as re_mod
from photon_ml_tpu.game.random_effect import (
    _bucket_offsets,
    _bucket_step,
    _run_starts,
    prepare_buckets,
)
from photon_ml_tpu.obs.metrics import REGISTRY
from photon_ml_tpu.ops.losses import logistic_loss
from photon_ml_tpu.optim.common import select_minimize_fn
from photon_ml_tpu.types import VarianceComputationType


def _block_rows(counts, capacity, first=0):
    """(k, capacity) row numbers of lanes that hold consecutive rows, one
    lane after the other from row ``first``, -1 in the padded slots."""
    rows = np.full((len(counts), capacity), -1, np.int64)
    at = first
    for i, c in enumerate(counts):
        rows[i, :c] = np.arange(at, at + c)
        at += c
    return rows


def _offsets(n, seed=3):
    off = np.random.default_rng(seed).standard_normal(n).astype(np.float32)
    off[0] = -abs(off[0]) - 0.5  # a padded slot reads offsets[0]: its zero is -0.0
    return jnp.asarray(off)


def _bits(x):
    return np.asarray(x).view(np.uint32)


def _case(name):
    """(host row indices, rows in the data set, lanes of device padding)."""
    rng = np.random.default_rng(11)
    if name in ("blocks_c8", "blocks_c64", "blocks_c8192"):
        C = int(name.split("_c")[1])
        counts = rng.integers(C // 2 + 1, C + 1, size=5)
        counts[2] = C  # a full lane
        return _block_rows(counts, C, first=37), 37 + int(counts.sum()) + 1000, 0
    if name == "last_lane_ends_at_the_last_row":
        # the last lane's window of C slots runs far past the array's end
        rows = _block_rows([40, 33, 3], 64, first=200)
        return rows, 200 + 76, 0
    if name == "array_shorter_than_one_window":
        return _block_rows([5, 9], 8192), 14, 0
    if name == "device_padding_lanes":
        return _block_rows([6, 8, 5], 8, first=130), 400, 5
    if name == "rows_not_a_multiple_of_128_apart":
        return _block_rows(rng.integers(20, 65, size=40), 64, first=127), 127 + 40 * 64, 2
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "blocks_c8", "blocks_c64", "blocks_c8192", "last_lane_ends_at_the_last_row",
    "array_shorter_than_one_window", "device_padding_lanes",
    "rows_not_a_multiple_of_128_apart",
])
def test_run_start_slices_are_the_slot_gather_bit_for_bit(name):
    rows, n, pad = _case(name)
    starts = _run_starts(rows)
    assert starts is not None
    np.testing.assert_array_equal(starts, rows[:, 0])
    zeros = lambda a: np.concatenate([a, np.zeros((pad,) + a.shape[1:], a.dtype)])
    idx = jnp.asarray(zeros(np.maximum(rows, 0)), jnp.int32)
    mask = jnp.asarray(zeros((rows >= 0).astype(np.float32)))
    off = _offsets(n)
    want = off[idx] * mask
    got = _bucket_offsets(off, jnp.asarray(zeros(starts), jnp.int32), mask)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(_bits(got), _bits(want))
    # the slot form goes through the same helper
    np.testing.assert_array_equal(_bits(_bucket_offsets(off, idx, mask)), _bits(want))
    padded = np.asarray(mask) == 0  # offsets[0] < 0, so every padded slot is -0.0
    assert padded.any() and np.all(np.signbit(np.asarray(got)[padded]))


def test_detector_refuses_a_bucket_with_one_lane_that_is_no_run():
    rows = _block_rows([6, 8, 5, 7], 8, first=10)
    assert _run_starts(rows) is not None
    swapped = rows.copy()
    swapped[2, [1, 3]] = swapped[2, [3, 1]]
    assert _run_starts(swapped) is None
    gap = rows.copy()
    gap[1, 4:8] += 1  # consecutive but for one skipped row
    assert _run_starts(gap) is None
    late = rows.copy()
    late[3, 0] = -1  # the lane's first slot holds no row
    assert _run_starts(late) is None
    empty = rows.copy()
    empty[0] = -1  # a lane without rows starts at 0 and stays a run
    np.testing.assert_array_equal(_run_starts(empty), [0, 16, 24, 29])


def _effect(n_entities, counts_rng, shuffled, d=3, seed=5):
    rng = np.random.default_rng(seed)
    counts = counts_rng(rng, n_entities)
    ids = np.repeat(rng.permutation(n_entities), counts).astype(np.int32)
    if shuffled:
        ids = ids[rng.permutation(len(ids))]
    n = len(ids)
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=(n_entities, d)).astype(np.float32)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-np.sum(w[ids] * X, axis=1))))
    return ids, X, y.astype(np.float32)


def _slot_counters():
    return {k: v["value"] for k, v in REGISTRY.snapshot("re_offsets.")["counters"].items()}


@pytest.mark.parametrize("shuffled", [False, True])
def test_prepare_stages_run_starts_only_where_every_lane_is_a_run(shuffled):
    ids, X, y = _effect(30, lambda r, e: r.integers(3, 40, size=e), shuffled)
    buckets = bucket_entities(group_by_entity(ids, num_entities=30))
    REGISTRY.reset(prefix="re_offsets")
    prepared = prepare_buckets(
        DenseFeatures(X=jnp.asarray(X)), y, np.ones(len(y), np.float32), buckets
    )
    slots = sum(r.size for r in buckets.row_indices)
    for pb, rows in zip(prepared, buckets.row_indices):
        if shuffled:
            assert pb.row_idx.shape == rows.shape
        else:
            np.testing.assert_array_equal(np.asarray(pb.row_idx), rows[:, 0])
        assert pb.mask.shape == rows.shape
    assert _slot_counters() == {
        "re_offsets.slots": slots, "re_offsets.run_slots": 0 if shuffled else slots,
    }


def _blocks_with_one_broken_lane():
    """Rows sorted by entity, 40 entities in a narrow and a wide class; in
    the wide class one entity has a row elsewhere in the file."""
    ids, X, y = _effect(
        40, lambda r, e: np.where(np.arange(e) % 2 == 0, r.integers(3, 9, size=e),
                                  r.integers(20, 33, size=e)), shuffled=False, seed=8,
    )
    wide = np.flatnonzero(np.bincount(ids) >= 20)
    a, b = np.flatnonzero(ids == wide[0])[0], np.flatnonzero(ids == wide[-1])[-1]
    ids[[a, b]] = ids[[b, a]]
    buckets = bucket_entities(group_by_entity(ids, num_entities=40), capacities=(8, 32))
    return X, y, buckets


def test_one_broken_lane_keeps_its_bucket_on_slot_indices_and_no_other(monkeypatch):
    """That bucket is staged whole by slot indices, the narrow class by run
    starts, and the solve is the all-slot-index solve bit for bit."""
    X, y, buckets = _blocks_with_one_broken_lane()
    assert buckets.capacities == (8, 32)
    args = (DenseFeatures(X=jnp.asarray(X)), y, np.zeros(len(y), np.float32),
            np.ones(len(y), np.float32), buckets, 40, logistic_loss,
            OptimizerConfig(max_iterations=30, tolerance=1e-8))
    REGISTRY.reset(prefix="re_offsets")
    prepared = prepare_buckets(args[0], y, args[3], buckets)
    assert [pb.row_idx.ndim for pb in prepared] == [1, 2]
    assert _slot_counters() == {
        "re_offsets.slots": sum(r.size for r in buckets.row_indices),
        "re_offsets.run_slots": buckets.row_indices[0].size,
    }
    got = train_random_effects(*args, l2_weight=1.0)
    monkeypatch.setattr(re_mod, "_run_starts", lambda rows: None)
    want = train_random_effects(*args, l2_weight=1.0)
    np.testing.assert_array_equal(_bits(got.coefficients), _bits(want.coefficients))
    np.testing.assert_array_equal(got.iterations, want.iterations)


_GATHER = re.compile(
    r"stablehlo\.gather.*?:\s*\(tensor<[^>]*>,\s*tensor<([0-9x]+)xi(?:32|64)>\)"
)


def _gather_index_counts(text):
    """Elements of the index operand of every gather in a lowered module."""
    return [int(np.prod([int(t) for t in m.group(1).split("x")]))
            for m in _GATHER.finditer(text)]


@pytest.mark.parametrize("form", ["run_starts", "slot_indices"])
def test_lowered_bucket_step_gathers_no_index_a_slot_from_run_starts(form, monkeypatch):
    """Exact gate, counted in the CPU lowering: the step of a run bucket
    holds no gather whose index operand has k_pad * C elements; the same
    bucket staged by slot indices holds exactly one (the control)."""
    if form == "slot_indices":
        monkeypatch.setattr(re_mod, "_run_starts", lambda rows: None)
    ids, X, y = _effect(13, lambda r, e: r.integers(33, 65, size=e), shuffled=False)
    buckets = bucket_entities(group_by_entity(ids, num_entities=13), capacities=(64,))
    (pb,) = prepare_buckets(
        DenseFeatures(X=jnp.asarray(X)), y, np.ones(len(y), np.float32), buckets
    )
    config = OptimizerConfig(max_iterations=5, tolerance=1e-6)
    minimize_fn, extra = select_minimize_fn(config, 0.0)
    text = _bucket_step.lower(
        jnp.zeros((13, 3), jnp.float32), None, jnp.zeros(len(y), jnp.float32),
        pb.static, pb.row_idx, pb.mask, pb.ids, None, None,
        jnp.asarray(1.0, jnp.float32), None, None, None,
        minimize_fn=minimize_fn, loss=logistic_loss, config=config,
        intercept_index=None, variance_computation=VarianceComputationType.NONE,
        k=pb.num_real, sharding=None, **extra,
    ).as_text()
    counts = _gather_index_counts(text)
    assert counts, "the pattern finds no gather at all in the lowered step"
    slots = pb.mask.shape[0] * pb.mask.shape[1]
    assert counts.count(slots) == (0 if form == "run_starts" else 1), counts
    # whole aligned 128-wide rows of the offsets, in the run form alone
    rows_gathered = text.count("slice_sizes = array<i64: 1, 128>")
    assert rows_gathered == (1 if form == "run_starts" else 0)


@pytest.mark.parametrize("placement", ["lane_sharded", "owned_split", "device_split"])
def test_run_starts_follow_every_placement_of_the_lanes(placement, monkeypatch):
    """Lanes sharded over a mesh, owned sub-bucket atoms and atoms placed on
    local devices stage and shard the starts as they do the indices: under
    each, the solve is the slot-index solve bit for bit, and the atoms of one
    parent bucket share one form (they are concatenated again)."""
    from photon_ml_tpu.parallel import data_mesh

    if placement != "lane_sharded":
        monkeypatch.setenv("PHOTON_RE_SHARD", "1")
        monkeypatch.setenv("PHOTON_RE_SPLIT", "6")
    if placement == "device_split":
        monkeypatch.setenv("PHOTON_RE_DEVICE_SPLIT", "1")
    X, y, buckets = _blocks_with_one_broken_lane()
    feats, ones = DenseFeatures(X=jnp.asarray(X)), np.ones(len(y), np.float32)
    prepared = prepare_buckets(feats, y, ones, buckets, data_mesh())
    forms: dict = {}
    for i, pb in enumerate(prepared):
        forms.setdefault(i if pb.parent is None else pb.parent, set()).add(pb.row_idx.ndim)
    assert sorted(map(sorted, forms.values())) == [[1], [2]]
    if placement != "lane_sharded":
        assert len(prepared) > len(buckets.capacities)  # the classes were split
    args = (feats, y, np.zeros(len(y), np.float32), ones, buckets, 40, logistic_loss,
            OptimizerConfig(max_iterations=8, tolerance=1e-9))
    got = train_random_effects(*args, l2_weight=1.0, mesh=data_mesh())
    monkeypatch.setattr(re_mod, "_run_starts", lambda rows: None)
    want = train_random_effects(*args, l2_weight=1.0, mesh=data_mesh())
    np.testing.assert_array_equal(_bits(got.coefficients), _bits(want.coefficients))
    np.testing.assert_array_equal(got.iterations, want.iterations)
    assert got.iterations.max() > 1


@pytest.mark.parametrize("prepared", [True, False])
def test_the_run_report_renders_the_slot_counters(tmp_path, prepared):
    from photon_ml_tpu.obs.report import format_summary, summarize_run
    from photon_ml_tpu.obs.sink import TelemetrySink

    counters = {
        "re_offsets.slots": {"value": 4000.0},
        "re_offsets.run_slots": {"value": 1000.0},
    } if prepared else {}
    sink = TelemetrySink(str(tmp_path), run_id="HEAD", shard_index=None)
    sink.emit({"event": "run_start", "t": 1000.0, "schema_version": 1,
               "run_id": "HEAD", "pid": 0, "process_index": 0, "knobs": {},
               "fleet": {"process_count": 1}, "metrics_baseline": {}})
    sink.emit({"event": "run_end", "t": 1002.0, "run_id": "HEAD",
               "metrics": {"counters": counters, "gauges": {},
                           "histograms": {}, "timers": {}}})
    sink.close()
    summary = summarize_run(sink.path)
    if not prepared:
        assert "re_offsets" not in summary
        assert "re-offsets" not in format_summary(summary)
        return
    assert summary["re_offsets"]["run_slot_share"] == 0.25
    assert "(25.0%) read by run-start slices" in format_summary(summary)
