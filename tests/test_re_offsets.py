"""A bucket's residual offsets (``game/random_effect._bucket_offsets``) by one
run start a lane: into the residual itself where ``_run_starts`` finds every
lane of the effect to be one run of the file's rows, else into the effect's
own order (``_effect_order``), which a visit gathers once, one index a real
row. Both are the plain reading, one index a slot, bit for bit, zero signs
included; the choice is read from the data, effect by effect."""

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
    _effect_order,
    _ordered_offsets,
    _run_starts,
    prepare_buckets,
    train_prepared,
)
from photon_ml_tpu.obs.metrics import REGISTRY
from photon_ml_tpu.ops.losses import logistic_loss

from stage_programs import slot_index_reading


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


def _shuffled_rows(counts_by_class, n, seed=7):
    """Classes of (k, C) row numbers whose lanes hold rows drawn all over a
    file of ``n`` rows, ``counts_by_class`` a list of (C, rows a lane[, lanes
    without rows at the class's end])."""
    rng = np.random.default_rng(seed)
    file_rows = rng.permutation(n)
    at, out = 0, []
    for cap, counts, *pad_lanes in counts_by_class:
        rows = np.full((len(counts) + sum(pad_lanes), cap), -1, np.int64)
        for i, c in enumerate(counts):
            rows[i, :c] = file_rows[at:at + c]
            at += c
        out.append(rows)
    return out


def _ordered_case(name):
    """(classes of host rows, rows in the data set, devices the lanes are cut over)."""
    if name == "lanes_shorter_than_c":
        return _shuffled_rows([(64, [5, 64, 33, 1]), (128, [65, 127])], 700), 700, 1
    if name == "an_empty_lane":
        rows = _shuffled_rows([(8, [3, 8, 5]), (32, [9, 32])], 90)
        rows[0][1] = -1
        rows[1][0] = -1
        return rows, 90, 1
    if name == "classes_c8_and_c8192":
        return _shuffled_rows([(8, [8, 1, 7, 5, 6]), (8192, [4097, 8192, 5000])], 20000), 20000, 1
    if name == "the_arrays_last_row_inside_a_lane":
        rows = _shuffled_rows([(16, [9, 16, 12]), (64, [40])], 300)
        rows[0][1, 4] = 299  # whatever else holds it: a slot reads what it names
        rows[1][0, 39] = 0
        return rows, 300, 1
    if name == "a_slot_without_a_row_inside_a_lane":
        rows = _shuffled_rows([(16, [9, 16, 12])], 100)
        rows[0][0, 3] = -1
        rows[0][2, 0] = -1
        return rows, 100, 1
    if name == "device_padding_lanes_over_four_devices":
        # 5 + 3 and 2 + 2 lanes: the devices' segments differ in length
        return _shuffled_rows([(8, [3, 8, 5, 7, 1], 3), (32, [9, 32], 2)], 200), 200, 4
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "lanes_shorter_than_c", "an_empty_lane", "classes_c8_and_c8192",
    "the_arrays_last_row_inside_a_lane", "a_slot_without_a_row_inside_a_lane",
    "device_padding_lanes_over_four_devices",
])
def test_the_ordered_copy_and_its_slices_are_the_slot_gather_bit_for_bit(name):
    classes, n, n_dev = _ordered_case(name)
    order, starts = _effect_order(classes, n_dev)
    real = sum(int((r >= 0).sum()) for r in classes)
    width = len(order) // n_dev
    assert order.dtype == np.int32 and len(order) == n_dev * width
    if n_dev == 1 and "without_a_row" not in name:
        assert len(order) == real + 1  # one index a real row, behind the leading 0
    np.testing.assert_array_equal(order[::width], 0)
    off = _offsets(n)
    ordered = _ordered_offsets(off, jnp.asarray(order))
    assert _bits(ordered[0]) == _bits(off[0])
    for rows, s in zip(classes, starts):
        assert s.shape == rows.shape[:1]
        mask = jnp.asarray((rows >= 0).astype(np.float32))
        want = off[jnp.asarray(np.maximum(rows, 0), jnp.int32)] * mask
        got = _bucket_offsets(ordered, jnp.asarray(s, jnp.int32), mask)
        np.testing.assert_array_equal(_bits(got), _bits(want))
        padded = np.asarray(mask) == 0  # offsets[0] < 0: every padded slot is -0.0
        assert padded.any() and np.all(np.signbit(np.asarray(got)[padded]))
        # a device reads its own segment alone, its starts counted from it
        lanes = len(rows) // n_dev
        for j in range(n_dev):
            block = slice(j * lanes, (j + 1) * lanes)
            segment = off[jnp.asarray(order[j * width:(j + 1) * width])]
            local = jnp.asarray(s[block] - j * width, jnp.int32)
            assert int(local.min()) >= 0 and int(local.max()) < width
            np.testing.assert_array_equal(
                _bits(_bucket_offsets(segment, local, mask[block])), _bits(want[block])
            )
    if n_dev > 1:
        used = 1 + sum((r >= 0).reshape(n_dev, -1).sum(axis=1) for r in classes)
        assert len(set(used.tolist())) > 1 and width == used.max()  # filler, no more than needed


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
def test_prepare_stages_run_starts_into_the_file_or_into_the_effects_order(shuffled):
    ids, X, y = _effect(30, lambda r, e: r.integers(3, 40, size=e), shuffled)
    buckets = bucket_entities(group_by_entity(ids, num_entities=30))
    REGISTRY.reset(prefix="re_offsets")
    prepared = prepare_buckets(
        DenseFeatures(X=jnp.asarray(X)), y, np.ones(len(y), np.float32), buckets
    )
    slots = sum(r.size for r in buckets.row_indices)
    assert len(prepared) > 1
    for pb, rows in zip(prepared, buckets.row_indices):
        assert pb.row_idx.shape == rows.shape[:1] and pb.row_idx.dtype == jnp.int32
        assert pb.mask.shape == rows.shape
        assert pb.order is prepared[0].order  # ONE array, the effect's
        if not shuffled:
            np.testing.assert_array_equal(np.asarray(pb.row_idx), rows[:, 0])
    if shuffled:
        order = np.asarray(prepared[0].order)
        assert order.dtype == np.int32 and order[0] == 0
        # a permutation of the real rows in the effect's order: class by
        # class, lane by lane, a lane's rows as its slots hold them
        np.testing.assert_array_equal(
            order[1:], np.concatenate([r[r >= 0] for r in buckets.row_indices])
        )
        np.testing.assert_array_equal(np.sort(order[1:]), np.arange(len(y)))
    else:
        assert prepared[0].order is None
    assert _slot_counters() == {
        "re_offsets.slots": slots, "re_offsets.run_slots": slots,
        "re_offsets.ordered_rows": len(y) + 1 if shuffled else 0,
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


def _solve(prepared, n, mesh=None, iterations=30):
    return train_prepared(
        prepared, jnp.asarray(np.linspace(-1.0, 1.0, n, dtype=np.float32)), 3, 40,
        logistic_loss, OptimizerConfig(max_iterations=iterations, tolerance=1e-9),
        l2_weight=1.0, mesh=mesh,
    )


def test_one_broken_lane_sends_its_whole_effect_through_the_ordered_copy():
    """Every class of the effect, the narrow one whose lanes are all runs
    too, slices the one ordered copy; the solve is the slot-index solve bit
    for bit."""
    X, y, buckets = _blocks_with_one_broken_lane()
    assert buckets.capacities == (8, 32)
    assert [_run_starts(r) is None for r in buckets.row_indices] == [False, True]
    REGISTRY.reset(prefix="re_offsets")
    prepared = prepare_buckets(
        DenseFeatures(X=jnp.asarray(X)), y, np.ones(len(y), np.float32), buckets
    )
    assert [pb.row_idx.ndim for pb in prepared] == [1, 1]
    assert prepared[0].order is prepared[1].order is not None
    slots = sum(r.size for r in buckets.row_indices)
    assert _slot_counters() == {
        "re_offsets.slots": slots, "re_offsets.run_slots": slots,
        "re_offsets.ordered_rows": len(y) + 1,
    }
    got = _solve(prepared, len(y))
    reference = slot_index_reading(prepared)
    assert [pb.row_idx.ndim for pb in reference] == [2, 2]
    want = _solve(reference, len(y))
    np.testing.assert_array_equal(_bits(got.coefficients), _bits(want.coefficients))
    np.testing.assert_array_equal(got.iterations, want.iterations)
    assert got.iterations.max() > 1


_GATHER = re.compile(
    r"stablehlo\.gather.*?:\s*\(tensor<[^>]*>,\s*tensor<([0-9x]+)xi(?:32|64)>\)"
)


def _gather_index_counts(text):
    """Elements of the index operand of every gather in a lowered module."""
    return [int(np.prod([int(t) for t in m.group(1).split("x")]))
            for m in _GATHER.finditer(text)]


def _item_coordinate(shuffled):
    from photon_ml_tpu.config import OptimizationConfig, RegularizationContext
    from photon_ml_tpu.game import RandomEffectCoordinate, make_game_batch
    from photon_ml_tpu.types import OptimizerType, RegularizationType, TaskType

    ids, X, y = _effect(13, lambda r, e: r.integers(5, 65, size=e), shuffled)
    batch = make_game_batch(y, {"pi": X}, id_tags={"item": ids})
    grouping = group_by_entity(ids, num_entities=13)
    return RandomEffectCoordinate(
        coordinate_id="per_item", batch=batch, feature_shard_id="pi",
        random_effect_type="item",
        config=OptimizationConfig(
            optimizer=OptimizerConfig(
                optimizer_type=OptimizerType.NEWTON_CHOLESKY, max_iterations=5,
                tolerance=1e-6,
            ),
            regularization=RegularizationContext(RegularizationType.L2),
            regularization_weight=1.0,
        ),
        grouping=grouping, buckets=bucket_entities(grouping, capacities=(16, 64)),
        task_type=TaskType.LOGISTIC_REGRESSION, num_entities=13,
    ), len(y)


@pytest.mark.parametrize("form", ["blocks", "shuffled", "slot_indices"])
def test_lowered_visit_gathers_one_index_a_real_row_and_none_a_slot(form):
    """Exact gate, counted in the CPU lowering of the fused visit: a shuffled
    effect's holds ONE gather over its n_real + 1 ordered rows and none whose
    index operand has a class's k_pad * C elements; an effect in the file's
    order holds neither; the same shuffled effect read by slot indices holds
    one a class (the control)."""
    coord, n = _item_coordinate(shuffled=form != "blocks")
    if form == "slot_indices":
        object.__setattr__(coord, "_prepared_cache", slot_index_reading(coord._prepared))
    make_static, _, _, _ = coord._fused_visit_parts()
    total = jnp.zeros((n,), jnp.float32)
    text = coord._visit_fn[1].lower(total, total, *make_static(None)).as_text()
    counts = _gather_index_counts(text)
    assert counts, "the pattern finds no gather at all in the lowered visit"
    slots = [pb.mask.shape[0] * pb.mask.shape[1] for pb in coord._prepared]
    assert len(slots) == 2 and not set(slots) & {n, n + 1}
    assert [counts.count(s) for s in slots] == ([1, 1] if form == "slot_indices" else [0, 0])
    # the scoring gather reads n ids; the ordered copy n + 1 rows
    assert counts.count(n + 1) == (1 if form == "shuffled" else 0), counts
    # whole aligned 128-wide rows of the offsets, a class: the run forms alone
    rows_gathered = text.count("slice_sizes = array<i64: 1, 128>")
    assert rows_gathered == (0 if form == "slot_indices" else 2)


@pytest.mark.parametrize("placement", ["lane_sharded", "owned_split", "device_split"])
def test_the_ordered_copy_follows_every_placement_of_the_lanes(placement, monkeypatch):
    """Lanes sharded over a mesh (a device's segment of the order each),
    owned sub-bucket atoms and atoms placed on local devices all slice the
    effect's one ordered copy: under each, the solve is the slot-index solve
    bit for bit."""
    from photon_ml_tpu.parallel import data_mesh

    if placement != "lane_sharded":
        monkeypatch.setenv("PHOTON_RE_SHARD", "1")
        monkeypatch.setenv("PHOTON_RE_SPLIT", "6")
    if placement == "device_split":
        monkeypatch.setenv("PHOTON_RE_DEVICE_SPLIT", "1")
    X, y, buckets = _blocks_with_one_broken_lane()
    feats, ones = DenseFeatures(X=jnp.asarray(X)), np.ones(len(y), np.float32)
    REGISTRY.reset(prefix="re_offsets")
    prepared = prepare_buckets(feats, y, ones, buckets, data_mesh())
    assert all(pb.row_idx.ndim == 1 and pb.order is prepared[0].order for pb in prepared)
    order = np.asarray(prepared[0].order)
    if placement == "lane_sharded":
        n_dev = data_mesh().size
        assert len(order) % n_dev == 0 and len(order) > len(y) + n_dev  # filler
        np.testing.assert_array_equal(order[::len(order) // n_dev], 0)
    else:
        assert len(prepared) > len(buckets.capacities)  # the classes were split
        assert len(order) == len(y) + 1
    assert _slot_counters()["re_offsets.ordered_rows"] == len(order)
    np.testing.assert_array_equal(np.unique(order), np.arange(len(y)))
    got = _solve(prepared, len(y), data_mesh(), iterations=8)
    want = _solve(slot_index_reading(prepared), len(y), data_mesh(), iterations=8)
    np.testing.assert_array_equal(_bits(got.coefficients), _bits(want.coefficients))
    np.testing.assert_array_equal(got.iterations, want.iterations)
    assert got.iterations.max() > 1


@pytest.mark.parametrize("prepared", ["ordered", "before_the_counter", "none"])
def test_the_run_report_renders_the_slot_counters(tmp_path, prepared):
    from photon_ml_tpu.obs.report import format_summary, summarize_run
    from photon_ml_tpu.obs.sink import TelemetrySink

    counters = {
        "re_offsets.slots": {"value": 4000.0},
        "re_offsets.run_slots": {"value": 1000.0},
    } if prepared != "none" else {}
    if prepared == "ordered":
        counters["re_offsets.run_slots"] = {"value": 4000.0}
        counters["re_offsets.ordered_rows"] = {"value": 1800.0}
    sink = TelemetrySink(str(tmp_path), run_id="HEAD", shard_index=None)
    sink.emit({"event": "run_start", "t": 1000.0, "schema_version": 1,
               "run_id": "HEAD", "pid": 0, "process_index": 0, "knobs": {},
               "fleet": {"process_count": 1}, "metrics_baseline": {}})
    sink.emit({"event": "run_end", "t": 1002.0, "run_id": "HEAD",
               "metrics": {"counters": counters, "gauges": {},
                           "histograms": {}, "timers": {}}})
    sink.close()
    summary = summarize_run(sink.path)
    if prepared == "none":
        assert "re_offsets" not in summary
        assert "re-offsets" not in format_summary(summary)
        return
    text = format_summary(summary)
    if prepared == "ordered":
        assert summary["re_offsets"]["run_slot_share"] == 1.0
        assert summary["re_offsets"]["index_share"] == 0.45
        assert "(100.0%) read by run-start slices" in text
        assert "1.80K rows gathered a visit" in text
        assert "(45.0% of the slots read one index each)" in text
    else:  # a run from before ``re_offsets.ordered_rows``: read as 0
        assert summary["re_offsets"]["run_slot_share"] == 0.25
        assert summary["re_offsets"]["index_share"] == 0.75
        assert "(25.0%) read by run-start slices" in text
