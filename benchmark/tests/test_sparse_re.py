"""The cell ``sparse_re_descent`` without the chip: its generator, its
runner tiny through the harness on the CPU backend (and the check failing
where it must), the work function of ``sparse_re.pass_roofline`` on a case
counted by hand, and the new readers on a synthetic slice."""

import copy
import json
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from benchmark import datagen_sparse_re, harness, stages, work, work_sparse_re

CELL = "sparse_re_descent"


# -- the generator -------------------------------------------------------------

def _effects(users=50, items=30, width=512, nonzeros=6):
    return {
        "userId": dict(kind="sparse", descriptor_of="itemId", entities=users,
                       width=width, nonzeros=nonzeros, zipf_exponent=1.0,
                       assignment="blocks", rows_floor=5, lognormal_mu=4.17,
                       lognormal_sigma=1.14),
        "itemId": dict(kind="dense", entities=items, width=8,
                       assignment="shuffled", rows_floor=1, lognormal_mu=5.66,
                       lognormal_sigma=1.38),
    }


def test_descriptors_are_distinct_unit_norm_and_seeded():
    cols, vals = datagen_sparse_re.item_descriptors(40, 512, 16, 1.0, 0)
    assert cols.shape == vals.shape == (40, 16)
    assert all(len(set(row)) == 16 for row in cols)
    assert cols.min() >= 0 and cols.max() < 512
    np.testing.assert_allclose((vals * vals).sum(axis=1), 1.0, rtol=1e-6)
    assert vals.min() > 0
    again = datagen_sparse_re.item_descriptors(40, 512, 16, 1.0, 0)
    np.testing.assert_array_equal(cols, again[0])
    other = datagen_sparse_re.item_descriptors(40, 512, 16, 1.0, 1)
    assert not np.array_equal(cols, other[0])


@pytest.fixture(scope="module")
def two_seeds():
    eff = _effects()
    return eff, [
        datagen_sparse_re.glmix_sparse_rows(seed, 4000, 8, eff, 0)
        for seed in (3, 2_147_483_660)
    ]


def test_an_items_rows_share_its_descriptor(two_seeds):
    eff, ((_, _, shards, ids), _) = two_seeds
    cols, vals = (np.asarray(a) for a in shards["userId"])
    assert cols.shape == vals.shape == (4000, eff["userId"]["nonzeros"])
    for item in np.unique(ids["itemId"])[:10]:
        rows = np.flatnonzero(ids["itemId"] == item)
        assert np.all(cols[rows] == cols[rows[0]])
        assert np.all(vals[rows] == vals[rows[0]])
    first = {i: np.flatnonzero(ids["itemId"] == i)[0] for i in np.unique(ids["itemId"])}
    distinct = {tuple(sorted(cols[r])) for r in first.values()}
    assert len(distinct) > 0.9 * len(first)  # items do not share a vector


def test_the_seed_relabels_and_moves_no_shape(two_seeds):
    """Other entity ids and another order of a row's nonzeros: the same
    labels, the same fixed features, the same sets of nonzeros row by row,
    the same rows an entity has, so the same supports and bucket shapes."""
    _, ((y0, X0, s0, ids0), (y1, X1, s1, ids1)) = two_seeds
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
    np.testing.assert_array_equal(np.asarray(X0), np.asarray(X1))
    np.testing.assert_array_equal(np.asarray(s0["itemId"]), np.asarray(s1["itemId"]))
    c0, c1 = np.asarray(s0["userId"][0]), np.asarray(s1["userId"][0])
    assert not np.array_equal(c0, c1)
    np.testing.assert_array_equal(np.sort(c0, axis=1), np.sort(c1, axis=1))
    for tag in ("userId", "itemId"):
        assert not np.array_equal(ids0[tag], ids1[tag])
        np.testing.assert_array_equal(
            np.sort(np.bincount(ids0[tag])), np.sort(np.bincount(ids1[tag]))
        )
        # one relabelling of the entities takes one id column to the other
        pairs = set(zip(ids0[tag].tolist(), ids1[tag].tolist()))
        assert len(pairs) == len(np.unique(ids0[tag]))


# -- the work function ----------------------------------------------------------

def test_pass_work_on_a_case_counted_by_hand():
    """Two entities. A: 3 rows of 2 nonzeros on 4 columns; B: 1 row of 2
    nonzeros on 2 columns. A pass reads every nonzero twice (a value and an
    index, 8 bytes) and reads or writes each vector once a direction."""
    flops, bytes_ = work_sparse_re.entity_passes(
        rows=[3, 1], nonzeros=[6, 2], support=[4, 2]
    )
    np.testing.assert_array_equal(flops, [4 * 6, 4 * 2])
    np.testing.assert_array_equal(
        bytes_, [2 * 6 * 8 + 2 * (3 + 4) * 4, 2 * 2 * 8 + 2 * (1 + 2) * 4]
    )
    assert (flops[0], bytes_[0]) == work.sparse_pass(3, 4, 6)
    # A ran 5 iterations and B 2: the chip's least time for the useful work
    it = np.array([5, 2])
    least, bound = work.least_seconds(
        float(it @ flops), float(it @ bytes_), "TPU v5 lite"
    )
    assert bound == "memory"
    assert least == pytest.approx((5 * 152 + 2 * 56) / 819e9)


# -- the readers ----------------------------------------------------------------

def _reader(name):
    return harness.layer_reader(name)


@pytest.fixture
def synthetic_slice(monkeypatch):
    """A slice of 2 units of work: 0.6 s under ``re.sparse_pass`` (which
    sits inside ``re.solve``), 0.2 s under ``re.subspace``, 0.1 s of the
    solve in neither, 0.1 s unstaged."""
    solve = ("jit(fused)", "coord.per_userId", "visit.re", "re.solve")
    ops = [
        stages.OpTime("jit_fused", "fusion.1", solve + ("while", "body", "re.sparse_pass", "reduce"), 10, 0.5),
        stages.OpTime("jit_fused", "scatter.2", solve + ("re.sparse_pass", "scatter-add"), 2, 0.1),
        stages.OpTime("jit_fused", "gather.3", solve + ("re.subspace", "gather"), 2, 0.2),
        stages.OpTime("jit_fused", "fusion.4", solve + ("lbfgs.two_loop", "dot"), 10, 0.1),
        stages.OpTime("jit_fused", "copy.5", (), 1, 0.1),
    ]
    sl = stages.Slice(window_s=1.2, busy_s=1.0, ops=ops)
    stages._observed.cache_clear()
    stages._family.cache_clear()
    monkeypatch.setattr(stages, "trace_path", lambda: "synthetic")
    monkeypatch.setattr(stages, "read_slice", lambda path: sl)
    monkeypatch.setattr(stages, "_program_has_stages", lambda: True)
    yield sl
    stages._observed.cache_clear()
    stages._family.cache_clear()


def _obs(**counters):
    return SimpleNamespace(
        counters={"work": 2.0, **counters},
        trace=SimpleNamespace(ops={}), device_kind="TPU v5 lite",
    )


def test_stage_readers_on_a_synthetic_slice(synthetic_slice):
    obs = _obs()
    assert _reader("sparse_re.pass_s_per_iter")(obs) == pytest.approx(0.3)
    assert _reader("sparse_re.subspace_s_per_iter")(obs) == pytest.approx(0.1)
    family = ("re.sparse_pass", "re.subspace")
    assert stages.part(obs, family, stages.UNSTAGED) == pytest.approx(0.1)
    # the accepted reader of the whole solve sees all three beneath it
    assert _reader("descent.re_solve_s_per_iter")(obs) == pytest.approx(0.45)


def test_roofline_reader_divides_least_time_by_the_stages_seconds(synthetic_slice):
    obs = _obs(**{"sparse_re.useful_pass_flops": 1e9,
                  "sparse_re.useful_pass_bytes": 819e9 * 0.06})
    assert _reader("sparse_re.pass_roofline")(obs) == pytest.approx(10.0)
    assert _reader("sparse_re.pass_roofline")(_obs()) is None  # nothing counted


def test_readers_find_nothing_where_the_program_has_nothing(monkeypatch):
    """A program without the stages or the counters (the parent commit
    under this benchmark): None, not an error."""
    monkeypatch.setattr(stages, "_program_has_stages", lambda: False)
    obs = _obs()
    for name in ("sparse_re.pass_s_per_iter", "sparse_re.subspace_s_per_iter",
                 "sparse_re.pass_roofline", "sparse_re.width_pad_ratio",
                 "sparse_re.build_s"):
        assert _reader(name)(obs) is None


def test_counter_readers():
    obs = _obs(**{"re_subspace.padded_columns": 300.0,
                  "re_subspace.support_columns": 200.0,
                  "re_subspace.build.seconds": 1.25})
    assert _reader("sparse_re.width_pad_ratio")(obs) == pytest.approx(1.5)
    assert _reader("sparse_re.build_s")(obs) == 1.25


# -- the runner, tiny -------------------------------------------------------------

def _tiny():
    resolved = copy.deepcopy(harness.resolve(harness.load_manifest(), CELL))
    resolved.traffic.update(trace_slice_s=0.3)
    cfg = resolved.config
    cfg.update(rows=6000, users=60, items=40)
    cfg["fixed"]["width"] = 8
    cfg["random_effects"]["userId"].update(entities=60, width=512, nonzeros=4)
    cfg["random_effects"]["itemId"].update(entities=40)
    cfg["guarantees"].update(
        log_loss_ratio_max=0.99, entities_checked=8, sparse_entities_checked=16,
        sparse_entities_solved=8,
    )
    return resolved


def _run(resolved, trace=False):
    logs = []
    out = harness.run_cell(
        resolved, seed=2_147_483_659, seconds=0.5, trace=trace,
        devices=jax.devices()[:1], t_start=time.perf_counter(), log=logs.append,
    )
    json.dumps(out)  # the last line must serialise
    return out, logs


def test_untraced_run_is_correct_and_reports_the_end_to_end_metrics():
    resolved = _tiny()
    out, logs = _run(resolved)
    assert out["correct"] is True, logs
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"descent_iter_s", "setup_s"}


def test_traced_run_reports_the_per_layer_metrics():
    resolved = _tiny()
    out, logs = _run(resolved, trace=True)
    assert out["correct"] is True, logs
    listed = {m["name"] for m in resolved.per_layer}
    # the CPU backend has no memory stats, and its trace names no paths, so
    # everything is unstaged there: a stage's seconds read 0 and a share of
    # 0 seconds is not reported
    absent = {"device.peak_hbm_bytes", "device.hbm_fill", "sparse_re.pass_roofline"}
    assert listed - set(out["metrics"]) <= absent
    assert out["metrics"]["compile.in_window"]["value"] == 0
    assert out["metrics"]["sparse_re.width_pad_ratio"]["value"] >= 1.0
    assert out["metrics"]["sparse_re.build_s"]["value"] > 0
    assert 0 < out["metrics"]["re_solve.useful_lane_share"]["value"] <= 100
    assert out["breakdown"]["device_ops"]


def _checked(mutate=None, **optimizer):
    resolved = _tiny()
    resolved.config["random_effects"]["userId"]["optimizer"].update(optimizer)
    runner = harness.load_runner(resolved)
    cell = SimpleNamespace(
        config=resolved.config, traffic=resolved.traffic, seed=7,
        devices=jax.devices()[:1], annotate=harness._annotate,
    )
    st = runner.setup(cell)
    runner.account(st, runner.unit(st))
    if mutate is not None:
        mutate(st)
    return runner.check(st)


def test_check_fails_when_a_support_column_is_zeroed():
    """One coefficient on an entity's support set to 0 (a dropped column):
    the entity's gradient no longer vanishes there."""
    def drop(st):
        W = np.array(st.last[1]["per_userId"])
        entity = int(np.argmax(np.abs(W).sum(axis=1)))
        W[entity, int(np.argmax(np.abs(W[entity])))] = 0.0
        st.last[1]["per_userId"] = W

    good = _checked()
    assert good["correct"] is True, good
    bad = _checked(drop)
    assert bad["correct"] is False
    # the sample may miss the entity; the scores cannot
    assert (bad["notes"]["score_max_abs_diff"] > 1e-3
            or bad["notes"]["per_userId"]["grad_ratio_max"]
            > good["notes"]["per_userId"]["grad_ratio_max"])


def test_check_fails_when_the_solve_is_cut_to_two_iterations():
    bad = _checked(max_iterations=2)
    assert bad["correct"] is False
    assert bad["notes"]["per_userId"]["stopped_by"]["cap"] > 0.5
