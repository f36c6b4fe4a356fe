"""``chip_smoke.py``'s legs at tiny sizes on the 8-device CPU mesh.

The smoke itself only runs on a TPU; what tier-1 can hold is that every
leg still drives the entry points it names, that its own checks pass on
a small problem, and that the script refuses to produce a result on CPU.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import jax
import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SPEC = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(_ROOT, "chip_smoke.py")
)
chip_smoke = importlib.util.module_from_spec(_SPEC)
sys.modules.setdefault("chip_smoke", chip_smoke)
_SPEC.loader.exec_module(chip_smoke)

GAME = dict(
    n_train=1200, n_val=400, d_fixed=8,
    effects={"userId": (12, 3), "itemId": (6, 3)},
    requests=128, rate_hz=2000.0,
)


def test_main_refuses_without_a_tpu(tmp_path, capsys):
    rc = chip_smoke.main(["--out", str(tmp_path / "out")])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""  # no device line, no result line
    assert "needs a TPU" in out.err and "platform='cpu'" in out.err
    assert not (tmp_path / "out").exists()  # refused before any leg ran


def test_last_stdout_line_is_the_verdict_and_nothing_else(tmp_path, capsys):
    """The chip check parses the LAST stdout line and refuses anything but
    ``{"ok", "device": {"platform", "kind", "count"}}`` (PR 21's first
    smoke put the per-leg summary there and was refused); the detail goes
    on the line before it and into chip_smoke.json."""
    summary = {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "jax": "0.9.0", "seconds": 1.0, "compile_seconds": 0.5,
        "compile_cache": {"dir": "/x", "hits": 1, "misses": 2},
        "native_available": True,
        "legs": {"game": {"ok": True, "seconds": 1.0, "compile_seconds": 0.5}},
    }
    chip_smoke.report(summary, str(tmp_path))
    lines = capsys.readouterr().out.splitlines()
    verdict = json.loads(lines[-1])
    assert verdict == {"ok": True, "device": summary["device"]}
    assert type(verdict["device"]["count"]) is int
    prefix = "chip_smoke: summary: "
    assert lines[-2].startswith(prefix)
    assert json.loads(lines[-2][len(prefix):]) == summary
    assert json.loads((tmp_path / "chip_smoke.json").read_text()) == summary


def test_full_sizes_are_the_bench_widths():
    full = chip_smoke.FULL_SIZES
    assert full["game"]["d_fixed"] == 64
    assert full["game"]["effects"] == {"userId": (20000, 8), "itemId": (4000, 8)}
    assert full["game"]["n_train"] >= 1 << 16 and full["game"]["n_val"] >= 1 << 13
    assert (full["sparse"]["d"], full["sparse"]["k"]) == (1 << 17, 32)
    assert full["sparse"]["n"] >= 1 << 16
    assert (full["dense"]["n"], full["dense"]["d"]) == (1 << 20, 512)
    assert full["distributed"]["d_dense"] == 512
    assert full["distributed"]["d_sparse"] == 1 << 17


def test_game_leg_over_the_cpu_mesh(tmp_path):
    """train -> score -> publish -> serve through the CLI mains; with eight
    visible devices ``cli.train.main`` builds the mesh by itself, so the
    fixed effect's rows must span all eight."""
    facts = chip_smoke.leg_game(str(tmp_path), **GAME)
    assert facts["batch_devices"] == len(jax.devices()) == 8
    assert facts["served_bitwise"] and facts["served_sample"] == 128
    assert facts["devcost_capture_errors"] == 0
    losses = facts["fixed_loss_per_outer_iteration"]
    assert losses[1] < losses[0]
    # the run's own telemetry carries the span tree the leg asked for
    runs = os.listdir(tmp_path / "telemetry")
    assert any(f.endswith(".jsonl") for f in runs)


def test_train_run_on_mesh_matches_single_device(tmp_path):
    """``cli.train.run(mesh=data_mesh())`` against ``mesh=None``: the same
    fit up to the reduction order of the sharded sums."""
    import io

    from photon_ml_tpu.cli import train
    from photon_ml_tpu.cli.common import load_training_config
    from photon_ml_tpu.data.synthetic import synthetic_game_data
    from photon_ml_tpu.parallel import data_mesh
    from photon_ml_tpu.utils import PhotonLogger

    effects, d_fixed = GAME["effects"], GAME["d_fixed"]
    data = synthetic_game_data(
        np.random.default_rng(0), 800, d_fixed=d_fixed, effects=effects
    )
    chip_smoke._write_game_avro(
        str(tmp_path / "train.avro"), data, 0, 600, d_fixed, effects
    )
    chip_smoke._write_game_avro(
        str(tmp_path / "val.avro"), data, 600, 800, d_fixed, effects
    )
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(chip_smoke._game_config(effects).to_dict()))
    cfg = load_training_config(str(cfg_path))

    def fit(name, mesh):
        return train.run(
            cfg, [str(tmp_path / "train.avro")], str(tmp_path / name),
            validation_data=[str(tmp_path / "val.avro")], mesh=mesh,
            logger=PhotonLogger(None, stream=io.StringIO()),
        )

    single, meshed = fit("single", None), fit("mesh", data_mesh())
    assert meshed.evaluation.primary == pytest.approx(
        single.evaluation.primary, abs=1e-4
    )
    for cid, sub in single.model.models.items():
        np.testing.assert_allclose(
            np.asarray(meshed.model.models[cid].coefficient_means),
            np.asarray(sub.coefficient_means), rtol=1e-3, atol=1e-3,
        )


def test_dense_leg():
    facts = chip_smoke.leg_dense(n=2048, d=128, lbfgs_iters=3, tron_iters=2)
    for rung in ("bf16", "f32"):
        assert facts[rung]["lbfgs_loss"] < facts[rung]["initial_loss"]
        assert facts[rung]["tron_loss"] < facts[rung]["initial_loss"]


@pytest.mark.kernel
def test_sparse_leg(tmp_path, monkeypatch):
    import photon_ml_tpu.ops.streaming as ost

    # a budget under the dense matrix's bytes, so the driver's layout
    # decision tiles at this width as it does at the full width
    monkeypatch.setattr(ost, "device_hbm_budget_bytes", lambda *a, **k: 4096.0)
    facts = chip_smoke.leg_sparse(str(tmp_path), n=2048, d=4096, k=8, iters=3)
    assert facts["tile_layout_packs"] >= 1 and facts["interpret"] is True
    assert facts["rel_diff"] <= 1e-4
    assert set(facts["rungs"]) == {"f32", "int8"}
    assert all(isinstance(r, dict) for r in facts["rungs"].values())


@pytest.mark.kernel
def test_distributed_leg(monkeypatch):
    import photon_ml_tpu.ops.streaming as ost

    monkeypatch.setattr(ost, "device_hbm_budget_bytes", lambda *a, **k: 4096.0)
    facts = chip_smoke.leg_distributed(
        n_dense=2048, d_dense=128, n_sparse=2048, d_sparse=4096, k=8, iters=3
    )
    assert facts["devices"] == 8
    assert facts["dense"]["rel_diff"] <= 1e-4
    assert facts["sparse"]["rel_diff"] <= 1e-4


def test_run_legs_records_a_failing_leg_and_keeps_going(tmp_path, monkeypatch):
    def boom(**kw):
        raise chip_smoke.SmokeFailure("made to fail")

    fine = lambda *a, **kw: {"fine": 1}
    monkeypatch.setattr(chip_smoke, "leg_game", fine)
    monkeypatch.setattr(chip_smoke, "leg_sparse", fine)
    monkeypatch.setattr(chip_smoke, "leg_dense", boom)
    monkeypatch.setattr(chip_smoke, "leg_distributed", fine)
    legs = chip_smoke.run_legs(
        str(tmp_path), dict.fromkeys(("game", "sparse", "dense", "distributed"), {})
    )
    assert list(legs) == ["game", "sparse", "dense", "distributed"]
    assert legs["dense"]["ok"] is False and "made to fail" in legs["dense"]["error"]
    assert legs["distributed"] == {
        "ok": True, "seconds": legs["distributed"]["seconds"],
        "compile_seconds": legs["distributed"]["compile_seconds"], "fine": 1,
    }


class TestCompileCacheHelper:
    def test_environment_wins_and_nothing_is_touched(self, monkeypatch):
        from photon_ml_tpu.utils import compile_cache

        calls = []
        monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
        assert compile_cache.configure_compile_cache() == "/x"
        assert calls == []

    def test_default_is_the_checkouts_own_directory(self, monkeypatch):
        from photon_ml_tpu.utils import compile_cache

        calls = {}
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: calls.__setitem__(k, v)
        )
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(_ROOT, ".jax_cache")
        assert compile_cache.configure_compile_cache() == want
        assert calls["jax_compilation_cache_dir"] == want
        assert calls["jax_persistent_cache_min_compile_time_secs"] == 0.0
