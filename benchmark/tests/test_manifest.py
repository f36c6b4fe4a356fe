"""BENCHMARK.json against the contract's limits and the files it names."""

import json
import os
import re

import pytest

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert os.path.getsize(harness.MANIFEST) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["paths"]) <= 16
    assert len(manifest["command"]) <= 32
    assert 2 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["configs"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16
    assert 1 <= len(manifest["per_layer"]) <= 128
    for part in manifest["command"][1:]:
        assert not part.startswith("/") and ".." not in part


def test_names_are_plain_and_used_once(manifest):
    names = [
        e["name"]
        for key in ("configs", "workloads", "end_to_end", "per_layer")
        for e in manifest[key]
    ]
    assert all(NAME.match(n) for n in names), names
    assert len(names) == len(set(names))
    for e in manifest["configs"] + manifest["workloads"]:
        assert len(e["why"]) <= 200, e["name"]


def test_every_file_under_paths_has_a_plain_name(manifest):
    for path in manifest["paths"]:
        for dirpath, dirnames, files in os.walk(os.path.join(harness.ROOT, path)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), harness.ROOT)
                assert PATH.match(rel), rel


def test_cells_resolve_to_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    pairs = set()
    for cell in manifest["workloads"]:
        assert cell["chips"] in (1, 4)
        pair = (cell["config"], cell["traffic"])
        assert pair not in pairs
        pairs.add(pair)
        used.add(cell["config"])
        resolved = harness.resolve(manifest, cell["name"])
        assert os.path.exists(resolved.runner_path), resolved.runner_path
        runner = harness.load_runner(resolved)
        for fn in ("setup", "unit", "account", "facts", "shape", "check"):
            assert callable(getattr(runner, fn)), (cell["name"], fn)
        assert resolved.traffic["metric"] in {
            m["name"] for m in resolved.end_to_end
        }
    assert used == set(configs), "a configuration no cell uses"
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in manifest["paths"]))
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        for key in ("assumed", "deployment", "stopping_rule", "guarantees"):
            assert key in body, (c["name"], key)


def test_four_chip_cells_within_their_share(manifest):
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_metrics_follow_the_contract(manifest):
    end = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in end and end["setup_s"]["bound"] <= 0.1
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1, m["name"]
        assert m["source"] in {"host_clock", "device_trace"}
        assert m["better"] in {"lower", "higher"}
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        assert m["source"] in SOURCES, m["name"]
        assert LAYER.match(m["layer"]), (m["name"], m["layer"])
        assert m["moves"] in end, m["name"]
        assert "bound" not in m
        assert set(m.get("workloads", cells)) <= cells
        assert m["name"].endswith("_roofline") == (
            "roofline" in m["name"]
        ) and (not m["name"].endswith("_roofline") or m["unit"] == "%")
        assert callable(harness.layer_reader(m["name"]))


def test_every_cell_reports_enough(manifest):
    for cell in manifest["workloads"]:
        r = harness.resolve(manifest, cell["name"])
        reported = {m["name"] for m in r.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2, cell["name"]
        assert any(m["moves"] in reported for m in r.per_layer), cell["name"]


def test_the_benchmark_stands_alone():
    """Nothing under benchmark/ imports bench.py or chip_smoke.py, and the
    references import nothing of the program."""
    bad = re.compile(r"^\s*(import|from)\s+(bench|chip_smoke)\b", re.M)
    program = re.compile(r"^\s*(import|from)\s+photon_ml_tpu\b", re.M)
    for dirpath, _, files in os.walk(harness.HERE):
        for f in files:
            if not f.endswith(".py"):
                continue
            with open(os.path.join(dirpath, f)) as fh:
                text = fh.read()
            assert not bad.search(text), f
            if os.path.basename(dirpath) == "reference":
                assert not program.search(text), f
