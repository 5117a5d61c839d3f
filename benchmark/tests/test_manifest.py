"""BENCHMARK.json against the contract's limits, and every name in it
against the files the harness finds by that name."""

import json
import os
import re

import pytest

from benchmark.harness import BENCH, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s, limit=200):
    return 1 <= len(s) <= limit and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536
    assert manifest["paths"] == ["benchmark"]
    assert all(_line(w) for w in manifest["command"])
    n = 24  # later PRs may fill every cell
    budget = (2 + 14 * n) * (manifest["run_seconds"] + 60) + n * 180 + 1200
    assert 1 <= manifest["run_seconds"] <= 51 and budget <= 43200


def test_configs(manifest):
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(REPO, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"]
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
    names = [c["name"] for c in manifest["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in manifest["workloads"]}
    assert used == set(names)


def test_cells_have_their_files(manifest):
    seen = set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            mix = json.load(f)
        assert mix["config"] == w["config"]
        assert os.path.exists(os.path.join(
            BENCH, "traffic", mix["driver"] + ".py"))
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 2)


def test_metrics(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = list(e2e) + [m["name"] for m in manifest["per_layer"]]
    assert len(set(names)) == len(names)
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert m["source"] in SOURCES and _line(m["layer"])
        layers.add(m["layer"])
        # each listed cell reports the metric it should move
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", cells)) <= set(moved)
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", spec["reader"] + ".py"))
        if "_roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for cell in cells:  # setup_s, one more end-to-end, one per-layer
        mine = [m for m in manifest["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in manifest["per_layer"])


def test_file_names_under_paths():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for root, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d not in ("out", "__pycache__")]
        for f in files:
            assert ok.match(os.path.relpath(os.path.join(root, f), REPO))
