"""BENCHMARK.json against the benchmark's contract, and against the
files the harness finds by name."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _metrics(bench):
    return bench["end_to_end"] + bench["per_layer"]


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert bench["paths"] == ["chipbench", "tests/chipbench"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    # the full check with 24 cells must fit into 43,200 s
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(bench)) < 64 * 1024


def test_names_and_units(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer") and "metric"
                          or group, entry["name"]))
    assert len(names) == len(set(names)), "a name appears twice"
    for m in _metrics(bench):
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m
        assert m["source"] in SOURCES, m
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_entries_have_just_their_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert 1 <= len(m["layer"]) <= 200


def test_every_cell_finds_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        cfg = configs[w["config"]]
        used.add(w["config"])
        assert cfg["file"].startswith("chipbench/configs/")
        with open(os.path.join(ROOT, cfg["file"])) as f:
            raw = json.load(f)
        for key in ("n_embd", "n_layer", "n_head", "n_positions",
                    "vocab_size", "assumed"):
            assert key in raw, (cfg["file"], key)
        assert not cfg["reduced"], "no width or depth is cut"
        path = os.path.join(ROOT, "chipbench", "traffic",
                            w["traffic"] + ".json")
        with open(path) as f:
            mix = json.load(f)
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "drivers", mix["driver"] + ".py"))
        assert mix["why"]
    assert used == set(configs), "a configuration no cell uses"
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))


def test_four_chip_cells_within_their_share(bench):
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_what_each_cell_reports(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] <= 0.1
    cells = [w["name"] for w in bench["workloads"]]

    def reported(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    for m in _metrics(bench):
        for name in m.get("workloads", []):
            assert name in cells, (m["name"], name)
    for cell in cells:
        assert sum(reported(m, cell) for m in bench["end_to_end"]) >= 2
        assert any(reported(m, cell) for m in bench["per_layer"])
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in cells:
            if reported(m, cell):
                assert reported(moved, cell), (m["name"], cell)


def test_every_per_layer_metric_has_a_reader(bench):
    for m in bench["per_layer"]:
        path = os.path.join(ROOT, "chipbench", "metrics", m["name"] + ".py")
        assert os.path.exists(path), path
    layers = {}
    for m in bench["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def test_files_under_paths_are_named_from_allowed_characters(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in bench["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, base)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert ok.match(rel), rel
