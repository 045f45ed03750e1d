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
    from chipbench import harness

    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        cfg = configs[w["config"]]
        used.add(w["config"])
        assert cfg["file"].startswith("chipbench/configs/")
        with open(os.path.join(ROOT, cfg["file"])) as f:
            raw = json.load(f)
        family = harness.load_family(raw["family"])
        # the cut, if any, is declared as the guide's section 4 wants it
        assert harness.cut_problems(cfg["reduced"], raw, family.CUTS) == []
        assert raw["source"] == cfg["source"]
        assert family.sizes(raw)["vocab_size"] > 0
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
    assert configs["gpt2-124m"]["reduced"] == []


# A cut as the guide's example makes it: depth, the experts held here
# and the vocabulary slice reduced, every width as published, the
# layers shared by eight chips.
CUT = {"source": "https://example.org/config.json", "family": "gpt",
       "hidden_size": 2048, "moe_intermediate_size": 1408,
       "num_hidden_layers": 6, "n_routed_experts": 8, "vocab_size": 12800,
       "published": {"num_hidden_layers": 27, "n_routed_experts": 64,
                     "vocab_size": 102400},
       "assumed": {}, "deployment": {
           "chips_per_layer": 8,
           "how": "experts and vocabulary rows over eight chips"}}
CUT_KEYS = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
CUTS = {"num_hidden_layers": "depth", "n_routed_experts": "experts",
        "vocab_size": "vocabulary", "num_attention_heads": "heads"}


def _without(d, key):
    return {k: v for k, v in d.items() if k != key}


def _held(**held):
    return dict(CUT, **held)


@pytest.mark.parametrize("reduced,raw,said", [
    (CUT_KEYS, CUT, None),                                   # a good cut
    ([], _without(CUT, "published"), None),                  # no cut
    (CUT_KEYS + ["hidden_size"], dict(
        CUT, hidden_size=1024,
        published=dict(CUT["published"], hidden_size=2048)),
     "'hidden_size' is not a key its family lets be cut"),
    (CUT_KEYS + ["kv_lora_rank"], dict(
        CUT, kv_lora_rank=128,
        published=dict(CUT["published"], kv_lora_rank=512)),
     "'kv_lora_rank' is not a key its family lets be cut"),
    (CUT_KEYS, dict(CUT, published=_without(CUT["published"], "vocab_size")),
     "lacks the source's 'vocab_size'"),
    (CUT_KEYS, dict(CUT, published=dict(CUT["published"], vocab_size=12800)),
     "'vocab_size' is listed as reduced and is not below"),
    (CUT_KEYS + ["num_attention_heads"], CUT,
     "'num_attention_heads' is not a key of the file"),
    (CUT_KEYS[:2], CUT, "'vocab_size' has a published value"),
    (CUT_KEYS, dict(CUT, deployment="eight chips share each layer"),
     "chips_per_layer"),
    (CUT_KEYS, _without(CUT, "assumed"), "the file lacks 'assumed'"),
    # the guide's floors: what is left is still the model
    (CUT_KEYS, _held(num_hidden_layers=3), "at least four layers"),
    (CUT_KEYS, dict(CUT, num_hidden_layers=4, deployment=dict(
        CUT["deployment"], leading_dense_layers=1)), "at least four layers"),
    (CUT_KEYS, _held(n_routed_experts=4), "at least 8 routed experts"),
    (CUT_KEYS, _held(vocab_size=12799), "at least an eighth"),
])
def test_a_cut_is_declared_and_keeps_to_its_floors(reduced, raw, said):
    from chipbench import harness

    bad = harness.cut_problems(reduced, raw, CUTS)
    if said is None:
        assert bad == []
    else:
        assert len(bad) == 1 and said in bad[0], bad


def test_every_family_says_what_may_be_cut():
    from chipbench import harness

    for base in harness.FAMILY_PATH:
        for f in sorted(os.listdir(base)):
            if f.endswith(".py"):
                family = harness.load_family(f[:-3])
                assert family.CUTS and set(family.CUTS.values()) <= set(
                    harness.CUT_FLOORS), f


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
