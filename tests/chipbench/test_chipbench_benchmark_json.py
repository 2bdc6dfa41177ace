"""BENCHMARK.json keeps to the contract's limits, and agrees with the files
it names."""

import json
import re

import pytest

from chipbench.harness import loader

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return loader.load_benchmark()


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (loader.REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in bench["paths"])
    assert len(bench["command"]) <= 32
    for word in bench["command"]:
        assert 1 <= len(word) <= 200 and "\n" not in word and "\t" not in word


def test_names_units_and_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for group in ("configs", "workloads"):
        names = [x["name"] for x in bench[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metrics) == len(set(metrics))
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]


def test_cells_name_files_that_load(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        assert w["config"] in configs
        # the contract's rule: a pair of configuration and traffic once
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cell = loader.load_cell(w)
        cfg = cell["config"]
        assert cfg["name"] == w["config"] and cfg["chips"] == w["chips"]
        assert set(cfg["limits"]) == {"loss_gap", "update1_gap",
                                      "updateK_gap", "gain_gap",
                                      "root_gain_gap", "root_hess_gap",
                                      "rows_gap",
                                      "leaves_gap"}
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        cfg = json.loads((loader.REPO / c["file"]).read_text())
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_per_layer_metrics_match_their_files_and_move_what_cells_report(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    specs = {s["name"]: s for s in loader.load_layer_metrics()}
    assert set(specs) == {m["name"] for m in bench["per_layer"]}
    for m in bench["per_layer"]:
        spec = specs[m["name"]]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        # which cells report it is said in BENCHMARK.json alone
        assert "workloads" not in spec
        assert spec["module"] is not None or spec["reader"] in ("span",
                                                                "counter")
        assert m["moves"] in e2e
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            # the cell reports the end-to-end metric this one should move
            assert cell in moved.get("workloads", cells)
    # every cell reports setup_s, another end-to-end metric and a layer metric
    for cell in cells:
        assert any(cell in m["workloads"] for m in bench["per_layer"])
        assert len([m for m in bench["end_to_end"]
                    if cell in m.get("workloads", cells)]) >= 2


def test_rooflines_and_mfu_are_named_as_the_contract_says(bench):
    names = {m["name"]: m for m in bench["per_layer"]}
    assert names["hist_roofline"]["unit"] == "%"
    assert any("mfu" in n.split("_") for n in names)
