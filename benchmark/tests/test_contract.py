"""``BENCHMARK.json`` against the contract's mechanical limits, and
every name in it resolves to files that exist."""

import os
import re

import pytest

from benchmark.harness import cell as cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark()


def _line(s, limit=200):
    return isinstance(s, str) and 1 <= len(s) <= limit \
        and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    path = os.path.join(cells.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["command"]) <= 32
    assert all(_line(w) for w in bench["command"])
    assert bench["paths"] == ["benchmark"]
    assert 1 <= len(bench["configs"]) <= 24
    assert 2 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128


def test_names_units_and_keys(bench):
    for group, keys, optional in (
            ("configs", {"name", "source", "file", "reduced", "why"}, set()),
            ("workloads", {"name", "config", "traffic", "chips", "why"},
             set()),
            ("end_to_end", {"name", "unit", "better", "bound", "source"},
             {"workloads"}),
            ("per_layer", {"name", "unit", "better", "source", "layer",
                           "moves"}, {"workloads"})):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        for e in bench[group]:
            assert keys <= set(e) <= keys | optional, (group, e["name"])
            assert NAME.match(e["name"]), e["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" and m["bound"] == 0.1
               for m in bench["end_to_end"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and _line(m["layer"])
    for c in bench["configs"]:
        assert _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert _line(w["why"]) and w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_every_workload_resolves_to_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    used = set()
    pairs = set()
    for w in bench["workloads"]:
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        cell = cells.load_cell(w["name"])
        assert cell.config["source"] == configs[w["config"]]["source"]
        assert sorted(cell.config["reduced"]) == \
            sorted(configs[w["config"]]["reduced"])
        assert callable(cells.load_driver(cell.traffic["kind"]))
        # setup_s, one more end-to-end metric, one per-layer metric
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(cells.load_layer_metric(m["name"])), m["name"]
            assert m["moves"] in names, (w["name"], m["name"])
    assert used == set(configs)
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for f in files:
        assert f.startswith("benchmark/") and os.path.exists(
            os.path.join(cells.ROOT, f))


def test_file_names_under_paths():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    root = os.path.join(cells.ROOT, "benchmark")
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), cells.ROOT)
            assert ok.match(rel), rel


def test_run_length_fits_the_check_with_24_cells(bench):
    runs = 2 + 14 * 24
    total = runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200
