"""``BENCHMARK.json`` against the limits of the benchmark's contract that
can be checked without a chip, and against the files it names."""

import json
import os
import re

import pytest

from benchmarks import harness

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def doc():
    return json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def one_line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_keys_names_and_units(doc):
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert doc["paths"] == ["benchmarks"] and 1 <= doc["run_seconds"] <= 51
    assert len(doc["command"]) <= 32 and all(one_line(w) for w in doc["command"])
    metrics = doc["end_to_end"] + doc["per_layer"]
    for group in (doc["configs"], doc["workloads"], metrics):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in doc["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in doc["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert one_line(m["layer"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert any(m["name"] == "setup_s" for m in doc["end_to_end"])


def test_configs_and_cells(doc):
    configs = {c["name"]: c for c in doc["configs"]}
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["why"]) and one_line(c["source"])
        assert c["file"].startswith("benchmarks/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    assert 2 <= len(doc["workloads"]) <= 24
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(doc["workloads"]) // 4)
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and one_line(w["why"])
    assert {w["config"] for w in doc["workloads"]} == set(configs)


def test_every_cell_resolves_and_reports_enough(doc):
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    for w in doc["workloads"]:
        cell = harness.load_cell(w["name"])
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer
        assert cell.family and cell.driver
        for m in cell.per_layer:    # reported only where what it moves is
            assert m["moves"] in cell.end_to_end and m["moves"] != "setup_s"
            harness.load_module("readers", m["reader"])
    assert set(e2e) == {n for w in doc["workloads"]
                        for n in harness.load_cell(w["name"]).end_to_end}


def test_layer_metric_files_say_what_the_entries_say(doc):
    for m in doc["per_layer"]:
        spec = json.load(open(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".json")))
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert spec.get("workloads") == m.get("workloads")
    on_disk = {f[:-5] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))}
    assert on_disk == {m["name"] for m in doc["per_layer"]}


def test_every_file_is_named_from_the_allowed_characters():
    for d, dirs, files in os.walk(BENCH):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), ROOT)
            assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel
