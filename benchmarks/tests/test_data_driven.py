"""A later PR adds a configuration, a traffic mix and a per-layer metric by
adding files and appending entries to ``BENCHMARK.json``; no file that is
there changes. Shown here on a copy of the real benchmark directory."""

import hashlib
import json
import os
import shutil

from benchmarks import harness

from conftest import BENCH, ROOT


def digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha1(open(p, "rb").read()).hexdigest()
    return out


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = digest(bench)
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))

    # the later PR's new files ...
    medium = json.load(open(bench / "configs" / "gpt2-medium.json"))
    (bench / "configs" / "gpt2-medium-12l.json").write_text(json.dumps(
        dict(medium, n_layer=12)))
    (bench / "traffic" / "train-b8-s512.json").write_text(json.dumps(
        {"driver": "train", "batch": 8, "seq": 512, "warmup_steps": 3,
         "distinct_batches": 4}))
    (bench / "layer_metrics" / "steps_in_window.json").write_text(json.dumps(
        {"reader": "value", "layer": "model step", "unit": "count",
         "better": "higher", "source": "program_counter",
         "moves": "train_tokens_per_s", "workloads": ["gpt2m12-train-s512"],
         "args": {"value": "steps"}}))
    # ... and its appended entries
    doc["configs"].append({"name": "gpt2-medium-12l", "source": medium["source"],
                           "file": "benchmarks/configs/gpt2-medium-12l.json",
                           "reduced": ["n_layer"], "why": "half the depth"})
    doc["workloads"].append({"name": "gpt2m12-train-s512",
                             "config": "gpt2-medium-12l",
                             "traffic": "train-b8-s512", "chips": 1, "why": "x"})
    doc["per_layer"].append({"name": "steps_in_window", "unit": "count",
                             "better": "higher", "source": "program_counter",
                             "layer": "model step", "moves": "train_tokens_per_s",
                             "workloads": ["gpt2m12-train-s512"]})
    for m in doc["end_to_end"]:
        if m["name"] == "train_tokens_per_s":   # its entry names the new cell
            m["workloads"] = m["workloads"] + ["gpt2m12-train-s512"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    cell = harness.load_cell("gpt2m12-train-s512", root=str(tmp_path))
    assert cell.config["n_layer"] == 12 and cell.traffic["seq"] == 512
    assert cell.family.__name__ == "benchmarks.families.gpt"
    assert cell.driver.__name__ == "benchmarks.drivers.train"
    assert cell.end_to_end == ["train_tokens_per_s", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["steps_in_window"]

    # the new metric is read by a reader that was already there
    obs = harness.Observed(True, 10, 0, {"steps": 10.0})
    run = harness.Run(cell=cell, seed=0, seconds=1.0, trace=True, devices=[],
                      t_start=0.0, compiles=None)
    assert harness.per_layer_metrics(run, obs) == {"steps_in_window": 10.0}
    assert cell.units["steps_in_window"] == "count"

    # an old cell is what it was, and no old file changed
    old = harness.load_cell("gpt2m-train-s1024", root=str(tmp_path))
    assert old.config["n_layer"] == 24
    after = digest(bench)
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/gpt2-medium-12l.json", "layer_metrics/steps_in_window.json",
        "traffic/train-b8-s512.json"]


def test_a_reader_with_nothing_to_read_leaves_its_metric_out():
    cell = harness.load_cell("gpt2m-train-s1024")
    run = harness.Run(cell=cell, seed=0, seconds=1.0, trace=True, devices=[],
                      t_start=0.0, compiles=None)
    obs = harness.Observed(True, 1, 0, {})          # no trace, no counters
    assert harness.per_layer_metrics(run, obs) == {}
