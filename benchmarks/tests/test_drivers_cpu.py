"""Each driver end to end on the CPU at the toy configurations of
``tests/data`` (kernels interpreted, four virtual devices for dp2 x tp2).
No time, rate or share from here means anything; what is checked is that
every path runs, the outputs pass the reference checks, and the result line
has the contract's keys. The command itself still refuses a CPU."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmarks import harness

from conftest import ROOT, TINY_CELLS

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_cell_runs_and_is_correct(tiny_root, cell):
    line = harness.run_cell(cell, seed=3, seconds=2.0, trace=False,
                            t_start=time.perf_counter(), root=tiny_root,
                            allow_cpu=True)
    assert KEYS <= set(line)
    assert line["correct"] is True, line["notes"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    metrics = line["metrics"]
    assert "setup_s" in metrics and len(metrics) >= 2
    assert all(m["value"] > 0 and m["unit"] for m in metrics.values())
    json.dumps(line)
    if "dp2tp2" in cell:
        s = line["notes"]["structure"]
        assert sum(s["collectives"].values()) > 0 and s["tp_sharded_params"] > 0


def test_traced_run_reports_per_layer_metrics(tiny_root):
    line = harness.run_cell("tiny-serve-open", seed=4, seconds=3.0, trace=True,
                            t_start=time.perf_counter(), root=tiny_root,
                            allow_cpu=True)
    assert line["correct"] is True, line["notes"]
    names = set(line["metrics"])
    assert {"compiles_in_window.steady", "request_latency_p50_ms",
            "request_latency_p99_ms",
            "generator_late_p99_ms.steady", "completed_tokens_per_s"} <= names
    assert line["metrics"]["compiles_in_window.steady"]["value"] == 0
    assert not names & {"request_latency_p95_ms", "setup_s"}
    # no device plane in a CPU trace: busy is 0, which the driver refuses
    assert line["device"]["busy_s"] == 0.0 and line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "gpt2m-train-s1024", "--seed", "0", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
    assert "no TPU" in p.stderr
