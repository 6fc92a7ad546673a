"""The benchmark's own tests run on the CPU at toy sizes. They build a
throw-away root (a ``BENCHMARK.json`` and a benchmark directory of data
files) so that the real ``BENCHMARK.json`` is never what a test edits."""

import json
import os
import shutil
import sys

# before jax is imported: the CPU, as four devices for the dp2 x tp2 toy
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_CELLS = {
    "tiny-train": ("tiny-gpt", "tiny-train", 1),
    "tiny-serve-closed": ("tiny-gpt", "tiny-serve-closed", 1),
    "tiny-serve-open": ("tiny-gpt", "tiny-serve-open", 1),
    "tiny-train-dp2tp2": ("tiny-gpt-dp2tp2", "tiny-train", 4),
}


@pytest.fixture
def tiny_root(tmp_path):
    """A root whose cells are the toys of ``tests/data``, with the real
    metric definitions and readers."""
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(BENCH, "layer_metrics"), bench / "layer_metrics")
    (bench / "configs").mkdir()
    (bench / "traffic").mkdir()
    configs, workloads = {}, []
    for cell, (config, traffic, chips) in TINY_CELLS.items():
        shutil.copy(os.path.join(HERE, "data", config + ".json"),
                    bench / "configs" / (config + ".json"))
        shutil.copy(os.path.join(HERE, "data", traffic + ".json"),
                    bench / "traffic" / (traffic + ".json"))
        configs[config] = {"name": config, "source": "none", "reduced": [],
                           "file": f"bench/configs/{config}.json", "why": "toy"}
        workloads.append({"name": cell, "config": config, "traffic": traffic,
                          "chips": chips, "why": "toy"})
    kind = {"train": "tiny-train", "batch": "tiny-serve-closed",
            "steady": "tiny-serve-open"}

    def cells_of(metric):
        out = set()
        for w in metric.get("workloads", []):
            for k, cell in kind.items():
                if k in w:
                    out.add(cell)
            if "dp2tp2" in w:
                out.add("tiny-train-dp2tp2")
        return sorted(out)

    doc = dict(real, configs=list(configs.values()), workloads=workloads)
    for group in ("end_to_end", "per_layer"):
        doc[group] = [dict(m, workloads=cells_of(m)) if "workloads" in m else m
                      for m in real[group]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    return str(tmp_path)
