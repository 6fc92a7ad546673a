"""The data-driven part of the benchmark: find a cell's files by the names
in ``BENCHMARK.json``, hold what a run observed, and print the result line.

A cell (one entry of ``workloads``) names a configuration and a traffic mix.
``configs/<config>.json`` names its family module, ``traffic/<traffic>.json``
its driver, and ``layer_metrics/<metric>.json`` its reader. Adding any of
them is adding files and appending entries: nothing here lists them.
"""

from __future__ import annotations

import collections
import dataclasses
import importlib
import json
import os
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


def read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmarks.<kind>.<name>``: a family, a driver or a reader."""
    return importlib.import_module(f"benchmarks.{kind}.{name}")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]          # the configuration file as it is run
    traffic_name: str
    traffic: Dict[str, Any]         # the traffic file
    end_to_end: List[str]           # metric names reported with --trace 0
    per_layer: List[Dict[str, Any]]  # layer-metric files read with --trace 1
    units: Dict[str, str]           # unit of every metric, by name

    @property
    def family(self):
        return load_module("families", self.config["family"])

    @property
    def driver(self):
        return load_module("drivers", self.traffic["driver"])


def _in_cell(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: str = ROOT,
              bench_dir: Optional[str] = None) -> Cell:
    """Resolve ``workload`` through ``<root>/BENCHMARK.json``. Files are
    found by name under ``bench_dir`` (default: the directory of the
    configuration's ``file``)."""
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has: "
                         + ", ".join(sorted(cells)))
    w = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    cfg_path = os.path.join(root, cfg_entry["file"])
    bench_dir = bench_dir or os.path.dirname(os.path.dirname(cfg_path))
    per_layer = []
    for m in bench["per_layer"]:
        if _in_cell(m, workload):
            spec = read_json(os.path.join(bench_dir, "layer_metrics",
                                          m["name"] + ".json"))
            per_layer.append({**spec, "name": m["name"], "unit": m["unit"]})
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        config=read_json(cfg_path), traffic_name=w["traffic"],
        traffic=read_json(os.path.join(bench_dir, "traffic",
                                       w["traffic"] + ".json")),
        end_to_end=[m["name"] for m in bench["end_to_end"]
                    if _in_cell(m, workload)],
        per_layer=per_layer,
        units={m["name"]: m["unit"]
               for m in bench["end_to_end"] + bench["per_layer"]})


# ---------------------------------------------------------------------------
# the device


def peaks_for(device_kind: str) -> Dict[str, float]:
    table = read_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise SystemExit(f"device kind {device_kind!r} is not in "
                         f"benchmarks/peaks.json: no peak, no utilization")
    return table[device_kind]


def require_devices(chips: int, allow_cpu: bool = False):
    """The devices this cell runs on. Anything but ``chips`` TPU chips ends
    the process non-zero before a result line (``allow_cpu`` is for the
    tests' tiny runs, never for the command)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise SystemExit(f"benchmarks: jax found no TPU (platform "
                         f"{devs[0].platform!r}); no result on this machine")
    if len(devs) < chips:
        raise SystemExit(f"benchmarks: the cell needs {chips} chips, jax "
                         f"reports {len(devs)}")
    return devs[:chips]


def _stat(device, key: str) -> int:
    return int((device.memory_stats() or {}).get(key, 0))


def window_bytes(devices) -> int:
    """HBM the fullest chip holds when a window closes: the buffers in use
    now (weights, optimizer state, feeds) plus the most the runtime has set
    aside for the temporaries of programs (``peak_bytes_reserved``). The TPU
    runtime counts the two apart, and their peaks need not coincide, so the
    two peaks are never added."""
    return max(_stat(d, "bytes_in_use") + _stat(d, "peak_bytes_reserved")
               for d in devices)


def device_info(devices, at_window_close: int = 0) -> Dict[str, Any]:
    """The device as JAX reports it. ``memory_peak_bytes`` is the larger of
    the most buffers ever in use (set-up included) and ``window_bytes`` when
    the window closed."""
    import jax

    d = devices[0]
    peak = max(_stat(x, "peak_bytes_in_use") for x in devices)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count(),
            "memory_peak_bytes": max(peak, int(at_window_close)),
            "memory_stats": {k: v for k, v in (d.memory_stats() or {}).items()
                             if isinstance(v, (int, float))}}


# ---------------------------------------------------------------------------
# compiles


class CompileLog:
    """Counts XLA compiles (or loads from the persistent cache: either one
    stalls a request or a step) and the cache's hits and misses, through
    ``jax.monitoring``. ``mark()`` starts the measured window."""

    def __init__(self):
        import jax

        self.events = collections.Counter()
        self._at_mark: Optional[collections.Counter] = None
        jax.monitoring.register_event_listener(
            lambda event, **kw: self.events.update([event]))
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, secs, **kw: self.events.update([event]))

    def mark(self) -> None:
        self._at_mark = collections.Counter(self.events)

    def since_mark(self, event: str = COMPILE_EVENT) -> int:
        return self.events[event] - self._at_mark[event]

    def cache(self) -> Dict[str, int]:
        return {"hits": self.events[CACHE_HIT], "misses": self.events[CACHE_MISS]}


# ---------------------------------------------------------------------------
# what a run observed, and the result line


@dataclasses.dataclass
class Observed:
    """Everything a driver hands back. ``values`` holds end-to-end metrics
    and plain counters by name, ``series`` per-request or per-step lists;
    readers take per-layer metrics from these and from ``trace``."""
    correct: bool
    attempted: int
    failed: int
    values: Dict[str, float]
    series: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    trace: Any = None               # trace_reduce.Trace, with --trace 1
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Run:
    """What a driver is given."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    t_start: float                  # perf_counter at process start
    compiles: CompileLog
    peaks: Optional[Dict[str, float]] = None

    @property
    def trace_seconds(self) -> float:
        """How much of the window's end a traced run traces: a few steps
        or batches (the traffic file may say; decode runs some 200,000
        device operations a second, and the trace is read in Python)."""
        return float(self.cell.traffic.get("trace_seconds", 4.0))

    def log(self, msg: str) -> None:
        print(f"[{self.cell.name} +{time.perf_counter() - self.t_start:6.1f}s] "
              f"{msg}", flush=True)


def per_layer_metrics(run: Run, obs: Observed) -> Dict[str, float]:
    out = {}
    for spec in run.cell.per_layer:
        value = load_module("readers", spec["reader"]).read(run, obs, spec)
        if value is not None:
            out[spec["name"]] = float(value)
    return out


def result_line(run: Run, obs: Observed) -> Dict[str, Any]:
    if run.trace:
        metrics = per_layer_metrics(run, obs)
    else:
        metrics = {n: obs.values[n] for n in run.cell.end_to_end}
    device = device_info(run.devices, obs.values.get("peak_bytes_window", 0))
    line = {"correct": bool(obs.correct), "attempted": int(obs.attempted),
            "failed": int(obs.failed),
            "metrics": {n: {"value": v, "unit": run.cell.units[n]}
                        for n, v in metrics.items()},
            "device": device}
    if run.trace and obs.trace is not None:
        device["busy_s"] = obs.trace.busy_s()
        device["window_s"] = obs.trace.window_s
        line["breakdown"] = obs.trace.breakdown()
    line["notes"] = {**obs.notes, "compile_cache": run.compiles.cache(),
                     "workload": run.cell.name, "seed": run.seed}
    return line


def start_run(workload: str, seed: int, seconds: float, trace: bool,
              t_start: float, root: str = ROOT, bench_dir: Optional[str] = None,
              allow_cpu: bool = False) -> Run:
    """Resolve the cell, take its devices (or refuse), turn the compile
    cache on where ``paddle_tpu.core.config.compile_cache_dir`` says."""
    cell = load_cell(workload, root, bench_dir)
    devices = require_devices(cell.chips, allow_cpu)

    from paddle_tpu.core.config import enable_compile_cache
    enable_compile_cache()
    run = Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
              devices=devices, t_start=t_start, compiles=CompileLog())
    if devices[0].platform == "tpu":
        run.peaks = peaks_for(devices[0].device_kind)
    return run


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, **where) -> Dict[str, Any]:
    """One cell, once, in this process; returns the result line's object."""
    run = start_run(workload, seed, seconds, trace, t_start, **where)
    return result_line(run, run.cell.driver.run(run))
