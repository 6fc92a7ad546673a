"""From a profiler trace to numbers: device busy time, idle gaps and what
the host was doing in them, kernel and collective time, the step's device
duration, and the operations that took most time.

Two stages, so that the arithmetic can be checked on a small recorded
trace (``tests/data/``): ``read_xplane`` turns the profiler's
``.xplane.pb`` into plain lists through ``jax.profiler.ProfileData``;
``Trace`` reduces those lists. Times are nanoseconds on the trace's clock.

What a TPU trace holds (seen on a v5e, JAX 0.9): one plane per chip named
``/device:TPU:<n>`` whose line ``XLA Ops`` has one event per executed HLO
operation, named by the operation's whole HLO text (``%fusion.3 = bf16[..]
fusion(...), kind=kOutput``; kept here as ``fusion.3 [fusion]``), and whose
line ``XLA Modules`` has one event per executed program; and a plane
``/host:CPU`` with one line per thread, on which this benchmark's own
``jax.profiler.TraceAnnotation`` spans appear under their names.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]          # [start, end) in ns

SPAN_PREFIX = "bench."              # the benchmark's own host spans
WINDOW_SPAN = SPAN_PREFIX + "window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
CONTAINER = re.compile(r"\[(while|conditional|call)\]$")
KERNEL_TARGET = "tpu_custom_call"    # a Pallas kernel is a call to Mosaic
_OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")


def op_label(hlo: str) -> str:
    """``name [opcode]`` from an operation's HLO text; a name that is no
    HLO text is kept as it is."""
    name, eq, rest = hlo.partition(" = ")
    if not eq:
        return hlo
    m = _OPCODE.search(" " + rest)
    return f"{name.lstrip('%')} [{m.group(1) if m else '?'}]"


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals; sorted, disjoint."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that the disjoint, sorted ``busy`` leaves."""
    out, at = [], window[0]
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < window[1]:
        out.append((at, window[1]))
    return out


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """``a`` minus ``b``."""
    b = union(b)
    out: List[Interval] = []
    for s, e in union(a):
        out += clip(gaps(clip(b, (s, e)), (s, e)), (s, e))
    return out


def self_times(events: Sequence[Tuple[str, int, int]]) -> Dict[str, int]:
    """Per name, duration not covered by events nested inside (a ``while``
    holds its body's operations: only the body's time is theirs)."""
    out: Dict[str, int] = {}
    stack: List[List] = []          # [name, end, self]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0) + max(own, 0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(1 << 62)
    return out


@dataclasses.dataclass
class Trace:
    """``ops`` and ``modules``: per chip, ``[name, start, dur]``; ``kernels``:
    names of operations that are Pallas (Mosaic) kernel calls; ``host``:
    the benchmark's spans ``[name, start, dur]`` without the prefix;
    ``window``: the traced part of the measured window."""
    ops: Dict[int, List[Tuple[str, int, int]]]
    modules: Dict[int, List[Tuple[str, int, int]]]
    kernels: List[str]
    host: List[Tuple[str, int, int]]
    window: Interval

    # -- serialised form (the recorded trace of the tests) -------------------
    def to_json(self) -> dict:
        return {"ops": {str(k): v for k, v in self.ops.items()},
                "modules": {str(k): v for k, v in self.modules.items()},
                "kernels": self.kernels, "host": self.host,
                "window": list(self.window)}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({int(k): [tuple(e) for e in v] for k, v in d["ops"].items()},
                   {int(k): [tuple(e) for e in v]
                    for k, v in d["modules"].items()},
                   list(d["kernels"]), [tuple(e) for e in d["host"]],
                   tuple(d["window"]))

    # -- reductions -----------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _ivals(self, chip: int, keep=None) -> List[Interval]:
        return clip(((s, s + d) for n, s, d in self.ops.get(chip, [])
                     if keep is None or keep(n)), self.window)

    def busy(self, chip: int) -> List[Interval]:
        return union(self._ivals(chip))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.ops:
            return 0.0
        return sum(total(self.busy(c)) for c in self.ops) / len(self.ops) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def leaf_time_s(self, keep, chip: int = 0) -> float:
        """Device seconds of the operations ``keep(name)`` selects."""
        return total(union(self._ivals(chip, keep))) / 1e9

    def count(self, keep, chip: int = 0) -> int:
        lo, hi = self.window
        return sum(1 for n, s, d in self.ops.get(chip, [])
                   if keep(n) and s >= lo and s + d <= hi)

    def is_kernel(self, name: str) -> bool:
        return name in self.kernels

    def collective_s(self, chip: int = 0) -> Tuple[float, float]:
        """(seconds in collectives, seconds of them in which no other
        operation ran on the chip)."""
        coll = union(self._ivals(chip, COLLECTIVE.match))
        # a ``while`` or ``conditional`` spans its body: not other work
        other = self._ivals(chip, lambda n: not COLLECTIVE.match(n)
                            and not CONTAINER.search(n))
        return total(coll) / 1e9, total(subtract(coll, other)) / 1e9

    def main_module(self, chip: int = 0) -> Optional[str]:
        """The program that took most device time in the window."""
        by: Dict[str, int] = {}
        for n, s, d in self.modules.get(chip, []):
            by[n] = by.get(n, 0) + total(clip([(s, s + d)], self.window))
        return max(by, key=by.get) if by else None

    def module_durations_s(self, name: str, chip: int = 0) -> List[float]:
        """Durations of the executions of ``name`` that lie whole inside
        the window."""
        lo, hi = self.window
        return [d / 1e9 for n, s, d in self.modules.get(chip, [])
                if n == name and s >= lo and s + d <= hi]

    def idle_by_span(self, chip: int = 0) -> Dict[str, float]:
        """Idle seconds of ``chip``, each gap under the host span that
        covers most of it (the shortest one on a tie), or ``(no span)``."""
        out: Dict[str, float] = {}
        spans = [(n, s, s + d) for n, s, d in self.host
                 if SPAN_PREFIX + n != WINDOW_SPAN]
        for gs, ge in gaps(self.busy(chip), self.window):
            best, best_key = "(no span)", (0, 0)
            for n, s, e in spans:
                cover = min(e, ge) - max(s, gs)
                if cover > 0 and (cover, s - e) > best_key:
                    best, best_key = n, (cover, s - e)
            out[best] = out.get(best, 0.0) + (ge - gs) / 1e9
        return out

    def breakdown(self, top: int = 10) -> dict:
        own = self_times(
            [(n, max(s, self.window[0]), min(s + d, self.window[1]) - max(s, self.window[0]))
             for n, s, d in self.ops.get(0, [])
             if min(s + d, self.window[1]) > max(s, self.window[0])])
        ops = sorted(own.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_by_span().items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, t / 1e9] for n, t in ops],
                "idle_gaps": [[n, t] for n, t in idle]}


def median(xs: Sequence[float]) -> Optional[float]:
    return statistics.median(xs) if xs else None


# ---------------------------------------------------------------------------
# stage one: the profiler's file


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str, chips: int) -> Trace:
    """The ``Trace`` of the first ``chips`` device planes of ``path``. The
    window is the ``bench.window`` span; a trace without one has the extent
    of its device operations as its window."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[int, list] = {}
    modules: Dict[int, list] = {}
    kernels: set = set()
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < chips:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs = ops.setdefault(chip, [])
                    for e in line.events:
                        label = op_label(e.name)
                        evs.append((label, int(e.start_ns), int(e.duration_ns)))
                        if KERNEL_TARGET in e.name:
                            kernels.add(label)
                elif line.name == MODULES_LINE:
                    modules.setdefault(chip, []).extend(
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name[len(SPAN_PREFIX):], int(e.start_ns),
                             int(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    win = [(s, s + d) for n, s, d in host if SPAN_PREFIX + n == WINDOW_SPAN]
    if win:
        window = win[0]
    else:
        every = [(s, s + d) for evs in ops.values() for _, s, d in evs]
        window = (min(s for s, _ in every), max(e for _, e in every)) \
            if every else (0, 1)
    return Trace(ops, modules, sorted(kernels), host, window)


def _stats(event) -> Dict[str, str]:
    try:
        return {str(k): str(v) for k, v in event.stats}
    except Exception:  # a stat the binding cannot convert: do without
        return {}


def describe(path: str, per_line: int = 12) -> dict:
    """Planes, lines and a few events with their stats: what to look at by
    hand before trusting a reduction."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = {
                "events": len(evs),
                "sample": [{"name": e.name, "start_ns": int(e.start_ns),
                            "dur_ns": int(e.duration_ns), "stats": _stats(e)}
                           for e in evs[:per_line]]}
        out[plane.name] = lines
    return out


if __name__ == "__main__":
    import sys
    print(json.dumps(describe(sys.argv[1]), indent=1)[:200000])
