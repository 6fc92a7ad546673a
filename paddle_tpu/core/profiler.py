"""The program's one span record, and the views of it.

Analog of the reference's host profiler (platform/profiler.h:27/73/127:
RecordEvent RAII ranges, EnableProfiler/DisableProfiler with a sorted
aggregate table) and CUPTI device tracer (device_tracer.h:49).

``record_event(name, **ids)`` is the one entry. It enters a
``jax.profiler.TraceAnnotation``, so the span lies in the profiler's trace
on the device's clock whenever a session is open (``profiler(trace_dir)``
here, or anyone's ``jax.profiler.start_trace``) and costs a flag test when
none is; and it appends ``(name, start_ns, dur_ns, thread, ids)`` to one
bounded ring, always. ``start_ns`` is ``time.time_ns()``, the clock the
profiler stamps its host events from, so a reader can lay the ring beside
a trace; ``dur_ns`` comes from ``perf_counter_ns``. The ring keeps the
last ``RING`` spans of the process: a reader that wants a window takes its
snapshot (``spans(since_ns)``) when the window closes.

A span's parent is the span that encloses it on the same thread; readers
compute self time as duration minus children. Spans of one request share
``req=<journal span id>``, spans of one dispatch ``dispatch=<n>``.

The aggregate table (``enable_profiler`` / ``disable_profiler`` /
``profiler()``) and the chrome dump (``timeline``) are views of the ring.
JAX's own compile phases arrive through one ``jax.monitoring`` listener as
``jax.trace`` / ``jax.lower`` / ``jax.compile`` / ``jax.cache_read`` spans
on the compiling thread, so whatever span that thread is in is their
parent: "what compiled, under what".

The device's side has one record too. Whoever holds an executable
(``Trainer``, ``io._aot_compile``) registers it with ``register_program``:
the XLA module's name as a trace prints it and a thunk for its optimized
HLO text, nothing evaluated. ``program_tables(name)`` turns the text into
``{instruction: ScopeRow}`` (``profiling.fusion.scope_table``: the
``named_scope`` path of the instruction's ``op_name``, ``remat``,
``backward``, ``inherited``, a collective's mesh ``axes``) when first
asked, and
``device_scopes(events, tables)`` joins a trace's device operations to
it: measured self time by scope path. The profiler's device rows and the
benchmark's ``scope_time_share`` reader are two views of that one join.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import json
import os
import threading
import time
from collections import deque
from typing import (Any, Callable, Dict, Iterable, Iterator, List, Optional,
                    Sequence, Tuple)

import jax

RING = 16384

Span = Tuple[str, int, int, int, Dict[str, Any]]  # name, start, dur, thread, ids

_ring: deque = deque(maxlen=RING)
# the profiler window the table and the chrome dump cover: [enable, disable)
_window: List[Optional[int]] = [None, None]
_trace_dir: Optional[str] = None

_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_read",
}


class record_event:
    """RAII span (RecordEvent, profiler.h:73): ``with record_event("io.export",
    bucket=16): ...``. ``ids`` may be filled in while the span is open
    (``span.ids["rows"] = n``); the ring sees them, the trace annotation
    holds what was known on entry."""

    __slots__ = ("name", "ids", "_ann", "_start_ns", "_t0")

    def __init__(self, name: str, **ids):
        self.name = name
        self.ids = ids

    def __enter__(self) -> "record_event":
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.ids)
        self._ann.__enter__()
        self._start_ns = time.time_ns()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        dur = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        _ring.append((self.name, self._start_ns, dur, threading.get_ident(),
                      self.ids))
        return False


def record_span(name: str, start_ns: int, dur_ns: int,
                thread: Optional[int] = None, **ids) -> None:
    """Add a span whose start (``time.time_ns()``) or identity is only
    known at its end; ``thread`` when it is another thread's (a request's
    wait, recorded by the worker that ends it). Ring only: the trace has
    no way to take a span late."""
    _ring.append((name, int(start_ns), int(dur_ns),
                  threading.get_ident() if thread is None else thread, ids))


def spans(since_ns: int = 0) -> List[Span]:
    """A snapshot of the ring: the spans that started at or after
    ``since_ns``, in the order they ended."""
    return [s for s in _ring.copy() if s[1] >= since_ns]


def _on_jax_duration(event: str, secs: float, **kw) -> None:
    name = _JAX_EVENTS.get(event)
    if name is not None:
        dur = int(secs * 1e9)
        ids = {"fun": kw["fun_name"]} if "fun_name" in kw else {}
        record_span(name, time.time_ns() - dur, dur, event=event, **ids)


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


# -- the device's side: programs, their tables, the join -------------------------

PROGRAMS = 8
CHIP = 0            # the chip whose operations the device rows are made of
UNSCOPED = "(unscoped)"
NOT_IN_TABLE = "(not in table)"

# [name, key, text thunk or None once read, mesh axes, table or None]
_programs: deque = deque(maxlen=PROGRAMS)


def register_program(name: str, text: Callable[[], str],
                     mesh_axes: Sequence[Tuple[str, int]] = (),
                     key: Any = None) -> None:
    """Remember an executable: ``name`` is its XLA module's name as a trace
    prints it, without the ``(n)`` (``jit_train_step``); ``text()`` gives
    its optimized HLO text and is not called here; ``mesh_axes`` the
    ``((axis, size), ...)`` of the mesh it runs on, for its collectives.
    The last ``PROGRAMS`` are kept, for the life of the process: the
    thunk must hold no array. A second registration under the same
    ``name`` and ``key`` (not None) takes the first one's place."""
    if key is not None:
        for p in list(_programs):
            if p[0] == name and p[1] == key:
                _programs.remove(p)
    _programs.append([name, key, text, tuple(mesh_axes), None])


def module_name(trace_name: str) -> str:
    """``jit_train_step(10337867909937505871)`` -> ``jit_train_step``."""
    return trace_name.split("(", 1)[0]


def program_tables(name: str) -> List[Dict[str, Any]]:
    """The ``scope_table`` of every registered program whose module is
    ``name`` (a trace's ``name(n)`` will do), oldest first. A program's
    text is fetched and parsed the first time it is asked for; its thunk
    is let go then."""
    from ..profiling.fusion import scope_table

    name, out = module_name(name), []
    for p in list(_programs):
        if p[0] != name:
            continue
        if p[4] is None:
            with record_event("profiler.program_table", module=name) as span:
                text = p[2]()
                p[4] = scope_table(text, p[3])
                span.ids.update(text_bytes=len(text), rows=len(p[4]))
            p[2] = None
        out.append(p[4])
    return out


def _op_key(label: str) -> str:
    """``fusion.300``, ``fusion.300 [fusion]`` or an operation's whole HLO
    text (``%fusion.300 = bf16[...] fusion(...)``) -> ``fusion.300``."""
    return label.split(" ", 1)[0].lstrip("%")


def _self_times(events: Iterable[Tuple[str, int, int]]
                ) -> Dict[str, List[int]]:
    """``{operation: [self ns, calls]}``: an event's duration less the
    events nested inside it (a ``while`` holds its body's operations).
    ``benchmarks.trace_reduce.self_times`` makes the same sweep without the
    calls; the package imports nothing of the benchmark."""
    out: Dict[str, List[int]] = {}
    stack: List[List] = []          # [key, end, self]

    def close(upto: int) -> None:
        while stack and stack[-1][1] <= upto:
            key, _, own = stack.pop()
            row = out.setdefault(key, [0, 0])
            row[0] += max(own, 0)
            row[1] += 1

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([_op_key(name), start + dur, dur])
    close(1 << 62)
    return out


def device_scopes(events: Iterable[Tuple[str, int, int]],
                  tables) -> Dict[str, Any]:
    """Measured device time by the program's own scopes: the one join.

    ``events``: ``(name, start_ns, dur_ns)`` of one chip's operations, a
    name in any form ``_op_key`` reads. ``tables``: one ``scope_table`` or
    several (programs that share a module name): the one whose
    instructions name most of the events' self time is taken, the
    smallest of them on a tie. Returns ``total_ns`` (all self time),
    ``table`` (which one, None without any) and ``paths``: for each scope
    path (``"gpt/attn"``, ``UNSCOPED`` for an operation under no
    ``named_scope``, ``NOT_IN_TABLE`` for one the table does not hold)
    ``path`` (the tuple), ``calls``, ``ns``, of it ``remat_ns``,
    ``backward_ns`` and ``inherited_ns`` (operations with no name stack of
    their own, placed with a neighbour: the compiler's copies, slices and
    kernels), ``axes`` (``{mesh axes: ns}`` of its collectives) and ``ops``
    (``{operation: (ns, calls)}``)."""
    with record_event("profiler.device_scopes") as span:
        own = _self_times(events)
        span.ids["operations"] = len(own)
    tables = [tables] if isinstance(tables, dict) else list(tables)
    pick, best = None, (-1, 0)
    for i, t in enumerate(tables):
        cover = (sum(v[0] for k, v in own.items() if k in t), -len(t))
        if cover > best:
            pick, best = i, cover
    table = tables[pick] if pick is not None else {}
    paths: Dict[str, Dict[str, Any]] = {}
    for key, (ns, calls) in own.items():
        row = table.get(key)
        if row is None:
            label, path = NOT_IN_TABLE, ()
        else:
            label, path = "/".join(row.path) or UNSCOPED, row.path
        at = paths.setdefault(label, dict(path=path, calls=0, ns=0, remat_ns=0,
                                          backward_ns=0, inherited_ns=0,
                                          axes={}, ops={}))
        at["calls"] += calls
        at["ns"] += ns
        at["ops"][key] = (ns, calls)
        if row is not None:
            if row.remat:
                at["remat_ns"] += ns
            if row.backward:
                at["backward_ns"] += ns
            if row.inherited:
                at["inherited_ns"] += ns
            if row.axes is not None:
                at["axes"][row.axes] = at["axes"].get(row.axes, 0) + ns
    return {"total_ns": sum(v[0] for v in own.values()), "table": pick,
            "paths": paths}


def scope_ns(joined: Dict[str, Any], scopes: Sequence[str] = (),
             remat: bool = False, axes: Optional[str] = None,
             unscoped: bool = False, inherited: bool = False) -> int:
    """Nanoseconds of a ``device_scopes`` result that a selection names:
    ``scopes`` keeps the paths holding any of these components (all paths
    when empty), ``unscoped`` those under no scope or not in the table;
    of the kept ones ``remat`` counts the second forward only,
    ``inherited`` the operations placed with a neighbour only, ``axes``
    the collectives over exactly these mesh axes (``"dp"``)."""
    ns = 0
    for label, at in joined["paths"].items():
        if unscoped:
            if label not in (UNSCOPED, NOT_IN_TABLE):
                continue
        elif scopes and not set(scopes) & set(at["path"]):
            continue
        ns += (at["axes"].get(axes, 0) if axes is not None
               else at["remat_ns"] if remat
               else at["inherited_ns"] if inherited else at["ns"])
    return ns


def events_inside(events: Iterable[Tuple[str, int, int]],
                  runs: Iterable[Tuple[int, int]]) -> List[Tuple[str, int, int]]:
    """The events that lie whole inside one of the ``[start, end)`` runs
    (a module's executions, disjoint)."""
    runs = sorted(runs)
    starts = [s for s, _ in runs]
    out = []
    for ev in events:
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] + ev[2] <= runs[i][1]:
            out.append(ev)
    return out


def device_rows(trace_dir: str) -> List[dict]:
    """Device rows of the newest trace under ``trace_dir``: for each XLA
    module of chip ``CHIP`` that a registered program names, one row a
    scope path (``module``, ``name``, ``calls``, ``total`` ms, ``share`` of
    the module's operations, ``remat``, ``backward`` and ``inherited`` ms
    of it). Empty on a backend whose trace has no device plane."""
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        return []
    from jax.profiler import ProfileData

    ops: List[Tuple[str, int, int]] = []
    modules: Dict[str, List[Tuple[int, int]]] = {}
    for plane in ProfileData.from_file(found[-1]).planes:
        if plane.name != f"/device:TPU:{CHIP}":
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops = [(e.name, int(e.start_ns), int(e.duration_ns))
                       for e in line.events]
            elif line.name == "XLA Modules":
                for e in line.events:
                    modules.setdefault(module_name(e.name), []).append(
                        (int(e.start_ns), int(e.start_ns + e.duration_ns)))
    rows: List[dict] = []
    for mod, runs in sorted(modules.items(),
                            key=lambda kv: -sum(e - s for s, e in kv[1])):
        tables = program_tables(mod)
        if not tables:
            continue
        joined = device_scopes(events_inside(ops, runs), tables)
        for label, at in sorted(joined["paths"].items(),
                                key=lambda kv: -kv[1]["ns"]):
            rows.append(dict(
                module=mod, name=label, calls=at["calls"],
                total=at["ns"] / 1e6,
                share=100.0 * at["ns"] / max(joined["total_ns"], 1),
                remat=at["remat_ns"] / 1e6, backward=at["backward_ns"] / 1e6,
                inherited=at["inherited_ns"] / 1e6))
    return rows


# -- views of the ring ---------------------------------------------------------


def _in_window() -> List[Span]:
    since, until = _window
    if since is None:
        return []
    return [s for s in spans(since) if until is None or s[1] < until]


def enable_profiler(trace_dir: Optional[str] = None) -> None:
    """EnableProfiler analog: the table and the chrome dump cover the ring
    from now on; optionally also starts a jax device trace."""
    global _trace_dir
    _window[:] = [time.time_ns(), None]
    _trace_dir = trace_dir
    if trace_dir:
        jax.profiler.start_trace(trace_dir)


def disable_profiler(sorted_key: str = "total", print_table: bool = True) -> List[dict]:
    """DisableProfiler analog: stop tracing, return + print aggregate rows
    (milliseconds) of the spans since ``enable_profiler``. Where this
    profiler started the device trace, the device's rows by scope
    (``device_rows``) are printed under the host's."""
    global _trace_dir
    _window[1] = time.time_ns()
    device: List[dict] = []
    if _trace_dir:
        jax.profiler.stop_trace()
        device = device_rows(_trace_dir)
        _trace_dir = None
    samples: Dict[str, List[float]] = {}
    for name, _, dur, _, _ in _in_window():
        samples.setdefault(name, []).append(dur / 1e6)
    rows = [dict(name=name, calls=len(ms), total=sum(ms), min=min(ms),
                 max=max(ms), ave=sum(ms) / len(ms))
            for name, ms in samples.items()]
    key = sorted_key if sorted_key in ("total", "calls", "min", "max", "ave") else "total"
    rows.sort(key=lambda r: r[key], reverse=True)
    if print_table and rows:
        hdr = f"{'Event':<40}{'Calls':>8}{'Total(ms)':>12}{'Min':>10}{'Max':>10}{'Ave':>10}"
        print(hdr)
        print("-" * len(hdr))
        for r in rows:
            print(
                f"{r['name']:<40}{r['calls']:>8}{r['total']:>12.3f}"
                f"{r['min']:>10.3f}{r['max']:>10.3f}{r['ave']:>10.3f}"
            )
    if print_table and device:
        hdr = (f"{'Device scope (module)':<48}{'Calls':>8}{'Total(ms)':>12}"
               f"{'Share%':>9}{'Remat(ms)':>11}{'Bwd(ms)':>11}"
               f"{'Inher(ms)':>11}")
        print(hdr)
        print("-" * len(hdr))
        for r in device:
            print(f"{r['name'] + ' (' + r['module'] + ')':<48}{r['calls']:>8}"
                  f"{r['total']:>12.3f}{r['share']:>9.2f}{r['remat']:>11.3f}"
                  f"{r['backward']:>11.3f}{r['inherited']:>11.3f}")
    return rows


@contextlib.contextmanager
def profiler(trace_dir: Optional[str] = None, sorted_key: str = "total") -> Iterator[None]:
    """``fluid.profiler.profiler`` context-manager analog (profiler.py:221)."""
    enable_profiler(trace_dir)
    try:
        yield
    finally:
        disable_profiler(sorted_key)


def start_profiler(state: str = "All", trace_dir=None):
    """profiler.py start_profiler analog."""
    enable_profiler(trace_dir)


def stop_profiler(sorted_key: str = "total", profile_path=None):
    """profiler.py stop_profiler analog — prints the aggregate table."""
    return disable_profiler(sorted_key=sorted_key)


def cuda_profiler(*args, **kwargs):
    """profiler.py:39 cuda_profiler (nvprof control) — vendor-profiler
    control is jax.profiler's trace on TPU; kept as an explicit stub so
    ported drivers fail loudly rather than silently."""
    raise NotImplementedError(
        "cuda_profiler is CUDA-specific; use profiler()/jax.profiler traces")


def reset_profiler():
    """profiler.py reset_profiler analog: the table and the chrome dump
    start over from now (the ring itself is the process's, not theirs)."""
    if _window[0] is not None:
        _window[:] = [time.time_ns(), None]


def timeline(path: str, extra_spans=None) -> int:
    """tools/timeline.py:115 analog: dump the ring's spans of the profiler
    window (``enable_profiler`` to ``disable_profiler``) as
    chrome://tracing JSON; the device's side comes from the jax.profiler
    trace directory. Returns the number of events written.

    ``extra_spans`` — additional ``(name, start_us, dur_us, tid)`` tuples
    merged into the dump: the Trainer's dispatch spans
    (``StepTimer.spans_us``) export through here, so a trace exists even
    when the profiler was never enabled."""
    events = [
        {"name": name, "ph": "X", "ts": start / 1e3, "dur": dur / 1e3,
         "pid": 0, "tid": tid % 10000, "cat": "host", "args": ids}
        for name, start, dur, tid, ids in _in_window()
    ] + [
        {"name": name, "ph": "X", "ts": ts, "dur": dur,
         "pid": 0, "tid": tid, "cat": "host"}
        for name, ts, dur, tid in extra_spans or []
    ]
    events.sort(key=lambda e: e["ts"])
    with open(path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f,
                  default=str)
    return len(events)
