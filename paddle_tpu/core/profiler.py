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
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional, Tuple

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
    (milliseconds) of the spans since ``enable_profiler``."""
    global _trace_dir
    _window[1] = time.time_ns()
    if _trace_dir:
        jax.profiler.stop_trace()
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
