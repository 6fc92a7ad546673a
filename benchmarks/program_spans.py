"""The program's own spans, for the readers that turn them into metrics.

``paddle_tpu.core.profiler`` keeps one bounded ring of
``(name, start_ns, dur_ns, thread, ids)``: ``start_ns`` is ``time.time_ns()``,
the clock the profiler stamps its host events from before it subtracts the
session's start. Readers run in the driver's process after it returns, so
they take the ring's snapshot directly. A program without the ring (the
parent of the PR that added it) gives an empty list, and every reader here
then returns nothing.

Three things live here: the measured window on the ring's clock, nesting
(parent = the enclosing span on the same thread; self time = duration less
children, as ``trace_reduce.self_times`` does for device operations), and
the alignment that moves the ring onto a trace's clock.
"""

from __future__ import annotations

import statistics
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Span = Tuple[str, int, int, int, dict]      # name, start_ns, dur_ns, thread, ids
Interval = Tuple[int, int]

SLACK_NS = 200_000          # a program span lies inside its caller's to this
NEST_NS = 50_000            # and a child inside its parent to this


def ring(since_ns: int = 0) -> List[Span]:
    """A snapshot of the program's ring, oldest first; empty where the
    program has none."""
    from paddle_tpu.core import profiler

    read = getattr(profiler, "spans", None)
    return sorted(read(since_ns), key=lambda s: s[1]) if read else []


def ring_is_full(spans: Sequence[Span]) -> bool:
    """True when the ring may have dropped its oldest spans."""
    from paddle_tpu.core import profiler

    return len(spans) >= getattr(profiler, "RING", 1 << 62)


def to_ring_clock(perf_counter_s: float) -> int:
    """A ``time.perf_counter()`` reading as the ring's ``time.time_ns()``:
    the two clocks' difference, read once, now."""
    return int(perf_counter_s * 1e9) + time.time_ns() - time.perf_counter_ns()


def window(run, obs) -> Interval:
    """The measured window ``[t_start + setup_s, + window_s)`` on the
    ring's clock."""
    lo = to_ring_clock(run.t_start + obs.values["setup_s"])
    return lo, lo + int(obs.values["window_s"] * 1e9)


def started_in(spans: Iterable[Span], win: Interval) -> List[Span]:
    return [s for s in spans if win[0] <= s[1] < win[1]]


def by_dispatch(spans: Iterable[Span]) -> Dict[int, Dict[str, List[Span]]]:
    """``{dispatch: {name: [span, ...]}}`` of the spans that carry a
    ``dispatch`` id."""
    out: Dict[int, Dict[str, List[Span]]] = {}
    for s in spans:
        d = s[4].get("dispatch")
        if d is not None:
            out.setdefault(d, {}).setdefault(s[0], []).append(s)
    return out


# ---------------------------------------------------------------------------
# nesting


def parents(spans: Sequence[Span]) -> List[Optional[int]]:
    """For each span, the index of the span that encloses it on the same
    thread (the innermost one), or None. A span that only overlaps another
    is not its child."""
    out: List[Optional[int]] = [None] * len(spans)
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][3], spans[i][1], -spans[i][2]))
    stack: List[int] = []
    thread = None
    for i in order:
        _, start, dur, th, _ = spans[i]
        if th != thread:
            stack, thread = [], th
        # starts come from one clock and durations from another: a child
        # may seem to outlast its parent by a little
        while stack and (spans[stack[-1]][1] + spans[stack[-1]][2] + NEST_NS
                         < start + dur):
            stack.pop()
        out[i] = stack[-1] if stack else None
        stack.append(i)
    return out


def self_ns(spans: Sequence[Span]) -> List[int]:
    """Each span's duration less its direct children's."""
    own = [s[2] for s in spans]
    for i, p in enumerate(parents(spans)):
        if p is not None:
            own[p] -= spans[i][2]
    return [max(x, 0) for x in own]


def totals(spans: Sequence[Span]) -> Dict[str, Dict[str, float]]:
    """Per name: count, total seconds, self seconds, the longest one."""
    out: Dict[str, Dict[str, float]] = {}
    for s, own in zip(spans, self_ns(spans)):
        row = out.setdefault(s[0], {"count": 0, "total_s": 0.0, "self_s": 0.0,
                                    "max_s": 0.0})
        row["count"] += 1
        row["total_s"] += s[2] / 1e9
        row["self_s"] += own / 1e9
        row["max_s"] = max(row["max_s"], s[2] / 1e9)
    return out


# ---------------------------------------------------------------------------
# the ring on a trace's clock


def align(outer: Sequence[Interval], inner: Sequence[Interval],
          slack_ns: int = SLACK_NS, need: float = 0.95) -> Optional[int]:
    """The nanoseconds to add to the ring's clock to reach the trace's.

    ``outer``: the benchmark's spans round its calls into the program
    (``bench.submit``), from the trace, as ``(start, dur)``; ``inner``: the
    program's span of the same calls (``serving.submit``), from the ring.
    The trace holds the last calls of the run, so the pairs are the last
    ``len(outer)`` of ``inner``, or, tried next, those before the calls
    that began after the trace stopped. The shift is the median difference
    of the starts; it stands only if at least ``need`` of the shifted inner
    spans lie inside their outer span to ``slack_ns`` (a pairing off by one
    differs by the gaps between calls, which are not one number, and fails
    that). Otherwise None: no guess.
    """
    outer, inner = sorted(outer), sorted(inner)
    n = len(outer)
    for late in range(len(inner) - n + 1 if n else 0):
        tail = inner[len(inner) - n - late:len(inner) - late]
        shift = int(statistics.median(o[0] - i[0] for o, i in zip(outer, tail)))
        inside = sum(1 for o, i in zip(outer, tail)
                     if o[0] - slack_ns <= i[0] + shift
                     and i[0] + i[1] + shift <= o[0] + o[1] + slack_ns)
        if inside >= need * n:
            return shift
    return None


def trace_shift(obs, spans: Sequence[Span], outer_name: str,
                inner_name: str) -> Optional[int]:
    """``align`` for a traced run: ``obs.trace.host`` against the ring."""
    outer = [(s, d) for n, s, d in obs.trace.host if n == outer_name]
    inner = [(s[1], s[2]) for s in spans if s[0] == inner_name]
    return align(outer, inner)


def deepest_cover(spans: Sequence[Tuple[str, int, int]]) -> List[Tuple[int, int, str]]:
    """``spans`` as ``(name, start, end)``, any threads: disjoint, sorted
    ``(start, end, name)`` pieces, each under the shortest span covering
    it, so the deepest of a nest."""
    edges = sorted({t for _, s, e in spans for t in (s, e)})
    starts = sorted(spans, key=lambda x: x[1])
    out: List[Tuple[int, int, str]] = []
    active: List[Tuple[int, int, str]] = []     # (length, end, name)
    k = 0
    for lo, hi in zip(edges, edges[1:]):
        while k < len(starts) and starts[k][1] <= lo:
            n, s, e = starts[k]
            active.append((e - s, e, n))
            k += 1
        active = [a for a in active if a[1] > lo]
        if active:
            name = min(active)[2]
            if out and out[-1][2] == name and out[-1][1] == lo:
                out[-1] = (out[-1][0], hi, name)
            else:
                out.append((lo, hi, name))
    return out


def idle_by_program_span(trace, spans: Sequence[Span], shift: int,
                         skip: Sequence[str] = ()) -> Dict[str, float]:
    """Idle seconds of chip 0 in the traced window by program span: each
    gap of the device's busy time is cut where the program's spans (moved
    by ``shift``) begin and end, and each piece goes under the deepest span
    covering it, ``(no span)`` where none does. Spans named in ``skip``
    (waits, which are no one's work) do not count."""
    from benchmarks.trace_reduce import gaps

    lo, hi = trace.window
    cover = deepest_cover([(n, s + shift, s + shift + d)
                           for n, s, d, _, _ in spans
                           if n not in skip and d > 0
                           and s + shift < hi and s + shift + d > lo])
    out: Dict[str, float] = {}
    k = 0
    for gs, ge in gaps(trace.busy(0), trace.window):
        at = gs
        while k < len(cover) and cover[k][1] <= gs:
            k += 1
        j = k
        while j < len(cover) and cover[j][0] < ge:
            cs, ce, name = cover[j]
            if cs > at:
                out["(no span)"] = out.get("(no span)", 0.0) + (cs - at) / 1e9
            piece = min(ce, ge) - max(cs, at)
            out[name] = out.get(name, 0.0) + piece / 1e9
            at = min(ce, ge)
            j += 1
        if at < ge:
            out["(no span)"] = out.get("(no span)", 0.0) + (ge - at) / 1e9
    return out
