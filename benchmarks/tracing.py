"""The benchmark's own host spans and the traced part of a window.

Spans are ``jax.profiler.TraceAnnotation`` under the prefix ``bench.``, put
around the calls into each layer from the benchmark's files; they land in
the profiler's trace on the device's clock, where ``trace_reduce``
attributes idle gaps to them. Spans inside the program are a later issue.
"""

from __future__ import annotations

import shutil
import tempfile
from typing import Optional

from benchmarks import trace_reduce


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name)


class Tracer:
    """Traces from ``start()`` to ``stop()``; both on the same thread. The
    trace is written under the temporary directory (``TMPDIR``), reduced,
    and removed."""

    def __init__(self, chips: int):
        self.chips = chips
        self.started = False
        self._dir: Optional[str] = None
        self._window = None

    def start(self) -> None:
        import jax

        self._dir = tempfile.mkdtemp(prefix="bench_trace_")
        # TraceAnnotation spans need the host tracer; Python's own call
        # stacks are not read here and cost the host most, so they are off
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self._dir, profiler_options=options)
        self._window = span("window")
        self._window.__enter__()
        self.started = True

    def stop(self) -> trace_reduce.Trace:
        import jax

        self._window.__exit__(None, None, None)
        try:
            jax.profiler.stop_trace()
            return trace_reduce.read_xplane(
                trace_reduce.find_xplane(self._dir), self.chips)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
