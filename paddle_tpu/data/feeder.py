"""DataFeeder + device prefetch.

Analog of python/paddle/fluid/data_feeder.py (DataFeeder.feed:167 —
converts a list of per-sample tuples into batched dense arrays) and of
the py_reader/double_buffer device pipeline (operators/reader/
buffered_reader.cc, layers/io.py:478): ``DeviceFeeder`` runs the host
reader in a background thread and keeps N batches in flight on device so
host→HBM transfer overlaps with compute.

``DeviceFeeder(stack_k=K)`` additionally assembles K host batches into
one stacked super-batch ``{name: (K, batch, ...)}`` and transfers it in
ONE sharded put — the feed side of the fused multi-step dispatch
(``Trainer.run_steps`` / ``fit(steps_per_dispatch=K)``): one
host→device transfer and one launch per K optimizer steps instead of K.
"""

from __future__ import annotations

import queue as _queue
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..core import profiler
from ..core.dtypes import convert_dtype


class PipelineMetrics:
    """Input-pipeline stage accounting (thread-safe): per-stage wall
    time and byte counters accumulated by :class:`DeviceFeeder` (fill
    thread: reader / encode / stack / h2d / dispatch-wait) and by
    ``Trainer._put_feed`` on direct-step paths, surfaced through
    :meth:`report` / ``Trainer.pipeline_report()``.

    Stages:

    - ``reader``   — waiting on the host reader for the next batch;
    - ``encode``   — wire-format encode (quantize/cast) of host arrays;
    - ``stack``    — assembling K batches into a fused-dispatch
      super-batch;
    - ``h2d``      — the device put. On the DeviceFeeder fill thread
      this times the COMPLETED transfer (block_until_ready); the
      direct-step paths (``Trainer._put_feed`` / ``put_batch``) record
      submission time only, a lower bound on async backends;
    - ``dispatch`` — the fill thread blocked on a full prefetch queue,
      i.e. waiting for the consumer's dispatches to drain (the
      compute-bound signal).

    ``consumer_starved_s`` is the mirror image: time the training-loop
    thread waited for a batch (the input-bound signal). ``h2d_bytes``
    counts WIRE bytes (what actually crossed the link);
    ``encode_saved_bytes`` accumulates logical-minus-wire so the report
    can state the reduction honestly.

    Two overlap-era attributions (PR 15):

    - ``overlap_hidden_s`` — transfer seconds that ran CONCURRENTLY
      with host work / the consumer's dispatches under the
      :class:`_StagingRing` (the h2d stage keeps the full
      submit→complete transfer wall, so ``h2d_mbps`` still measures
      the link; hidden vs exposed says how much of it the pipeline
      actually waited for);
    - ``cache_hit_bytes`` / ``cache_hits`` — chunks served
      device-to-device from the HBM dataset cache
      (:class:`~paddle_tpu.data.device_cache.DeviceCache`). Cache hits
      touch neither ``h2d_bytes`` nor the h2d clock, so ``h2d_mbps``
      stays an honest LINK estimate that excludes cache-served chunks
      (they would otherwise report an infinite link)."""

    _STAGES = ("reader", "encode", "stack", "h2d", "dispatch")

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self):
        with self._lock:
            self.stage_s = {s: 0.0 for s in self._STAGES}
            self.h2d_bytes = 0
            self.encode_saved_bytes = 0
            self.consumer_starved_s = 0.0
            self.batches = 0
            self.chunks = 0
            self.overlap_hidden_s = 0.0
            self.cache_hit_bytes = 0
            self.cache_hits = 0

    def add(self, stage: str, seconds: float):
        with self._lock:
            self.stage_s[stage] += seconds

    def record_encode(self, seconds: float, logical_nbytes: int,
                      wire_nbytes: int):
        with self._lock:
            self.stage_s["encode"] += seconds
            self.encode_saved_bytes += max(0, logical_nbytes - wire_nbytes)

    def record_h2d(self, nbytes: int, seconds: float,
                   exposed_s: Optional[float] = None):
        """One completed transfer: ``seconds`` is the submit→complete
        wall. ``exposed_s`` (staging-ring path) is how long the fill
        thread actually stalled for it — the rest ran hidden under
        other work and accumulates as ``overlap_hidden_s``. ``None``
        (the blocking put / direct-step paths) means fully exposed."""
        with self._lock:
            self.stage_s["h2d"] += seconds
            if exposed_s is not None:
                self.overlap_hidden_s += max(0.0, seconds - exposed_s)
            self.h2d_bytes += nbytes
            self.chunks += 1

    def record_cache_hit(self, nbytes: int):
        """A chunk served device-to-device from the HBM dataset cache:
        ``nbytes`` of wire data did NOT cross the link. Deliberately
        touches neither ``h2d_bytes`` nor the h2d clock — see the class
        docstring's honesty note on ``h2d_mbps``."""
        with self._lock:
            self.cache_hit_bytes += nbytes
            self.cache_hits += 1

    def record_batch(self, reader_seconds: float):
        with self._lock:
            self.stage_s["reader"] += reader_seconds
            self.batches += 1

    def record_starved(self, seconds: float):
        with self._lock:
            self.consumer_starved_s += seconds

    def telemetry_families(self, inst: str = "0") -> list:
        """The same accumulators as registry metric families under the
        ``paddle_tpu_feeder_*`` names (scrape-time: the Trainer's
        telemetry collector calls this, so the exported series can
        never disagree with :meth:`report`)."""
        from ..telemetry.registry import counter_family

        with self._lock:
            stages = dict(self.stage_s)
            h2d_bytes, saved = self.h2d_bytes, self.encode_saved_bytes
            starved = self.consumer_starved_s
            batches, chunks = self.batches, self.chunks
            hidden = self.overlap_hidden_s
            cache_b, cache_n = self.cache_hit_bytes, self.cache_hits
        labels = {"inst": inst}
        return [
            counter_family(
                "paddle_tpu_feeder_stage_seconds_total",
                "Input-pipeline seconds per stage "
                "(reader/encode/stack/h2d/dispatch wait)",
                [({**labels, "stage": s}, round(v, 6))
                 for s, v in sorted(stages.items())]),
            counter_family(
                "paddle_tpu_feeder_batches_total",
                "Host batches pulled from the reader", [(labels, batches)]),
            counter_family(
                "paddle_tpu_feeder_chunks_total",
                "Device transfers (fused chunks count once)",
                [(labels, chunks)]),
            counter_family(
                "paddle_tpu_feeder_h2d_bytes_total",
                "Wire bytes moved host-to-device", [(labels, h2d_bytes)]),
            counter_family(
                "paddle_tpu_feeder_encode_saved_bytes_total",
                "Logical-minus-wire bytes the feed wire encode saved",
                [(labels, saved)]),
            counter_family(
                "paddle_tpu_feeder_consumer_starved_seconds_total",
                "Training-loop seconds spent waiting for input",
                [(labels, round(starved, 6))]),
            counter_family(
                "paddle_tpu_feeder_overlap_hidden_seconds_total",
                "Transfer seconds hidden under host work / compute by "
                "the double-buffered staging ring",
                [(labels, round(hidden, 6))]),
            counter_family(
                "paddle_tpu_feeder_cache_hit_bytes_total",
                "Wire bytes served device-to-device from the HBM "
                "dataset cache (never crossed the host link)",
                [(labels, cache_b)]),
            counter_family(
                "paddle_tpu_feeder_cache_hits_total",
                "Chunks served from the HBM dataset cache",
                [(labels, cache_n)]),
        ]

    def report(self) -> Dict[str, Any]:
        """Per-stage attribution + an effective-link estimate:
        ``h2d_mbps`` is wire bytes over transfer wall time — an honest
        LINK estimate that excludes cache-served chunks (they add
        neither bytes nor h2d seconds); ``overlap_hidden_s`` /
        ``h2d_exposed_s`` split the transfer wall into the part the
        staging ring hid under other work vs the part the pipeline
        stalled for; ``bottleneck`` names the stage with the most
        accumulated time, and ``input_bound`` says whether the training
        loop starved for data more than the fill thread waited on it."""
        with self._lock:
            stages = dict(self.stage_s)
            h2d_bytes = self.h2d_bytes
            saved = self.encode_saved_bytes
            starved = self.consumer_starved_s
            batches, chunks = self.batches, self.chunks
            hidden = self.overlap_hidden_s
            cache_b, cache_n = self.cache_hit_bytes, self.cache_hits
        logical = h2d_bytes + saved
        h2d_s = stages["h2d"]
        return {
            "stages_s": {k: round(v, 6) for k, v in stages.items()},
            "h2d_bytes": int(h2d_bytes),
            "logical_bytes": int(logical),
            "wire_reduction": (round(logical / h2d_bytes, 3)
                               if h2d_bytes else None),
            "h2d_mbps": (round(h2d_bytes / 1e6 / h2d_s, 2)
                         if h2d_s > 0 and h2d_bytes else None),
            "overlap_hidden_s": round(hidden, 6),
            "h2d_exposed_s": round(max(0.0, h2d_s - hidden), 6),
            "cache_hit_bytes": int(cache_b),
            "cache_hits": cache_n,
            "batches": batches,
            "chunks": chunks,
            "consumer_starved_s": round(starved, 6),
            "bottleneck": max(stages, key=stages.get) if any(
                v > 0 for v in stages.values()) else None,
            "input_bound": starved > stages["dispatch"],
        }


class DataFeeder:
    """Convert reader samples (tuples) into a named feed dict of batched
    numpy arrays (DataFeeder.feed analog, data_feeder.py:167)."""

    def __init__(self, feed_list: Sequence[str], dtypes: Optional[Sequence[Any]] = None):
        self.feed_list = list(feed_list)
        self.dtypes = list(dtypes) if dtypes is not None else [None] * len(self.feed_list)

    def feed(self, samples: Sequence[Tuple]) -> Dict[str, np.ndarray]:
        cols = list(zip(*samples))
        if len(cols) != len(self.feed_list):
            raise ValueError(
                f"sample arity {len(cols)} != feed_list arity {len(self.feed_list)}")
        out = {}
        for name, dt, col in zip(self.feed_list, self.dtypes, cols):
            arr = np.stack([np.asarray(v) for v in col])
            if dt is not None:
                arr = arr.astype(np.dtype(convert_dtype(dt).name))
            out[name] = arr
        return out


def stack_batches(bufs: Sequence[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    """Stack K same-shape feed dicts into one ``{name: (K, ...)}``
    super-batch (the fused-dispatch super-batch layout)."""
    return {k: np.stack([np.asarray(b[k]) for b in bufs]) for k in bufs[0]}


def host_feed_nbytes(feed: Dict[str, Any]) -> int:
    """Bytes of the HOST arrays in a feed dict — what a device put of it
    moves across the link (device-resident arrays count zero: they are
    already there)."""
    total = 0
    for v in feed.values():
        if isinstance(v, jax.Array):
            continue
        total += np.asarray(v).nbytes
    return total


def _stackable(a: Dict[str, np.ndarray], b: Dict[str, np.ndarray]) -> bool:
    """Two batches can share a super-batch: same keys, shapes, dtypes
    (a short final reader batch must not poison the stack)."""
    if a.keys() != b.keys():
        return False
    for k in a:
        va, vb = np.asarray(a[k]), np.asarray(b[k])
        if va.shape != vb.shape or va.dtype != vb.dtype:
            return False
    return True


def _host_chunks(batches: Iterator[Dict[str, np.ndarray]], k: int,
                 metrics: Optional[PipelineMetrics] = None):
    """The one chunking state machine both feed paths share: yields
    ``(n, host_feed)`` — full K-chunks stacked (``n == k``),
    remainder/odd-shape batches singly (``n == 1``, unstacked) so they
    fall through to the compiled single-step function with no
    fused-program retrace. ``metrics`` attributes the stack time."""
    buf: List[Dict[str, np.ndarray]] = []
    for b in batches:
        if buf and not _stackable(buf[0], b):
            for s in buf:
                yield 1, s
            buf = []
        buf.append(b)
        if len(buf) == k:
            t0 = time.perf_counter()
            stacked = stack_batches(buf)
            if metrics is not None:
                metrics.add("stack", time.perf_counter() - t0)
            yield k, stacked
            buf = []
    for s in buf:
        yield 1, s


def iter_chunked(batches: Iterator[Dict[str, np.ndarray]], k: int,
                 put_fn: Callable, put_stacked_fn: Callable):
    """Synchronous chunker (the no-prefetch path of
    ``fit(steps_per_dispatch=K)``): ``_host_chunks`` plus the device
    put, yielding ``(n, device_feed)``."""
    for n, hb in _host_chunks(batches, k):
        yield n, (put_stacked_fn(hb) if n > 1 else put_fn(hb))


class _StagingRing:
    """Depth-bounded asynchronous h2d staging — the device-side half of
    the double_buffer analog. ``submit`` dispatches the put and returns
    immediately, so the fill thread reads/encodes/stacks chunk N+1
    while chunk N's transfer is still in flight; a waiter thread waits
    each transfer to completion in submission order (the device-event
    wait — ``jax.block_until_ready``, not a wall-clock of the submit)
    and only then delivers the chunk downstream, so a consumer never
    dispatches on a half-arrived batch and the recorded h2d time is the
    transfer's true submit→complete wall.

    At most ``depth`` transfers are in flight: the fill thread blocks
    in ``submit`` only when the ring is full. That stall (plus the
    submit call itself) is the EXPOSED transfer time; the rest of each
    transfer ran hidden under host work and the consumer's dispatches
    and accumulates as ``PipelineMetrics.overlap_hidden_s``.

    Donation-safe by construction: staged buffers are feed arrays, and
    the step programs never donate feeds — only the training carry
    (params/opt_state/state/loss-scale) is donated, so a buffer parked
    in the ring can never be aliased away under an in-flight transfer.

    ``wait_fn(dev, t_submit)`` is the completion wait;
    ``testing.faults.slow_h2d`` substitutes a throttled one to make a
    slow host→device link deterministic in tests and bench."""

    _END = object()

    def __init__(self, depth: int, deliver: Callable, stop: threading.Event,
                 metrics: Optional[PipelineMetrics] = None,
                 wait_fn: Optional[Callable] = None, journal=None,
                 on_error: Optional[Callable] = None):
        self.depth = max(1, int(depth))
        self._deliver = deliver      # (dev, n, span) -> bool (False: stop)
        self._stop = stop
        self._metrics = metrics
        self._wait_fn = wait_fn or (
            lambda dev, t_submit: jax.block_until_ready(dev))
        self._journal = journal
        self._on_error = on_error
        self._sem = threading.Semaphore(self.depth)
        self._q: _queue.Queue = _queue.Queue()
        self._lock = threading.Lock()
        self._stall_s = 0.0          # fill-thread seconds blocked here
        self._thread = threading.Thread(target=self._drain, daemon=True)
        self._thread.start()

    def _take_stall(self) -> float:
        with self._lock:
            s, self._stall_s = self._stall_s, 0.0
            return s

    def submit(self, n: int, host_feed, putter: Callable) -> bool:
        """Dispatch one chunk's put into the ring. Returns False when
        the stop flag fired (the chunk was not submitted)."""
        t_a = time.perf_counter()
        while not self._sem.acquire(timeout=0.1):
            if self._stop.is_set():
                return False
        stall = time.perf_counter() - t_a
        span = self._journal.new_span() if self._journal is not None else None
        nbytes = host_feed_nbytes(host_feed)
        t0 = time.perf_counter()
        dev = putter(host_feed)
        t1 = time.perf_counter()
        with self._lock:
            # the submit call is exposed too: the fill thread paid it
            self._stall_s += stall + (t1 - t0)
        self._q.put((dev, n, span, t0, nbytes))
        return True

    def finish(self):
        """Fill-thread end-of-stream: let in-flight transfers deliver,
        then return (immediately once the stop flag fires — deliveries
        can no longer land on a closed consumer)."""
        self._q.put(self._END)
        while self._thread.is_alive():
            self._thread.join(timeout=0.1)
            if self._stop.is_set():
                return

    def _drain(self):
        while True:
            try:
                item = self._q.get(timeout=0.1)
            except _queue.Empty:
                if self._stop.is_set():
                    return
                continue
            if item is self._END:
                return
            dev, n, span, t0, nbytes = item
            try:
                self._wait_fn(dev, t0)
            except BaseException as e:  # surfaced on the consumer side
                if self._on_error is not None:
                    self._on_error(e)
                # fire the stop flag: a dead waiter releases no more
                # ring slots, so a fill thread parked in submit() (and
                # the consumer waiting on deliveries) must be unblocked
                # — the recorded error then propagates at __next__
                self._stop.set()
                self._sem.release()
                return
            seconds = time.perf_counter() - t0
            if self._metrics is not None:
                self._metrics.record_h2d(nbytes, seconds,
                                         exposed_s=self._take_stall())
            if self._journal is not None:
                self._journal.emit("feeder.fill", span=span, num_steps=n,
                                   nbytes=nbytes, put_s=round(seconds, 6))
            ok = self._deliver(dev, n, span)
            self._sem.release()
            if not ok:
                return


class DeviceFeeder:
    """Double-buffered host→device prefetch (py_reader + double_buffer
    analog). Wraps an iterator of feed dicts; ``__iter__`` yields dicts
    of on-device arrays while the next batches transfer in the
    background.

    With ``stack_k=K > 1`` the fill thread stacks K host batches into a
    super-batch, transfers it with ``put_stacked_fn`` in one put, and
    the iterator yields ``(n, feed)`` pairs — ``n == K`` for full
    chunks, ``n == 1`` (unstacked, via ``put_fn``) for remainder or
    shape-mismatched batches.

    The fill thread is CANCELLABLE: abandoning the iterator (break /
    exception / gc) or calling :meth:`close` unblocks it even when it is
    parked on a full queue holding device buffers — the old leak where a
    daemon thread pinned HBM until process exit.

    A reader/transfer exception on the fill thread PROPAGATES to the
    consumer: already-transferred batches drain first, then the original
    exception (fill-thread traceback attached) is re-raised at
    ``__next__`` — never a bare end-of-iteration that silently truncates
    the epoch. A fill thread that dies without delivering its END
    sentinel is detected by a liveness probe instead of hanging the
    consumer.

    ``encode_fn`` (e.g. ``FeedWire.encode``) runs ON THE FILL THREAD,
    per batch, BEFORE stacking — wire-format encode and per-field dtype
    conversion never touch the training-loop thread, and K-chunk
    stacking operates on the already-shrunk wire arrays. ``metrics``
    (a :class:`PipelineMetrics`) attributes per-stage time and wire
    bytes: reader wait, encode, stack, h2d put, and the
    fill-thread-blocked-on-consumer dispatch wait; pair it with a
    ``put_fn`` that does not itself record (``Trainer._put_feed``
    with ``record=False``) or the h2d stage double-counts.

    With ``overlap_depth >= 2`` (the default) and metrics attached, the
    put goes through a :class:`_StagingRing` instead of blocking the
    fill thread on ``block_until_ready``: transfers run up to
    ``overlap_depth`` deep while the fill thread keeps
    reading/encoding/stacking, completion time is recorded via a
    device-event wait on a waiter thread (the honest ``h2d_mbps``),
    and the hidden-vs-exposed split lands in
    ``PipelineMetrics.overlap_hidden_s``. ``overlap_depth=1`` restores
    the old blocking put (the bench A/B's "blocking" arm). ``wait_fn``
    overrides the completion wait — ``testing.faults.slow_h2d``
    simulates a slow link deterministically through it.

    ``journal`` (a :class:`paddle_tpu.telemetry.RunJournal`) correlates
    the pipeline with the dispatches it feeds: the fill thread mints a
    span id per chunk and emits a ``feeder.fill`` event when the
    transfer lands; after the iterator yields an item,
    :attr:`last_span` holds that item's span (exact for the serial
    single-consumer iteration contract) so the consumer can hand the
    SAME span to ``trainer.step``/``run_steps`` — fill and dispatch of
    one chunk then share one trace id end to end (``fit`` does this)."""

    def __init__(self, batches: Callable[[], Iterator[Dict[str, np.ndarray]]],
                 put_fn: Optional[Callable[[Dict[str, np.ndarray]], Dict[str, jax.Array]]] = None,
                 capacity: int = 2, stack_k: int = 1,
                 put_stacked_fn: Optional[Callable] = None,
                 encode_fn: Optional[Callable] = None,
                 metrics: Optional[PipelineMetrics] = None,
                 logical_nbytes_fn: Optional[Callable] = None,
                 journal=None, overlap_depth: int = 2,
                 wait_fn: Optional[Callable] = None):
        self.batches = batches
        self.put_fn = put_fn or (lambda d: jax.device_put(d))
        self.put_stacked_fn = put_stacked_fn or self.put_fn
        self.capacity = capacity
        self.stack_k = max(1, int(stack_k))
        self.encode_fn = encode_fn
        self.metrics = metrics
        self.journal = journal
        self.overlap_depth = max(1, int(overlap_depth))
        self.wait_fn = wait_fn
        self.last_span: Optional[str] = None
        # spec-aware logical-byte counter (FeedWire.logical_nbytes):
        # counts already-wire-dtype reader output at its DECODED width
        # so wire_reduction reports the true link saving
        self.logical_nbytes_fn = logical_nbytes_fn or host_feed_nbytes
        self._stops: List[threading.Event] = []
        self._threads: List[threading.Thread] = []

    def pipeline_report(self) -> Optional[Dict[str, Any]]:
        """The accumulated :meth:`PipelineMetrics.report`, or None when
        the feeder was built without metrics."""
        return self.metrics.report() if self.metrics is not None else None

    def _instrumented_batches(self) -> Iterator[Dict[str, np.ndarray]]:
        """Fill-thread source: times the reader wait per batch and runs
        the wire encode (host numpy) before chunk assembly."""
        m, enc = self.metrics, self.encode_fn
        it = iter(self.batches())
        while True:
            t0 = time.perf_counter()
            try:
                b = next(it)
            except StopIteration:
                return
            if m is not None:
                m.record_batch(time.perf_counter() - t0)
            if enc is not None:
                t0 = time.perf_counter()
                logical = self.logical_nbytes_fn(b) if m is not None else 0
                b = enc(b)
                if m is not None:
                    m.record_encode(time.perf_counter() - t0, logical,
                                    host_feed_nbytes(b))
            yield b

    def _timed_put(self, fn, host_feed):
        if self.metrics is None and self.wait_fn is None:
            return fn(host_feed)
        nbytes = host_feed_nbytes(host_feed)
        t0 = time.perf_counter()
        out = fn(host_feed)
        if self.wait_fn is not None:
            # injected completion wait (testing.faults.slow_h2d): the
            # blocking arm of the overlap A/B pays the same simulated
            # link the staging ring does
            self.wait_fn(out, t0)
            if self.metrics is not None:
                self.metrics.record_h2d(nbytes,
                                        time.perf_counter() - t0)
            return out
        # the BLOCKING put (overlap_depth=1 only): wait for the
        # transfer inline so h2d_mbps measures the link, not the
        # submission. It serializes the fill thread's host work behind
        # each transfer and caps in-flight transfers at one — the
        # default path is the _StagingRing, which records the same
        # honest completion time via a device-event wait on a waiter
        # thread while transfers pipeline overlap_depth deep.
        jax.block_until_ready(out)
        self.metrics.record_h2d(nbytes, time.perf_counter() - t0)
        return out

    def close(self):
        """Cancel every live fill thread (idempotent). Threads parked on
        a full queue wake on the stop flag and exit, dropping their
        device-buffer references."""
        for ev in self._stops:
            ev.set()
        for t in self._threads:
            t.join(timeout=5.0)
        self._threads = [t for t in self._threads if t.is_alive()]

    def __iter__(self):
        q: _queue.Queue = _queue.Queue(maxsize=self.capacity)
        END = object()
        err: List[BaseException] = []
        stop = threading.Event()
        self._stops.append(stop)

        metrics = self.metrics

        def put(item, timed: bool = True) -> bool:
            # bounded-wait put: a consumer that stopped consuming must
            # not strand this thread (and its device buffers) forever.
            # Time blocked here is the DISPATCH WAIT — the consumer's
            # device dispatches are what drains the queue.
            t0 = time.perf_counter()
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    if timed and metrics is not None:
                        metrics.add("dispatch", time.perf_counter() - t0)
                    return True
                except _queue.Full:
                    continue
            return False

        journal = self.journal

        def fill_event(n, hb, putter):
            """One chunk's transfer + its ``feeder.fill`` journal event
            (span minted HERE, on the fill thread, at chunk-creation
            time — the consumer re-uses it for the dispatch)."""
            if journal is None:
                return putter(hb), None
            span = journal.new_span()
            t0 = time.perf_counter()
            dev = putter(hb)
            journal.emit("feeder.fill", span=span, num_steps=n,
                         nbytes=host_feed_nbytes(hb),
                         put_s=round(time.perf_counter() - t0, 6))
            return dev, span

        # the staging ring replaces the blocking put when overlap is on
        # and there is something for it to do (metrics to keep honest,
        # or an injected wait_fn to obey); the legacy inline put remains
        # the overlap_depth=1 path and the metrics-less fast path
        ring = None
        if self.overlap_depth >= 2 and (metrics is not None
                                        or self.wait_fn is not None):
            def deliver(dev, n, span):
                payload = (n, dev) if self.stack_k > 1 else dev
                return put((payload, span))

            ring = _StagingRing(self.overlap_depth, deliver, stop,
                                metrics=metrics, wait_fn=self.wait_fn,
                                journal=journal, on_error=err.append)
            self._threads.append(ring._thread)

        def fill():
            try:
                chunks = (_host_chunks(self._instrumented_batches(),
                                       self.stack_k, metrics=metrics)
                          if self.stack_k > 1
                          else ((1, b) for b in
                                self._instrumented_batches()))
                for n, hb in chunks:
                    if stop.is_set():
                        return
                    putter = (lambda b, _n=n: (
                        self.put_stacked_fn if _n > 1 else self.put_fn)(b))
                    if ring is not None:
                        if not ring.submit(n, hb, putter):
                            return
                        continue
                    dev, span = fill_event(
                        n, hb, lambda b, _p=putter: self._timed_put(_p, b))
                    payload = (n, dev) if self.stack_k > 1 else dev
                    if not put((payload, span)):
                        return
            except BaseException as e:  # surfaced on the consumer side
                err.append(e)
            finally:
                # END must trail every in-flight staged transfer, or the
                # consumer would see end-of-epoch with chunks undelivered
                if ring is not None:
                    ring.finish()
                # END delivery is shutdown, not dispatch wait — untimed
                if not put(END, timed=False):
                    # stop was set (close() possibly from ANOTHER thread
                    # than the consumer): a consumer still parked in
                    # q.get() must not hang — if it is parked, the queue
                    # is empty and this delivery succeeds
                    try:
                        q.put_nowait(END)
                    except _queue.Full:
                        pass

        t = threading.Thread(target=fill, daemon=True)
        t.start()
        self._threads.append(t)
        try:
            while True:
                t_wait = time.perf_counter()
                try:
                    # the wait record_starved times, as a span: a
                    # starved gap on the device trace's clock
                    with profiler.record_event("fit.next_batch"):
                        item = q.get(timeout=0.5)
                    # starvation accounting: the training loop waited
                    # this long for input (END arrival is shutdown, not
                    # starvation — skip it below)
                    if metrics is not None and item is not END:
                        metrics.record_starved(time.perf_counter() - t_wait)
                except _queue.Empty:
                    if metrics is not None:
                        metrics.record_starved(time.perf_counter() - t_wait)
                    # liveness check: a fill thread that died without
                    # managing to enqueue END (its sentinel put lost a
                    # race with close()) must not hang the consumer —
                    # and its reader error must still surface
                    if not t.is_alive():
                        # the thread may have enqueued its final batches
                        # (and END) between our timeout and this check —
                        # drain them before concluding, or the race
                        # silently truncates the epoch
                        while True:
                            try:
                                item = q.get_nowait()
                            except _queue.Empty:
                                break
                            if item is END:
                                break
                            payload, self.last_span = item
                            yield payload
                        if err:
                            raise err[0]
                        return
                    continue
                if item is END:
                    if err:
                        # re-raise the READER's exception at __next__
                        # with its original fill-thread traceback — a
                        # reader crash must abort the epoch loudly, not
                        # truncate it to a silent StopIteration
                        raise err[0]
                    return
                payload, self.last_span = item
                yield payload
        finally:
            # break / exception / generator gc: release the fill thread
            stop.set()
