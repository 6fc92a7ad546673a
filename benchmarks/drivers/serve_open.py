"""Open loop: single-prompt requests on a Poisson schedule at the fixed rate
in the traffic file (independent users). One thread submits each request
when it is due and records how late it was; one thread notes when each
result is there. Latency runs from the time a request was *due*, so a stall
counts against every request it delays. A request that fails, is refused or
times out counts as the window's length.

The schedule is a Poisson process conditioned on its count: exactly
``round(rate_per_s * seconds)`` arrivals, uniform over the window, drawn
from the traffic file's ``schedule_seed`` and then turned round the window
(``(t + phase) mod seconds``) by a phase drawn from ``--seed``. Every run of
a cell offers the same gaps between arrivals, so the same bursts: the tail
of a queue follows the bursts of the draw far more than anything the program
does. Where in the window they fall, and so how they meet the server's
dispatches, follows ``--seed``: left fixed, the arrivals and the dispatches
mesh like two gears, the runs of one build agree to a thousandth, and a
millisecond more or less in a dispatch moves the tail by a hundredth. The
phase lets the runs of one build show that hundredth.

The judged tail is a median of readings: the requests, in the order they
were due, are cut into ``latency_blocks`` equal blocks (1: the whole window)
and ``request_latency_p95_ms`` is the median over the blocks of each block's
95th percentile, so that one stall of the host moves one reading.

Traffic file: the keys of ``drivers/serving.py``, ``rate_per_s``,
``schedule_seed``, ``latency_blocks`` and ``drain_timeout_s``.
"""

from __future__ import annotations

import threading
import time
from typing import List

import numpy as np

from benchmarks.drivers import serving
from benchmarks.harness import Observed, Run, window_bytes
from benchmarks.tracing import Tracer, span

POLL_S = 0.001


def schedule(draw_seed: int, rate_per_s: float, seconds: float,
             phase_seed: int) -> np.ndarray:
    """Arrival times in ``[0, seconds)``, a pure function of the two seeds:
    a Poisson process of the given rate conditioned on its count, which is
    ``round(rate_per_s * seconds)`` uniform draws from ``draw_seed``, turned
    round the window by a phase from ``phase_seed``."""
    n = max(1, int(round(rate_per_s * seconds)))
    times = np.random.RandomState(draw_seed + 101).uniform(0.0, seconds, n)
    phase = np.random.RandomState(phase_seed + 211).uniform(0.0, seconds)
    return np.sort((times + phase) % seconds)


def offer(server, prompts, due: np.ndarray, t0: float,
          drain_timeout_s: float) -> List[serving.Request]:
    """Submit request ``i`` at ``t0 + due[i]`` whatever happened to the
    others; return when every result is in (or the drain times out)."""
    requests = [serving.Request(index=i, prompt=i % len(prompts),
                                due=t0 + float(d)) for i, d in enumerate(due)]
    outstanding: List[serving.Request] = []
    lock = threading.Lock()
    submitted_all = threading.Event()

    def submitter() -> None:
        for req in requests:
            wait = req.due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            req.submitted = time.perf_counter()
            with span("submit"):
                try:
                    req.pending = server.submit(
                        {"prompt_ids": prompts[req.prompt]})
                except Exception as e:  # refused: a failed request
                    req.error = f"{type(e).__name__}: {e}"[:200]
                    req.done = time.perf_counter()
                    continue
            with lock:
                outstanding.append(req)
        submitted_all.set()

    def collector() -> None:
        give_up = None
        state = None                # the open span: waiting, or no request
        while True:
            with lock:
                waiting = list(outstanding)
            want = "wait_result" if waiting else "no_request"
            if state is None or state[0] != want:
                if state is not None:
                    state[1].__exit__(None, None, None)
                state = (want, span(want))
                state[1].__enter__()
            now = time.perf_counter()
            finished = [r for r in waiting if r.pending.done()]
            for r in finished:
                r.done = now
                serving.collect(r)
            if finished:
                with lock:
                    for r in finished:
                        outstanding.remove(r)
            if submitted_all.is_set():
                if not waiting:
                    break
                give_up = give_up or now + drain_timeout_s
                if now > give_up:
                    for r in waiting:
                        r.error, r.pending = "no result when the drain timed out", None
                    break
            time.sleep(POLL_S)
        state[1].__exit__(None, None, None)

    threads = [threading.Thread(target=f, daemon=True)
               for f in (submitter, collector)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return requests


def latencies_ms(requests, window_s: float) -> np.ndarray:
    return np.asarray([(r.done - r.due) * 1e3 if r.ok else window_s * 1e3
                       for r in requests])


def block_percentile(lat: np.ndarray, q: float, blocks: int) -> float:
    """Median over ``blocks`` equal cuts of ``lat`` (requests in the order
    they were due) of each cut's ``q``-th percentile."""
    cuts = np.array_split(lat, max(1, min(int(blocks), len(lat))))
    return float(np.median([np.percentile(c, q) for c in cuts]))


def run(run: Run) -> Observed:
    t = run.cell.traffic
    due = schedule(t["schedule_seed"], t["rate_per_s"], run.seconds,
                   run.seed)
    with serving.served(run) as (server, prompts):
        tracer = Tracer(run.cell.chips) if run.trace else None
        run.compiles.mark()
        t0 = time.perf_counter() + 0.05
        run.log(f"window opens: {len(due)} requests at {t['rate_per_s']}/s")
        result: list = []
        worker = threading.Thread(
            target=lambda: result.append(offer(server, prompts, due, t0,
                                               t["drain_timeout_s"])),
            daemon=True)
        worker.start()
        trace = None
        if tracer:          # the last seconds of the arrivals
            time.sleep(max(0.0, t0 + run.seconds - run.trace_seconds
                           - time.perf_counter()))
            tracer.start()
            time.sleep(max(0.0, t0 + run.seconds - time.perf_counter()))
            trace = tracer.stop()
        worker.join()
        requests = result[0]
        compiles = run.compiles.since_mark()
        peak = window_bytes(run.devices)
        verdict = serving.verdict(run, server, prompts, requests,
                                  compiles)
        run.log(f"verdict: {verdict}")

    lat = latencies_ms(requests, run.seconds)
    ok = [r for r in requests if r.ok]
    in_window = sum(1 for r in ok if r.done <= t0 + run.seconds)
    late = [(r.submitted - r.due) * 1e3 for r in requests]
    return Observed(
        correct=verdict["ok"] and len(ok) == len(requests),
        attempted=len(requests), failed=len(requests) - len(ok),
        values={"request_latency_p50_ms": float(np.percentile(lat, 50)),
                "request_latency_p95_ms":
                    block_percentile(lat, 95, t["latency_blocks"]),
                "setup_s": t0 - run.t_start, "window_s": run.seconds,
                "completed_tokens_per_s":
                    in_window * t["rows"] * t["new_tokens"] / run.seconds,
                "compiles_in_window": compiles, "peak_bytes_window": peak,
                "compiles_since_warmup":
                    verdict["server"]["compiles_since_warmup"],
                "coalesced_requests": verdict["server"]["coalesced_requests"],
                "coalesced_batches": verdict["server"]["coalesced_batches"],
                "largest_bucket": max(t["buckets"])},
        series={"latency_ms": lat.tolist(), "late_ms": late},
        trace=trace, notes=verdict)
