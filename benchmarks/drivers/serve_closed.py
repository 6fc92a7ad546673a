"""Closed loop: ``callers`` threads each resubmit a full-bucket request as
soon as the last one returns (offline batch generation). The window opens
when the callers start on a warm, idle server; at ``--seconds`` they stop
submitting, and the window closes when the last request returns, so every
request counted is whole.

``serve_tokens_per_s`` is a median of readings, not one quotient: the times
at which requests returned, in order, give one reading of the seconds a
request takes for every run of ``workers`` consecutive returns (the span
over ``workers`` returns, divided by ``workers``, so that workers that
finish in pairs read the same as workers that alternate), and the rate is
the tokens of a request over the median reading. Some thirty readings a
run: one stall of the host moves one of them, where it moved the quotient
of tokens and window by its whole length. That quotient is kept as
``window_tokens_per_s`` for a per-layer metric; the two part when something
stalls.

Traffic file: the keys of ``drivers/serving.py`` and ``callers``.
"""

from __future__ import annotations

import threading
import time
from typing import List

import numpy as np

from benchmarks.drivers import serving
from benchmarks.harness import Observed, Run, window_bytes
from benchmarks.tracing import Tracer, span


def seconds_per_request(returned: List[float], workers: int) -> List[float]:
    """One reading for every run of ``workers`` consecutive returns: the
    span from return ``i`` to return ``i + workers``, over ``workers``."""
    c = np.sort(np.asarray(returned, dtype=np.float64))
    w = max(1, int(workers))
    return ((c[w:] - c[:-w]) / w).tolist()


def run(run: Run) -> Observed:
    t = run.cell.traffic
    with serving.served(run) as (server, prompts):
        tracer = Tracer(run.cell.chips) if run.trace else None
        requests, lock = [], threading.Lock()
        run.compiles.mark()
        t0 = time.perf_counter()
        deadline = t0 + run.seconds
        run.log("window opens")

        def caller(k: int) -> None:
            i = k
            while time.perf_counter() < deadline:
                req = serving.Request(index=i, prompt=i % len(prompts),
                                      due=time.perf_counter())
                with span("submit"):
                    try:
                        req.pending = server.submit(
                            {"prompt_ids": prompts[req.prompt]})
                    except Exception as e:
                        req.error = f"{type(e).__name__}: {e}"[:200]
                req.submitted = time.perf_counter()
                if req.pending is not None:
                    with span("wait_result"):
                        try:
                            req.pending.result(timeout=300)
                        except Exception:
                            pass    # collect() records it
                    serving.collect(req)
                req.done = time.perf_counter()
                with lock:
                    requests.append(req)
                i += t["callers"]

        threads = [threading.Thread(target=caller, args=(k,), daemon=True)
                   for k in range(t["callers"])]
        for th in threads:
            th.start()
        trace = None
        if tracer:          # the last seconds before the callers stop
            time.sleep(max(0.0, deadline - run.trace_seconds
                           - time.perf_counter()))
            tracer.start()
            time.sleep(max(0.0, deadline - time.perf_counter()))
            trace = tracer.stop()
        for th in threads:
            th.join()
        t_end = max(r.done for r in requests)
        window = t_end - t0
        compiles = run.compiles.since_mark()
        run.log(f"window closed: {len(requests)} requests in {window:.2f} s")
        peak = window_bytes(run.devices)
        verdict = serving.verdict(run, server, prompts, requests,
                                  compiles)
        run.log(f"verdict: {verdict}")

    ok = [r for r in requests if r.ok]
    per_request = t["rows"] * t["new_tokens"]
    readings = seconds_per_request([r.done for r in ok], t["workers"])
    # too few returns for a reading (a toy run): the plain quotient
    typical = float(np.median(readings)) if readings else window / max(1, len(ok))
    verdict["seconds_per_request"] = [round(x, 4) for x in readings]
    return Observed(
        correct=verdict["ok"] and len(ok) == len(requests),
        attempted=len(requests), failed=len(requests) - len(ok),
        values={"serve_tokens_per_s": per_request / typical,
                "window_tokens_per_s": len(ok) * per_request / window,
                "setup_s": t0 - run.t_start, "window_s": window,
                "compiles_in_window": compiles, "peak_bytes_window": peak,
                "compiles_since_warmup":
                    verdict["server"]["compiles_since_warmup"],
                "rows": t["rows"], "prompt": t["prompt"],
                "new_tokens": t["new_tokens"]},
        series={"latency_ms": [(r.done - r.due) * 1e3 for r in ok]},
        trace=trace, notes=verdict)
