"""What the two serve drivers share: export, load and start the server,
time requests on the benchmark's own clock, hold served rows to the
reference.

Traffic file keys read here: ``prompt``, ``new_tokens``, ``buckets``,
``workers``, ``max_wait_ms``, ``queue_size``, ``distinct_prompts``,
``rows`` (rows per request).
"""

from __future__ import annotations

import contextlib
import dataclasses
import tempfile
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks.harness import Run


@dataclasses.dataclass
class Request:
    """One request as the benchmark saw it; times are ``perf_counter``."""
    index: int
    prompt: int                     # which of the distinct prompts
    due: float
    submitted: Optional[float] = None
    done: Optional[float] = None
    ids: Optional[np.ndarray] = None
    error: Optional[str] = None
    pending: Any = None

    @property
    def ok(self) -> bool:
        return self.ids is not None and self.error is None


@contextlib.contextmanager
def served(run: Run):
    """``(server, prompts)``: the generator exported with the traffic's
    buckets into a temporary directory (outside the checkout, removed on
    exit), loaded, compiled ahead of time, served by in-process workers
    and warmed at every bucket. The server holds the only copy of the
    weights on the device; the check makes them again from the seed."""
    from paddle_tpu.fleet import decode

    cell, fam, t = run.cell, run.cell.family, run.cell.traffic
    with tempfile.TemporaryDirectory(prefix="bench_model_") as dirname:
        fam.export_decoder(cell.config, run.seed, dirname, t["prompt"],
                           t["new_tokens"], t["buckets"])
        run.log(f"exported buckets {t['buckets']}")
        server = decode.decode_server(dirname, max_wait_ms=t["max_wait_ms"],
                                      workers=t["workers"],
                                      queue_size=t["queue_size"])
    warm_slices(run, t)
    run.log("server ready")
    prompts = fam.prompts(cell.config["vocab_size"], t["rows"], t["prompt"],
                          run.seed, t["distinct_prompts"])
    try:
        yield server, prompts
    finally:
        server.close(drain=False, timeout=30)


def warm_slices(run: Run, t: Dict[str, Any]) -> None:
    """The server warms each bucket's executable, not the slice that hands a
    coalesced request its rows back (``fleet.batching.slice_rows``, an eager
    ``v[a:b]`` that compiles once per bucket shape on first use). Warm it
    here through the program's own function, on an array placed like an
    executable's output, so that no request in the window pays for it."""
    import jax
    from paddle_tpu.fleet import batching

    for b in t["buckets"]:
        if b > t["rows"]:
            ids = jax.device_put(np.zeros((b, t["new_tokens"]), np.int32),
                                 run.devices[0])
            jax.block_until_ready(batching.slice_rows(
                {"ids": ids}, t["rows"], t["rows"], b))


def collect(req: Request) -> None:
    """Take the outcome of a request whose ``pending`` is done."""
    try:
        req.ids = np.asarray(req.pending.result(timeout=0)["ids"])
    except Exception as e:  # a served error is a failed request, not a crash
        req.error = f"{type(e).__name__}: {e}"[:200]
    req.pending = None


def verdict(run: Run, server, prompts, requests: List[Request],
            compiles: int) -> Dict[str, Any]:
    """Counters of the server, and a seeded sample of served rows against
    the reference. Call after the window, before the server closes."""
    fam, t = run.cell.family, run.cell.traffic
    report = server.report()
    counters = {k: report[k] for k in (
        "submitted", "completed", "errors", "timeouts", "hangs",
        "rejected_overload", "rejected_invalid", "coalesced_batches",
        "coalesced_requests", "compiles_since_warmup")}
    done = [r for r in requests if r.ok]
    rng = np.random.RandomState(run.seed + 31)
    rows_p, rows_s = [], []
    for _ in range(min(fam.SERVE_CHECK_ROWS, len(done) * t["rows"])):
        r = done[rng.randint(len(done))]
        row = rng.randint(t["rows"])
        rows_p.append(prompts[r.prompt][row])
        rows_s.append(r.ids[row])
    shapes_ok = all(r.ids.shape == (t["rows"], t["new_tokens"]) for r in done)
    check = {"ok": False, "rows": 0}
    if rows_p and shapes_ok:
        params = fam.decoder_params(run.cell.config, run.seed, t["prompt"],
                                    t["new_tokens"])
        check = fam.served_check(run.cell.config, params, np.stack(rows_p),
                                 np.stack(rows_s))
    clean = (counters["errors"] == counters["timeouts"] == counters["hangs"]
             == 0 and counters["compiles_since_warmup"] == 0 and compiles == 0)
    return {"ok": bool(check["ok"] and clean and shapes_ok and done),
            "check": check, "server": counters}
