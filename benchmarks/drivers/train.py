"""Train driver: seeded host batches through the input pipeline into
``Trainer.step``, by the entry a user calls, ``pt.fit(prefetch=True)``.

``fit`` builds the ``DeviceFeeder`` (capacity 2) itself. Its
``event_handler`` times the steps and the seeded reader ends the loop: it
stops yielding once the batches already in the pipeline will fill the rest
of the window at the step time seen so far, those batches run, and the
window is closed by ``block_until_ready`` on the last step's loss. So every
step counted is whole, and the window is ``--seconds`` to within a step or
two. The handler blocks on the
loss of two steps back, which keeps at most two steps in flight and never
makes the device wait for the host.

Traffic file: ``batch`` (global), ``seq``, ``warmup_steps``,
``distinct_batches``.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks.harness import Observed, Run, window_bytes
from benchmarks.tracing import Tracer, span

IN_FLIGHT = 2


def run(run: Run) -> Observed:
    import jax
    import paddle_tpu as pt

    cell, fam, t = run.cell, run.cell.family, run.cell.traffic
    batch, seq, warmup = t["batch"], t["seq"], t["warmup_steps"]
    batches = fam.lm_batches(cell.config["vocab_size"], batch, seq, run.seed,
                             t["distinct_batches"])
    trainer = fam.make_trainer(cell.config, run.seed, batches[0], run.devices)
    run.log("trainer started")
    structure = fam.step_structure(trainer, batches[0])
    run.log(f"step structure: {structure}")

    tracer = Tracer(cell.chips) if run.trace else None
    s = {"t0": None, "deadline": None, "losses": [], "starved0": 0.0,
         "open": None}

    def reader():
        i = 0
        while True:
            if s["deadline"] is not None:
                # steps known to be done: all but the ones still in flight
                now = time.perf_counter()
                done = max(len(s["losses"]) - IN_FLIGHT, 0)
                per_step = (now - s["t0"]) / done if done else 0.0
                ahead = i - (warmup + done)
                if now + ahead * per_step >= s["deadline"]:
                    return
            b = batches[i % len(batches)]
            i += 1
            yield list(zip(b["ids"], b["labels"]))

    def swap(name):
        if s["open"] is not None:
            s["open"].__exit__(None, None, None)
        s["open"] = span(name) if name else None
        if s["open"] is not None:
            s["open"].__enter__()

    def handler(ev):
        if ev.kind == "begin_step":
            swap("dispatch")
        elif ev.kind == "end_step":
            loss = ev.metrics["loss"]
            if s["t0"] is None:
                if ev.step == warmup:   # every shape compiled: open the window
                    jax.block_until_ready(loss)
                    run.compiles.mark()
                    s["starved0"] = trainer.pipeline_report()["consumer_starved_s"]
                    s["t0"] = time.perf_counter()
                    s["deadline"] = s["t0"] + run.seconds
                    run.log("window opens")
                swap("feed")
                return
            s["losses"].append(loss)
            if len(s["losses"]) > IN_FLIGHT:
                swap("block")
                jax.block_until_ready(s["losses"][-1 - IN_FLIGHT])
            if tracer and not tracer.started and time.perf_counter() >= \
                    s["deadline"] - run.trace_seconds:
                swap(None)
                tracer.start()
            swap("feed")

    pt.fit(trainer, reader, num_epochs=1, feed_names=["ids", "labels"],
           event_handler=handler, prefetch=True)
    swap("block")
    jax.block_until_ready(s["losses"][-1])
    t_end = time.perf_counter()
    swap(None)
    trace = tracer.stop() if tracer and tracer.started else None
    window = t_end - s["t0"]
    steps = len(s["losses"])
    compiles = run.compiles.since_mark()
    starved = trainer.pipeline_report()["consumer_starved_s"] - s["starved0"]
    losses = np.asarray([float(x) for x in s["losses"]], np.float64)
    run.log(f"window closed: {steps} steps in {window:.2f} s; "
            f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")

    tenth = max(1, steps // 10)
    finite = int(np.isfinite(losses).sum())
    fell = bool(losses[-tenth:].mean() < losses[:tenth].mean())
    on_tpu = run.devices[0].platform == "tpu"
    shape_ok = (not on_tpu) or structure["kernel_calls"] >= 3
    if trainer.mesh is not None:
        shape_ok = shape_ok and sum(structure["collectives"].values()) > 0 \
            and structure["tp_sharded_params"] > 0
    peak = window_bytes(run.devices)
    del trainer
    gc.collect()
    check = fam.train_check(cell.config, run.seed, seq, run.devices)
    run.log(f"reference check: {check}")

    tokens_per_s = steps * batch * seq / window
    return Observed(
        correct=bool(check["ok"] and finite == steps and fell and shape_ok
                     and compiles == 0),
        attempted=steps, failed=steps - finite,
        values={"train_tokens_per_s": tokens_per_s,
                "setup_s": s["t0"] - run.t_start,
                "window_s": window, "steps": steps,
                "compiles_in_window": compiles, "feeder_starved_s": starved,
                "peak_bytes_window": peak, "seq": seq, "batch": batch},
        series={"loss": losses.tolist()}, trace=trace,
        notes={"check": check, "structure": structure, "loss_fell": fell,
               "first_loss": losses[0], "last_loss": losses[-1]})
