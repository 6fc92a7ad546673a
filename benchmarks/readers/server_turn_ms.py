"""The host's turn between two dispatches of one worker, in milliseconds:
the time from the end of one ``serving.block`` (the device's result is
there) to the start of that worker's next ``serving.block`` (the next
program is enqueued and the worker waits again): replies sliced and
handed out, counters, the next group taken and merged, the feed sent. The
median over the window's consecutive dispatches, counting only a turn whose
``serving.dequeue`` took under ``args.backlog_dequeue_ms``: a request was
waiting, so the time is the host's and not an empty queue's."""

import statistics

from benchmarks import program_spans


def turns_ms(spans, backlog_dequeue_ms: float):
    """One reading for each pair of consecutive dispatches of a worker."""
    by_worker = {}
    for d, names in program_spans.by_dispatch(spans).items():
        if "serving.block" in names and "serving.dequeue" in names:
            block, dequeue = names["serving.block"][0], names["serving.dequeue"][0]
            by_worker.setdefault(block[4].get("worker"), []).append(
                (block[1], block[1] + block[2], dequeue[2]))
    out = []
    for blocks in by_worker.values():
        blocks.sort()
        for (_, prev_end, _), (start, _, dequeue_ns) in zip(blocks, blocks[1:]):
            if dequeue_ns < backlog_dequeue_ms * 1e6:
                out.append((start - prev_end) / 1e6)
    return out


def read(run, obs, spec):
    if "setup_s" not in obs.values or "window_s" not in obs.values:
        return None
    spans = program_spans.started_in(program_spans.ring(),
                                     program_spans.window(run, obs))
    values = turns_ms(spans, spec["args"]["backlog_dequeue_ms"])
    return statistics.median(values) if values else None
