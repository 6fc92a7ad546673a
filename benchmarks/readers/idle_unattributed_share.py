"""The share of the device's idle seconds, in the traced part of the
window, that no span of the program names.

The gaps of chip 0's busy time are cut where the program's spans begin and
end, and each piece is put under the deepest span covering it. The ring is moved onto the trace's clock by the
median of (``bench.<args.outer>`` start in the trace less ``args.inner``
start in the ring) over the calls the trace holds, and the reader returns
nothing if the shifted spans do not then lie inside their callers
(``program_spans.align``). ``args.waits`` are spans of waiting, no one's
work, and do not count; ``args.umbrella`` spans only enclose the named
parts of a loop, so idle time whose deepest cover is an umbrella lies in
a stretch of that loop no span names: unattributed, like time under none."""

from benchmarks import program_spans


def attribution(obs, args):
    """Idle seconds by program span, or None where there is no device
    trace, no ring or no alignment."""
    if obs.trace is None or not obs.trace.ops.get(0):
        return None
    spans = program_spans.ring()
    shift = program_spans.trace_shift(obs, spans, args["outer"], args["inner"])
    if shift is None:
        return None
    return program_spans.idle_by_program_span(obs.trace, spans, shift,
                                              skip=args["waits"])


def read(run, obs, spec):
    idle = attribution(obs, spec["args"])
    if not idle:
        return None
    unnamed = sum(v for k, v in idle.items()
                  if k == "(no span)" or k in spec["args"]["umbrella"])
    return 100.0 * unnamed / sum(idle.values())
