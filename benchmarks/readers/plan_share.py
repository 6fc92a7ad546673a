"""A share read off the program's newest plan span (a zero-length span
whose ids describe what a traced program carries): ``args.span`` names it,
``args.part`` the id in the numerator, ``args.of`` the ids summed in the
denominator, in percent. Nothing where the ring holds no such span or the
span lacks an id (a program that does not record it)."""

from benchmarks import program_spans


def read(run, obs, spec):
    args = spec["args"]
    plans = [s[4] for s in program_spans.ring() if s[0] == args["span"]]
    if not plans or any(k not in plans[-1] for k in args["of"] + [args["part"]]):
        return None
    whole = sum(plans[-1][k] for k in args["of"])
    return 100.0 * plans[-1][args["part"]] / whole if whole else None
