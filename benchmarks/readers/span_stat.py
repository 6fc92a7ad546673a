"""A percentile of program spans that started in the measured window, in
milliseconds: ``args.spans`` names them, ``args.q`` the percentile (50: the
median), and with ``args.per`` = ``"dispatch"`` the spans of one dispatch
(the ``dispatch`` id) are summed first, so that ``serving.merge`` +
``serving.run`` is one reading a dispatch, counted where each of them began
in the window (a server numbers its own dispatches: the window is what
tells two servers of one process apart)."""

import numpy as np

from benchmarks import program_spans


def read(run, obs, spec):
    if "setup_s" not in obs.values or "window_s" not in obs.values:
        return None
    args = spec["args"]
    spans = [s for s in program_spans.started_in(
        program_spans.ring(), program_spans.window(run, obs))
        if s[0] in args["spans"]]
    if args.get("per") == "dispatch":   # dispatches with every part inside
        values = [sum(s[2] for group in names.values() for s in group)
                  for names in program_spans.by_dispatch(spans).values()
                  if len(names) == len(args["spans"])]
    else:
        values = [s[2] for s in spans]
    return float(np.percentile(values, args["q"])) / 1e6 if values else None
