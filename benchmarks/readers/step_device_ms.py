"""Median device duration of the train step: the executions, whole inside
the traced window, of the XLA module that took most device time. Wall time
per step less this is time the device waited."""

from benchmarks.trace_reduce import median


def read(run, obs, spec):
    if obs.trace is None:
        return None
    name = obs.trace.main_module()
    d = median(obs.trace.module_durations_s(name)) if name else None
    return None if d is None else d * 1e3
