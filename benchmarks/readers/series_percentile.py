"""Percentile ``args.q`` of the per-request series ``args.series``."""

import numpy as np


def read(run, obs, spec):
    xs = obs.series.get(spec["args"]["series"])
    return float(np.percentile(xs, spec["args"]["q"])) if xs else None
