"""1 - union of the intervals in which an operation ran on the device /
traced window, averaged over the cell's chips."""


def read(run, obs, spec):
    if obs.trace is None:
        return None
    return 100.0 * obs.trace.idle_share()
