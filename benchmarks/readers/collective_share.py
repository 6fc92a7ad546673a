"""Device time of all-reduce, all-gather, reduce-scatter (and all-to-all,
collective-permute) operations on chip 0 over the traced window; with
``args.exposed`` only the part in which no other operation ran there."""


def read(run, obs, spec):
    if obs.trace is None:
        return None
    all_s, exposed_s = obs.trace.collective_s(0)
    return 100.0 * (exposed_s if spec["args"]["exposed"] else all_s) \
        / obs.trace.window_s
