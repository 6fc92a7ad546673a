"""A number the driver observed, under the name ``args.value``."""


def read(run, obs, spec):
    return obs.values.get(spec["args"]["value"])
