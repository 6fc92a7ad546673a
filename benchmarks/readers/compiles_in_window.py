"""Compiles (or loads from the persistent cache) that ``jax.monitoring``
saw inside the measured window, plus what the server counted after its
warm-up. Either kind stalls a step or a request; expected 0."""


def read(run, obs, spec):
    if "compiles_in_window" not in obs.values:
        return None
    return (obs.values["compiles_in_window"]
            + (obs.values.get("compiles_since_warmup") or 0))
