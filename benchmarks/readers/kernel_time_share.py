"""Device time of the calls of the named kernels (``args.kernels``: name
prefixes, as the trace shows Pallas kernels) over the device time of the
main module's executions, both inside the traced part, chip 0: the share of
a request that the architecture's own mixers take. Nothing where the trace
shows none of them."""

from benchmarks.trace_reduce import clip, total, union


def read(run, obs, spec):
    tr = obs.trace
    if tr is None or not tr.kernels:
        return None
    prefixes = tuple(spec["args"]["kernels"])
    name = tr.main_module(0)
    module = total(union(clip(((s, s + d) for n, s, d in tr.modules.get(0, [])
                               if n == name), tr.window)))
    mine = tr.leaf_time_s(lambda n: tr.is_kernel(n) and n.startswith(prefixes))
    return 100.0 * mine / (module / 1e9) if module and mine else None
