"""Device time of the Pallas (Mosaic) kernel calls / device busy time,
chip 0. The only kernels in the step are flash forward, dq and dkv."""

from benchmarks.trace_reduce import total


def read(run, obs, spec):
    tr = obs.trace
    if tr is None or not tr.kernels:
        return None
    busy = total(tr.busy(0)) / 1e9
    return 100.0 * tr.leaf_time_s(tr.is_kernel) / busy if busy else None
