"""The generator's share of its HBM roofline: bytes one batch needs at the
configuration's precision (the family's function: bf16 weights once per
decode step, keys and values up to the current position) over the median
device duration of the generator's XLA module, against the peak bandwidth.
What the program reads beyond that lowers the share, as it should."""

from benchmarks.trace_reduce import median


def read(run, obs, spec):
    tr = obs.trace
    if tr is None or run.peaks is None:
        return None
    name = tr.main_module()
    d = median(tr.module_durations_s(name)) if name else None
    if not d:
        return None
    need = run.cell.family.decode_min_bytes(
        run.cell.config, obs.values["rows"], obs.values["prompt"],
        obs.values["new_tokens"])
    return 100.0 * need / d / run.peaks["hbm_bytes_per_s"]
