"""The flash kernels' share of their roofline: the operations the
algorithm needs (the family's function, from shapes) over the kernels'
device time, against the peak. At head_dim 64 and sequence 1024 the kernels
are compute-bound, so the bound is operations over peak FLOP/s. Steps are
counted from kernel calls in the window (three per layer per step, on each
chip its share), so a step cut by the window's edge counts in part."""


def read(run, obs, spec):
    tr = obs.trace
    if tr is None or run.peaks is None or not tr.kernels:
        return None
    fam, cfg = run.cell.family, run.cell.config
    seconds = tr.leaf_time_s(tr.is_kernel)
    steps = tr.count(tr.is_kernel) / fam.flash_calls_per_step(cfg)
    if not seconds or not steps:
        return None
    flops = steps * fam.flash_flops_per_step(
        cfg, obs.values["batch"], obs.values["seq"]) / run.cell.chips
    return 100.0 * flops / seconds / run.peaks["bf16_flops_per_s"]
