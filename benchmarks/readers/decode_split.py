"""A generator execution split into its prefill and its decode loop, from
the device trace, and the two halves held to their bounds.

Within each execution of the main module that lies whole inside the traced
part, the decode loop is the ``[while]`` operation that holds the step's
``[conditional]`` (the first step takes the prefill's distribution instead
of running the stack: ``models/kimi_k2.py``); the prefill's own loops (the
scan over layers, the walk over expert pairs) hold none. Where several do,
the longest. ``args.part`` says which number:

- ``decode_step_ms``: the loop's duration over ``new_tokens - 1`` cached
  steps, median over executions;
- ``prefill_ms``: the module's duration less the loop's;
- ``decode_step_hbm_share``: the bytes a step has to read (the family's
  ``decode_step_bytes``, mean over the steps' positions) over
  ``decode_step_ms`` and the peak HBM bandwidth;
- ``prefill_mfu``: the operations the prefill needs (the family's
  ``prefill_flops``) over ``prefill_ms`` and the peak bf16 rate.

Nothing where no whole execution lies in the traced part, where no loop
holds a conditional, or where the family has no such count.
"""

from benchmarks.trace_reduce import median


def executions(tr, chip: int = 0):
    """``[(module seconds, decode loop seconds)]`` of the main module's
    whole executions in the traced part."""
    name = tr.main_module(chip)
    lo, hi = tr.window
    ops = tr.ops.get(chip, [])
    inside = lambda s, d, a, b: s >= a and s + d <= b
    out = []
    for n, s, d in tr.modules.get(chip, []):
        if n != name or not inside(s, d, lo, hi):
            continue
        mine = [(m, t, u) for m, t, u in ops if inside(t, u, s, s + d)]
        conds = [(t, u) for m, t, u in mine if m.endswith("[conditional]")]
        loops = [u for m, t, u in mine if m.endswith("[while]")
                 and any(inside(ct, cu, t, t + u) for ct, cu in conds)]
        if loops:
            out.append((d / 1e9, max(loops) / 1e9))
    return out


def read(run, obs, spec):
    tr = obs.trace
    if tr is None or "new_tokens" not in obs.values:
        return None
    runs = executions(tr)
    steps = obs.values["new_tokens"] - 1
    if not runs or steps < 1:
        return None
    step_s = median([loop / steps for _, loop in runs])
    prefill_s = median([whole - loop for whole, loop in runs])
    part = spec["args"]["part"]
    if part == "decode_step_ms":
        return step_s * 1e3
    if part == "prefill_ms":
        return prefill_s * 1e3
    fam, cfg = run.cell.family, run.cell.config
    rows, prompt = obs.values["rows"], obs.values["prompt"]
    if run.peaks is None:
        return None
    if part == "decode_step_hbm_share" and hasattr(fam, "decode_step_bytes"):
        need = sum(fam.decode_step_bytes(cfg, rows, prompt + j)
                   for j in range(steps)) / steps
        return 100.0 * need / step_s / run.peaks["hbm_bytes_per_s"]
    if part == "prefill_mfu" and hasattr(fam, "prefill_flops"):
        return (100.0 * fam.prefill_flops(cfg, rows, prompt) / prefill_s
                / run.peaks["bf16_flops_per_s"])
    return None
