"""A one-token kernel's share of its roofline, a call at a time: the
operations and bytes one call needs (the family's ``step_kernel_counts(
config, rows, kernel)`` -> ``(operations, bytes)``) against the chip's peaks,
the larger of the two bounds, over the mean device time of the kernel's calls
inside the main module's whole executions in the traced part.
``args.kernel`` is the kernel's name as the trace shows it (a prefix).

``readers/kernel_roofline.py`` counts a request's calls from the prompt
alone and tells a whole execution by that count; a step's kernel is called
once a layer and generated token, every call the same, so here each call is
its own reading and none has to be counted. Nothing where there is no device
trace, where it shows no such kernel, or where the family does not count it
(a program that lacks the kernel gives no number, it does not raise).
"""


def read(run, obs, spec):
    tr, fam = obs.trace, run.cell.family
    if (tr is None or run.peaks is None or "rows" not in obs.values
            or not hasattr(fam, "step_kernel_counts")):
        return None
    kernel = spec["args"]["kernel"]
    need = fam.step_kernel_counts(run.cell.config, obs.values["rows"], kernel)
    if need is None:
        return None
    flops, moved = need
    name = tr.main_module(0)
    lo, hi = tr.window
    whole = [(s, s + d) for n, s, d in tr.modules.get(0, [])
             if n == name and s >= lo and s + d <= hi]
    spent = [u for m, t, u in tr.ops.get(0, [])
             if m.startswith(kernel) and tr.is_kernel(m)
             and any(a <= t and t + u <= b for a, b in whole)]
    if not spent:
        return None
    least = max(flops / run.peaks["bf16_flops_per_s"],
                moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(spent) / (sum(spent) / 1e9)
