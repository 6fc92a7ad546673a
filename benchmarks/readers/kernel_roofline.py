"""A named kernel's share of its roofline: the operations and bytes all its
calls in one request need (the family's ``kernel_counts(config, rows,
prompt, kernel)`` -> ``(operations, bytes, calls)``), over the device time
of those calls, against the chip's peaks; the larger of the compute bound
(operations / peak bf16 FLOP/s) and the bandwidth bound (bytes / peak HBM
bytes/s) is the least time the chip could take. ``args.kernel`` is the
kernel's name as the trace shows it (a prefix: the trace numbers the calls).

A request's calls differ (a later chunk of a prompt reads more keys), so
only whole executions of the main module in the traced part are read: each
holds every call of a request once (an execution cut by the trace's end
shows as a shorter module inside the window: it is told by its missing
calls and left out). Nothing where there is none, where the trace shows no
such kernel, or where the family does not count it (a program that lacks
the kernel gives no number, it does not raise).
"""


def read(run, obs, spec):
    tr, fam = obs.trace, run.cell.family
    if (tr is None or run.peaks is None or "prompt" not in obs.values
            or not hasattr(fam, "kernel_counts")):
        return None
    kernel = spec["args"]["kernel"]
    need = fam.kernel_counts(run.cell.config, obs.values["rows"],
                             obs.values["prompt"], kernel)
    if need is None:
        return None
    flops, moved, calls = need
    name = tr.main_module(0)
    lo, hi = tr.window
    requests = []
    for n, s, d in tr.modules.get(0, []):
        if n != name or s < lo or s + d > hi:
            continue
        spent = [u for m, t, u in tr.ops.get(0, [])
                 if m.startswith(kernel) and tr.is_kernel(m)
                 and s <= t and t + u <= s + d]
        # an execution that the trace's end cut short shows as a shorter
        # one inside the window: whole is the one with all of a request's calls
        if len(spent) == calls:
            requests.append(sum(spent) / 1e9)
    if not requests:
        return None
    least = max(flops / run.peaks["bf16_flops_per_s"],
                moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * len(requests) * least / sum(requests)
