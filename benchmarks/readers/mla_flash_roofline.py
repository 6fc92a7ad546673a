"""The forward flash kernel's share of its roofline in a serving cell: the
operations causal attention needs for one call (the family's
``mla_flash_flops``: rows x heads x prompt x (prompt + 1) / 2 keys at 192
for the score and 128 for the value, two a multiply-add) times the
``flash_fwd`` calls that lie whole inside the traced part, over those
calls' device time, against the peak bf16 rate. At 1984 x 1984 the kernel
is compute-bound (its bytes, read once, would take a twelfth of the time).
The calls are found by the kernel's name; the grouped products of the
expert layers are Mosaic kernels too and are not counted."""


def read(run, obs, spec):
    tr = obs.trace
    fam = run.cell.family
    if (tr is None or run.peaks is None or "prompt" not in obs.values
            or not hasattr(fam, "mla_flash_flops")):
        return None
    lo, hi = tr.window
    calls = [d for n, s, d in tr.ops.get(0, [])
             if n.startswith("flash_fwd") and tr.is_kernel(n)
             and s >= lo and s + d <= hi]
    if not calls:
        return None
    flops = len(calls) * fam.mla_flash_flops(
        run.cell.config, obs.values["rows"], obs.values["prompt"])
    return 100.0 * flops / (sum(calls) / 1e9) / run.peaks["bf16_flops_per_s"]
