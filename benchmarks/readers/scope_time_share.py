"""Measured device time of the program's own scopes over the main module's
device time, both over the main module's whole executions in the traced
part (its parts there where it holds no whole one), chip 0.

The program registers each executable with ``paddle_tpu.core.profiler``
and, when asked, gives ``{instruction: (named_scope path, remat, backward,
a collective's mesh axes, inherited)}`` for it; ``profiler.device_scopes``
joins the trace's operations (self time: a ``while`` holds its body) to
that table, and this reader only picks the events and the selection. A
fusion is indivisible and lies whole under the scope its metadata names.
Scope paths partition the module; ``remat`` and ``inherited`` cut across
them (an operation of the second forward inside ``attn`` counts under
``attn`` and under remat; a layout copy the compiler made for ``attn``
counts under ``attn`` and under inherited).

``args``: ``scopes`` (path components: a path holding any of them is
kept), ``remat: true`` (of the kept paths the second forward only; no
``scopes`` = all paths, kernels included), ``inherited: true`` (of the
kept paths the operations that carry no name stack and were placed with
the instruction they feed), ``axes: "dp"`` (collectives over exactly
these mesh axes), or ``unscoped: true`` (time under no ``named_scope``
plus operations the table does not hold).

Nothing where there is no device trace, where the program keeps no table
(the parent of the PR that added it), or where no table is the module's.
"""

from benchmarks.trace_reduce import clip, total, union

CHIP = 0


def joined(obs):
    """``(profiler.device_scopes(...) of the main module's operations in
    its whole executions in the traced part, the module's device ns in
    them, the (start, end) of each, how many more the traced part holds a
    part of and were left out)``, or None. Made once a run: every entry
    of a cell reads the same join."""
    if not hasattr(obs, "_scope_join"):
        obs._scope_join = _join(obs)
    return obs._scope_join


def _join(obs):
    from paddle_tpu.core import profiler

    tr = obs.trace
    if tr is None or not tr.ops.get(CHIP) or not hasattr(profiler,
                                                         "device_scopes"):
        return None
    name = tr.main_module(CHIP)
    tables = profiler.program_tables(name) if name else []
    if not tables:
        return None
    lo, hi = tr.window
    mine = [(s, s + d) for n, s, d in tr.modules.get(CHIP, []) if n == name]
    touched = [(s, e) for s, e in mine if e > lo and s < hi]
    # a request's prefill and its steps differ, so a share is taken over
    # every whole execution the traced part holds, long or short; where
    # there is none, over the parts it holds. An execution that the end of
    # the device's record cut shows as a whole one whose event ends where
    # the record does (a long trace can end before the traced part): the
    # one execution that ends there is left out
    record_end = max(s + d for evs in (tr.ops[CHIP], tr.modules[CHIP])
                     for _, s, d in evs)
    whole = [(s, e) for s, e in touched
             if s >= lo and e <= hi and e < record_end]
    runs = whole or union(clip(touched, tr.window))
    events = [(n, max(s, lo), min(s + d, hi) - max(s, lo))
              for n, s, d in tr.ops[CHIP] if min(s + d, hi) > max(s, lo)]
    return (profiler.device_scopes(profiler.events_inside(events, runs),
                                   tables),
            total(runs), runs, len(touched) - len(whole) if whole else 0)


def read(run, obs, spec):
    from paddle_tpu.core import profiler

    got = joined(obs)
    if got is None or not got[1]:
        return None
    args = spec["args"]
    ns = profiler.scope_ns(got[0], scopes=args.get("scopes", ()),
                           remat=args.get("remat", False),
                           axes=args.get("axes"),
                           unscoped=args.get("unscoped", False),
                           inherited=args.get("inherited", False))
    return 100.0 * ns / got[1]
