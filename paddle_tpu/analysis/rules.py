"""The lint rule families of the static program checker.

Each rule takes the artifacts ``analysis.check`` prepared (jaxpr, param
scope, mesh/rules, example arguments) and appends :class:`Finding`s to a
:class:`LintReport`. Codes are ``family:rule``:

- ``collective:*`` — collective-placement hazards (the unhoisted-accum
  class of bug): reduction collectives nested in
  loop bodies multiply their wire bytes by the trip count.
- ``dtype:*``      — mixed-precision flow: f32 MXU ops surviving under
  an amp compute dtype, f64 leaks, no-op cast round-trips.
- ``sharding:*``   — whole-program audit of the rule table against the
  actual parameter scope (per-param ``_validate`` only sees one name at
  placement time; this sees rules that match nothing and large params
  left replicated).
- ``params:*``     — dead parameters (initialized, never read) and
  trainable parameters with structurally-zero gradients.
- ``donation:*``   — fetched step outputs aliasing donated inputs (the
  donated-buffer-reuse footgun, sharpened by the K-step fused dispatch
  donating the whole training carry).
- ``retrace:*``    — recompilation hazards in the traced arg signature
  (weak python scalars, unhashable objects).
- ``feed:*``       — input-pipeline wire-format opportunities: float32
  feed inputs whose first in-program uses are a cast/normalize could
  cross the host→device link as uint8/bf16 wire (data/wire.WireSpec)
  and decode on device for free.
- ``moe:*``        — mixture-of-experts routing shape: static
  ``capacity_factor``/``top_k`` combos whose expected token drop rate
  (computable from the dispatch tensor shapes alone) exceeds a
  threshold.
- ``sharding:replicated-optstate`` — optimizer state fully replicated
  across a data-parallel axis above a size threshold: the ZeRO
  (cross-replica sharded weight update) trigger.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from .report import LintReport, collect_into
from .walker import (COLLECTIVES, LOOP_PRIMS, PERMUTE_COLLECTIVES,
                     REDUCTION_COLLECTIVES, aval_bytes, eqn_out_bytes,
                     eqn_subjaxprs, in_loop, is_structural_zero, iter_eqns,
                     producer_map, used_var_ids)

# --------------------------------------------------------------------------
# 1. collective placement
# --------------------------------------------------------------------------


def _walk_with_trips(jaxpr, path=(), trips=1):
    """iter_eqns plus the product of enclosing loop trip counts (None
    once a loop with unknowable count — e.g. while — intervenes).
    Loop-primitive membership comes from walker.LOOP_PRIMS so the two
    walks can never disagree about what counts as a loop."""
    for eqn in jaxpr.eqns:
        yield eqn, path, trips
        name = eqn.primitive.name
        sub_trips = trips
        if name in LOOP_PRIMS:
            length = eqn.params.get("length")  # scan carries it; while: None
            sub_trips = (None if trips is None or length is None
                         else trips * int(length))
        for sub in eqn_subjaxprs(eqn):
            yield from _walk_with_trips(sub, path + (name,), sub_trips)


def _group_size(eqn, mesh) -> int:
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    n = 1
    for a in axes:
        if mesh is not None and a in getattr(mesh, "axis_names", ()):
            n *= mesh.shape[a]
    return n


def check_collectives(closed_jaxpr, report: LintReport, mesh=None) -> None:
    """Flag reduction collectives (psum / all_gather / all_to_all /
    psum_scatter) nested inside scan/while bodies: each loop iteration
    pays the exchange, the hoisted-accumulation hazard. Neighbor
    permutes (ppermute) inside loops are the *deliberate* structure of
    ring/pipeline schedules, so they are reported at info severity with
    the same byte accounting rather than warned."""
    for eqn, path, trips in _walk_with_trips(closed_jaxpr.jaxpr):
        name = eqn.primitive.name
        if name not in COLLECTIVES:
            continue
        if not in_loop(path):
            continue
        payload = eqn_out_bytes(eqn)
        n = _group_size(eqn, mesh)
        per_step = None if trips is None else payload * trips
        loop_desc = "while" if trips is None else f"×{trips} scan iterations"
        if name in REDUCTION_COLLECTIVES:
            report.add(
                "collective:in-scan", "warning",
                f"{name} inside a loop body ({' > '.join(path)}): the "
                f"exchange ({payload / 1e6:.3f} MB result"
                + (f", ~{per_step / 1e6:.3f} MB {loop_desc} per step"
                   if per_step is not None else f", {loop_desc}")
                + ") runs every iteration — hoist it out of the loop if it "
                "does not depend on the loop carry (the per-microbatch "
                "allreduce hazard; see DistStrategy.accum_exchange='hoisted')",
                where=name, payload_bytes=payload, trips=trips,
                per_step_bytes=per_step, group_size=n, path=list(path))
        else:
            report.add(
                "collective:permute-in-scan", "info",
                f"{name} inside a loop body ({' > '.join(path)}): "
                f"{payload / 1e6:.3f} MB neighbor-hop per iteration"
                + (f" (~{per_step / 1e6:.3f} MB per step)"
                   if per_step is not None else "")
                + " — expected for ring/pipeline schedules",
                where=name, payload_bytes=payload, trips=trips,
                per_step_bytes=per_step, group_size=n, path=list(path))


def check_accum_exchange(strategy, mesh, params, report: LintReport) -> None:
    """Config-level collective placement: ``accum_steps>1`` with the
    default GSPMD exchange on a data-parallel mesh rides one full
    gradient all-reduce INSIDE the microbatch scan per iteration (the
    collective is inserted by the SPMD partitioner, so it is invisible
    to the jaxpr walk — this rule reasons from the config; the compiled
    HLO's in-loop all-reduce is pinned by tests/test_collective_report.py)."""
    accum = int(getattr(strategy, "accum_steps", 1) or 1) if strategy else 1
    mode = getattr(strategy, "accum_exchange", "gspmd") if strategy else "gspmd"
    if accum <= 1 or mode != "gspmd" or mesh is None:
        return
    data_n = 1
    for a in ("dp", "fsdp"):
        if a in mesh.axis_names:
            data_n *= mesh.shape[a]
    if data_n <= 1:
        return
    grad_bytes = sum(int(np.prod(v.shape)) * 4
                     for v in jax.tree.leaves(params))  # f32 grads
    wire = 2.0 * (data_n - 1) / data_n * grad_bytes
    report.add(
        "collective:microbatch-exchange", "warning",
        f"accum_steps={accum} with accum_exchange='gspmd' on a "
        f"{data_n}-way data mesh exchanges gradients once per microbatch "
        f"(~{accum * wire / 1e6:.1f} MB wire/device/step vs "
        f"{wire / 1e6:.1f} MB hoisted) — set "
        "DistStrategy.accum_exchange='hoisted' when params are replicated",
        where="DistStrategy.accum_steps",
        accum_steps=accum, data_shards=data_n,
        per_step_bytes=accum * wire, hoisted_bytes=wire)


def check_quantized_exchange(strategy, mesh, params, report: LintReport,
                             profile=None) -> None:
    """``sharding:unquantized-exchange`` advisory: the run crosses a
    data axis with full-width f32 gradients while the measured profile
    says the link is the bottleneck (delivered throughput a small
    fraction of compute-only).
    Fires only with profile evidence (``profile_report()``'s bottleneck
    naming the link, or an explicit ``link_bound`` flag from bench):
    quantization is a tradeoff, so config alone never triggers it."""
    qmode = ((getattr(strategy, "quantized_allreduce", "none")
              if strategy else "none") or "none")
    if qmode != "none" or mesh is None:
        return
    data_n = 1
    for a in ("dp", "fsdp"):
        if a in mesh.axis_names:
            data_n *= mesh.shape[a]
    if data_n <= 1 or not profile:
        return
    link_bound = bool(profile.get("link_bound")) or \
        profile.get("bottleneck") == "h2d_s"
    if not link_bound:
        return
    grad_bytes = sum(int(np.prod(v.shape)) * 4
                     for v in jax.tree.leaves(params))  # f32 grads
    wire = 2.0 * (data_n - 1) / data_n * grad_bytes
    report.add(
        "sharding:unquantized-exchange", "info",
        f"profile marks the run link-bound "
        f"(bottleneck={profile.get('bottleneck')!r}) while gradients "
        f"cross the {data_n}-way data mesh at full f32 width "
        f"(~{wire / 1e6:.1f} MB wire/device/step) — consider "
        "DistStrategy.quantized_allreduce='int8' (~4x less gradient "
        "wire, block-scaled with error feedback; see MIGRATION.md "
        "\"Quantized collectives\")",
        where="DistStrategy.quantized_allreduce",
        data_shards=data_n, per_step_bytes=wire,
        bottleneck=profile.get("bottleneck"))


# --------------------------------------------------------------------------
# 2. dtype flow
# --------------------------------------------------------------------------

_MXU_PRIMS = frozenset({"dot_general", "conv_general_dilated"})


def _np_dtype(dt):
    """np.dtype(dt) or None for jax extended dtypes (typed PRNG keys in
    the train-step jaxpr, fp8 wrappers) that numpy cannot interpret —
    the dtype rules simply don't apply to those avals."""
    try:
        return np.dtype(dt)
    except TypeError:
        return None


def check_dtypes(closed_jaxpr, report: LintReport,
                 compute_dtype=None, feed: Optional[Dict[str, Any]] = None) -> None:
    """Mixed-precision flow over the whole jaxpr:

    - ``dtype:amp-f32-matmul`` — a matmul/conv whose operands stayed f32
      while the ambient compute dtype is reduced (bf16/f16): the layer
      bypassed ``cast_compute`` and its MXU op runs at 1/2 the
      throughput the amp_guard asked for.
    - ``dtype:f64-leak`` — any f64 aval (TPU has no f64 MXU path), plus
      f64 feed arrays that x64-off mode will silently truncate.
    - ``dtype:cast-roundtrip`` — convert chains that return to the
      source dtype (x→b→x): a no-op pair that usually marks a missing
      dtype plumb-through.
    """
    cd = np.dtype(compute_dtype) if compute_dtype is not None else None
    reduced = cd is not None and cd.itemsize < 4 and cd.kind in ("f", "V")
    for k, v in (feed or {}).items():
        try:
            dt = v.dtype if hasattr(v, "dtype") else np.asarray(v).dtype
        except Exception:
            continue  # untraceable value: the retrace family owns it
        if np.dtype(dt) == np.float64:
            report.add("dtype:f64-leak", "warning",
                       f"feed {k!r} is float64 — under the default x64-off "
                       "config it is silently truncated to float32 at "
                       "device_put; cast at the data layer",
                       where=k)

    def visit(jaxpr):
        producers = producer_map(jaxpr)
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            avals = [getattr(v, "aval", None) for v in eqn.invars]
            out_avals = [getattr(v, "aval", None) for v in eqn.outvars]
            for av in out_avals:
                if getattr(av, "dtype", None) is not None and \
                        _np_dtype(av.dtype) == np.float64:
                    report.add("dtype:f64-leak", "warning",
                               f"{name} produces float64 {av.shape} — no "
                               "f64 MXU path on TPU; cast to f32",
                               where=name)
                    break
            if reduced and name in _MXU_PRIMS:
                op_dts = [_np_dtype(av.dtype) for av in avals
                          if getattr(av, "dtype", None) is not None]
                op_dts = [dt for dt in op_dts if dt is not None]
                if op_dts and all(dt == np.float32 for dt in op_dts):
                    shapes = [tuple(getattr(av, "shape", ())) for av in avals]
                    report.add(
                        "dtype:amp-f32-matmul", "warning",
                        f"{name} on f32 operands {shapes} while the compute "
                        f"dtype is {cd} — the layer bypassed cast_compute; "
                        "this op misses the reduced-precision MXU path "
                        "amp_guard selected",
                        where=name, shapes=shapes)
            if name == "convert_element_type":
                src = eqn.invars[0]
                peqn = producers.get(id(src))
                if (peqn is not None
                        and peqn.primitive.name == "convert_element_type"):
                    orig = getattr(peqn.invars[0], "aval", None)
                    final = getattr(eqn.outvars[0], "aval", None)
                    odt = _np_dtype(orig.dtype) if orig is not None else None
                    fdt = _np_dtype(final.dtype) if final is not None else None
                    mid = _np_dtype(getattr(src, "aval").dtype)
                    if (odt is not None and fdt is not None
                            and mid is not None and odt == fdt):
                        report.add(
                            "dtype:cast-roundtrip", "info",
                            f"cast round-trip {odt} → {mid} "
                            f"→ {fdt}: the pair is a no-op "
                            "(or a silent precision truncation if the middle "
                            "dtype is narrower) — plumb the dtype through "
                            "instead",
                            where=name,
                            # the dtype triple discriminates fingerprints:
                            # a NEW f32->f16->f32 round-trip must not be
                            # suppressed by a baselined f32->bf16->f32 one
                            dtype=f"{odt}->{mid}->{fdt}")

    from .walker import walk_jaxprs
    walk_jaxprs(closed_jaxpr.jaxpr, visit)


# --------------------------------------------------------------------------
# 3. whole-program sharding audit
# --------------------------------------------------------------------------


def check_sharding(params: Dict[str, Any], mesh, rules,
                   report: LintReport, param_info=None,
                   large_param_bytes: int = 1 << 20) -> None:
    """Audit the rule table against the actual parameter scope. The
    per-param drop diagnostics (axis missing / dim not divisible /
    rank mismatch) come from routing ``sharding._warn_drop`` through
    the report collector while resolving every spec — the same code
    path placement uses, so the audit can never disagree with it."""
    if mesh is None or rules is None or not params:
        return
    from ..parallel.sharding import CANONICAL_AXES

    # typo'd axes must be read off the RAW table: adapted_to strips
    # non-mesh axes (and memoizes, so its one-shot adapt-time warning
    # may long since have fired outside any collector)
    nameset = set(mesh.axis_names)
    for i, (pat, spec) in enumerate(getattr(rules, "rules", []) or []):
        for entry in spec:
            axes = entry if isinstance(entry, tuple) else (
                (entry,) if entry is not None else ())
            for a in axes:
                if a not in nameset and a not in CANONICAL_AXES:
                    report.add(
                        "sharding:unknown-axis", "warning",
                        f"rule #{i} {pat.pattern!r} names axis {a!r} which "
                        f"is neither in the mesh {dict(mesh.shape)} nor a "
                        f"canonical axis name {sorted(CANONICAL_AXES)} — "
                        "likely a typo; that dim is silently replicated",
                        where=pat.pattern, rule_index=i, axis=a)

    adapted = rules.adapted_to(mesh)
    names = list(params)
    for i, (pat, spec) in enumerate(getattr(adapted, "rules", []) or []):
        if not any(pat.search(n) for n in names):
            report.add(
                "sharding:unmatched-rule", "warning",
                f"rule #{i} {pat.pattern!r} → {spec} matches no parameter "
                f"({len(names)} in scope) — stale pattern or renamed layer",
                where=pat.pattern, rule_index=i)

    fsdp_n = mesh.shape.get("fsdp", 1) if "fsdp" in mesh.axis_names else 1
    with collect_into(report):
        for name in names:
            v = params[name]
            spec = adapted.spec_for(name, tuple(v.shape), mesh)
            nbytes = int(np.prod(v.shape or (1,))) * np.dtype(v.dtype).itemsize
            replicated = all(e is None for e in spec)
            if replicated and fsdp_n > 1 and nbytes >= large_param_bytes:
                report.add(
                    "sharding:replicated-large", "warning",
                    f"{name} ({nbytes / 1e6:.2f} MB {v.dtype}{tuple(v.shape)}) "
                    f"is fully replicated although the mesh has an fsdp axis "
                    f"of size {fsdp_n} — each device holds a full copy "
                    f"(+{(fsdp_n - 1) / fsdp_n * nbytes / 1e6:.2f} MB/device "
                    "vs sharded)",
                    where=name, bytes=nbytes, fsdp=fsdp_n)


# --------------------------------------------------------------------------
# 4. dead / zero-gradient parameters
# --------------------------------------------------------------------------


def check_params(program, params, state, args, kwargs,
                 report: LintReport, loss_name: str = "loss",
                 closed_flat=None, invar_names=None) -> None:
    """``params:dead`` — parameters materialized by ``Program.init`` that
    never appear as live jaxpr invars (the trace never reads them: a
    created-but-unused layer, or a stale checkpoint name).
    ``params:zero-grad`` — ``trainable=True`` parameters whose gradient
    is *structurally* zero (literal-0 broadcast in the grad jaxpr):
    they consume optimizer state and exchange bandwidth every step and
    never move."""
    if closed_flat is None:
        closed_flat, invar_names = program.desc_flat(params, state, *args,
                                                     **kwargs)
    jaxpr = closed_flat.jaxpr
    used = used_var_ids(jaxpr)
    dead = set()
    for var, (kind, name) in zip(jaxpr.invars, invar_names):
        if kind == "param" and id(var) not in used:
            dead.add(name)
            report.add(
                "params:dead", "warning",
                f"parameter {name!r} "
                f"{tuple(getattr(var.aval, 'shape', ()))} is initialized "
                "but never read by the program — dead weight in every "
                "checkpoint and optimizer step",
                where=name)

    # gradient structure: only meaningful when a scalar loss is exposed
    leaves, treedef = jax.tree.flatten(params)
    pnames = sorted(params)  # jax flattens dicts in sorted-key order

    def loss_of(flat):
        p = jax.tree.unflatten(treedef, flat)
        out, _ = program.apply(p, state, *args, training=False, **kwargs)
        loss = out.get(loss_name) if isinstance(out, dict) else out
        return loss

    try:
        out_aval = jax.eval_shape(loss_of, leaves)
        if getattr(out_aval, "shape", None) != ():
            return
        closed_g = jax.make_jaxpr(jax.grad(loss_of))(leaves)
    except Exception:
        return  # no scalar loss under this name: skip the grad analysis
    gj = closed_g.jaxpr
    producers = producer_map(gj)
    info = getattr(program, "param_info", {}) or {}
    for name, gvar in zip(pnames, gj.outvars):
        pi = info.get(name)
        if pi is not None and not pi.trainable:
            continue  # frozen on purpose (stop_gradient): not a finding
        if name in dead:
            continue  # already reported with the sharper code
        if is_structural_zero(gvar, producers):
            report.add(
                "params:zero-grad", "warning",
                f"trainable parameter {name!r} has a structurally zero "
                f"gradient w.r.t. {loss_name!r} — it is read by the program "
                "but the loss does not depend on it (forgotten head? "
                "mark trainable=False to stop paying optimizer state)",
                where=name)


# --------------------------------------------------------------------------
# 5. donation aliasing
# --------------------------------------------------------------------------


def check_donation(closed_jaxpr, donated: Dict[int, str],
                   fetched: Dict[int, str], report: LintReport) -> None:
    """``donation:fetched-alias`` — a FETCHED step output that is a
    donated input passed through unchanged (the outvar IS the invar in
    the step jaxpr). With buffer donation XLA reuses the donated buffer
    for the in-place param/opt-state update, so the passthrough forces a
    defensive copy at best — and a caller that keeps the fetched handle
    across the next (donating) dispatch holds a buffer the runtime
    considers consumed: the donated-buffer-reuse footgun. The K-step
    fused dispatch (``Trainer.run_steps``) donates the whole training
    carry end-to-end, which widens the window — fetch a computed value
    (e.g. ``jnp.copy`` / a fresh reduction) instead of the raw carry
    leaf.

    ``donated`` maps flat invar index → display name for every donated
    leaf; ``fetched`` maps flat outvar index → display name for every
    leaf of the step's fetch dict."""
    jaxpr = closed_jaxpr.jaxpr
    donated_by_id = {id(jaxpr.invars[i]): name
                     for i, name in donated.items() if i < len(jaxpr.invars)}
    for i, oname in fetched.items():
        if i >= len(jaxpr.outvars):
            continue
        v = jaxpr.outvars[i]
        if type(v).__name__ == "Literal":
            continue
        src = donated_by_id.get(id(v))
        if src is not None:
            report.add(
                "donation:fetched-alias", "warning",
                f"fetched step output {oname} is donated input {src} "
                "passed through unchanged — donation hands that buffer to "
                "XLA for in-place reuse, so fetching the alias forces a "
                "copy (or, held across the next donating dispatch, reads "
                "a consumed buffer); fetch a computed value or drop it "
                "from fetch_list",
                where=oname, donated_input=src, outvar_index=i)


# --------------------------------------------------------------------------
# 6. recompilation hazards
# --------------------------------------------------------------------------


def check_signature(bound: Dict[str, Any], report: LintReport) -> None:
    """Inspect the example call signature for retrace hazards. ``bound``
    maps argument names to example values (``Program.arg_signature``)."""
    for name, val in bound.items():
        for sub, leaf in _named_leaves(name, val):
            if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
                continue  # array-like: shape/dtype keyed, retrace-safe
            if isinstance(leaf, bool) or isinstance(leaf, (int, float)):
                report.add(
                    "retrace:weak-scalar", "info",
                    f"argument {sub!r} is a weak-typed python "
                    f"{type(leaf).__name__} ({leaf!r}) — it promotes "
                    "silently in dtype arithmetic, and if routed to a "
                    "static argument it recompiles per distinct value; "
                    "wrap in np.asarray(..., dtype=...)",
                    where=sub)
                continue
            if isinstance(leaf, str) or leaf is None:
                continue
            try:
                hash(leaf)
            except TypeError:
                report.add(
                    "retrace:unhashable-arg", "warning",
                    f"argument {sub!r} is an unhashable "
                    f"{type(leaf).__name__} — it cannot key a compile "
                    "cache (static argnums reject it; as a traced arg each "
                    "call re-converts it); pass an array or a hashable "
                    "config object",
                    where=sub)


def _named_leaves(name: str, val):
    """(name, leaf) pairs one level of dict/tuple deep — enough to name
    feed entries without flattening arrays themselves."""
    if isinstance(val, dict) and not hasattr(val, "shape"):
        for k, v in val.items():
            yield f"{name}[{k!r}]", v
    else:
        # lists/tuples are reported on the container, not per element
        # (the common hazard is a python list standing in for an array)
        yield name, val


# --------------------------------------------------------------------------
# 7. pipeline shape
# --------------------------------------------------------------------------


def check_pipeline(strategy, mesh, feed: Optional[Dict[str, Any]],
                   report: LintReport) -> None:
    """``pipeline:*`` — the pipeline-schedule shape constraints that
    used to surface only as runtime enforces inside ``pipeline_apply``
    (mid-trace, after startup cost is sunk), checked statically at
    startup from the strategy + mesh + sample feed:

    - ``pipeline:batch-indivisible`` — batch % pp_microbatches != 0
      (the trace WILL fail at the first step);
    - ``pipeline:microbatch-indivisible`` — microbatch not divisible
      by the dp/fsdp data-shard product;
    - ``pipeline:bubble`` — the exact fill/drain waste fraction of the
      schedule (``parallel.pipeline.bubble_fraction``), warned above
      20% with the microbatch/interleave levers named.

    The runtime enforces stay (defense in depth); this family names
    the fix before anything compiles."""
    pp_m = int(getattr(strategy, "pp_microbatches", 0) or 0) if strategy else 0
    if pp_m <= 0:
        return
    pp_v = max(1, int(getattr(strategy, "pp_interleave", 1) or 1))
    b = None
    for v in (feed or {}).values():
        shape = getattr(v, "shape", None)
        if shape is None:
            try:
                shape = np.asarray(v).shape
            except Exception:
                continue
        if shape:
            b = int(shape[0])
            break
    indivisible = b is not None and b % pp_m != 0
    if indivisible:
        report.add(
            "pipeline:batch-indivisible", "warning",
            f"batch {b} is not divisible by pp_microbatches={pp_m} — "
            "pipeline_apply will reject the trace at the first step; "
            "re-batch the feed or lower pp_microbatches",
            where="DistStrategy.pp_microbatches", batch=b,
            pp_microbatches=pp_m)
    dshard = 1
    if mesh is not None:
        for a in ("dp", "fsdp"):
            if a in mesh.axis_names:
                dshard *= mesh.shape[a]
    # the microbatch-divisibility math would divide by a lie when the
    # batch itself is indivisible; the bubble estimate below depends
    # only on the schedule shape and must still run
    if b is not None and not indivisible and dshard > 1 \
            and (b // pp_m) % dshard != 0:
        report.add(
            "pipeline:microbatch-indivisible", "warning",
            f"microbatch size {b // pp_m} (batch {b} / "
            f"pp_microbatches {pp_m}) is not divisible by the data-shard "
            f"product {dshard} — lower pp_microbatches or raise the batch",
            where="DistStrategy.pp_microbatches", batch=b,
            pp_microbatches=pp_m, data_shards=dshard)
    p = (mesh.shape["pp"] if mesh is not None
         and "pp" in getattr(mesh, "axis_names", ()) else 1)
    if p > 1:
        from ..parallel.pipeline import bubble_fraction
        frac = bubble_fraction(p, pp_m, pp_v)
        sev = "warning" if frac > 0.2 else "info"
        report.add(
            "pipeline:bubble", sev,
            f"schedule bubble is {frac:.1%} of ticks (pp={p}, "
            f"microbatches={pp_m}, interleave={pp_v})"
            + (" — raise pp_microbatches (ideally a multiple of pp) or "
               "pp_interleave (V× less bubble, V× more neighbor-hop "
               "activation traffic)" if frac > 0.2 else ""),
            where="DistStrategy.pp_microbatches", bubble_fraction=frac,
            pp=p, microbatches=pp_m, interleave=pp_v)


# --------------------------------------------------------------------------
# 8. HLO-level collective placement (optimized-HLO walk)
# --------------------------------------------------------------------------

_HLO_REDUCTIONS = frozenset({"all-reduce", "reduce-scatter", "all-gather",
                             "all-to-all"})
_HLO_PERMUTES = frozenset({"collective-permute", "collective-broadcast"})


def check_hlo_collectives(units, report: LintReport) -> None:
    """``collective:hlo-*`` — collective placement read off the
    OPTIMIZED HLO of the compiled step (``profiling.fusion`` units),
    catching what the jaxpr walk structurally cannot: collectives the
    GSPMD partitioner *inserted* (the per-microbatch gradient exchange
    is invisible pre-partitioning — ``collective:microbatch-exchange``
    infers it from config; this sees it directly).

    - ``collective:hlo-in-while`` (warning) — a reduction collective
      inside a compiled while-loop body pays its wire every iteration;
    - ``collective:hlo-unrolled-loop`` (warning) — N>1 copies of the
      same source-level exchange whose op_name path shows a loop body:
      XLA unrolled the loop, the per-iteration cost is now N× visible
      instances (how XLA:CPU compiles small scans);
    - ``collective:hlo-permute-in-while`` (info) — in-loop neighbor
      permutes, the deliberate ring/pipeline structure, with bytes."""
    from collections import Counter

    unrolled: Counter = Counter()
    unrolled_bytes: Dict[Any, int] = {}
    for u in units:
        src = u.source_ops[0] if u.source_ops else ""
        if u.op in _HLO_REDUCTIONS and u.in_loop:
            report.add(
                "collective:hlo-in-while", "warning",
                f"{u.op} ({u.out_bytes / 1e6:.3f} MB result, source "
                f"{src or 'unknown'}) inside compiled while-loop body "
                f"{u.computation!r} — the partitioned executable pays this "
                "exchange EVERY iteration (×trip count wire); hoist the "
                "exchange out of the loop "
                "(DistStrategy.accum_exchange='hoisted') or confirm it is "
                "deliberate schedule structure",
                where=f"{u.computation}/{u.name}",
                payload_bytes=u.out_bytes, source=src)
        elif u.op in _HLO_PERMUTES and u.in_loop:
            report.add(
                "collective:hlo-permute-in-while", "info",
                f"{u.op} ({u.out_bytes / 1e6:.3f} MB, source "
                f"{src or 'unknown'}) inside while body {u.computation!r} "
                "— expected for ring/pipeline schedules",
                where=f"{u.computation}/{u.name}",
                payload_bytes=u.out_bytes, source=src)
        elif u.op in _HLO_REDUCTIONS and "while/body" in src:
            key = (u.op, src)
            unrolled[key] += 1
            unrolled_bytes[key] = unrolled_bytes.get(key, 0) + u.out_bytes
    for (op, src), n in sorted(unrolled.items()):
        if n <= 1:
            continue  # one instance = likely the hoisted/final exchange
        total = unrolled_bytes[(op, src)]
        report.add(
            "collective:hlo-unrolled-loop", "warning",
            f"{n} copies of {op} from loop-body source {src!r} "
            f"({total / 1e6:.3f} MB total) — XLA unrolled the loop, so "
            f"the per-iteration exchange is paid {n}×; same fix as "
            "collective:hlo-in-while",
            where=src, op=op, instances=n, payload_bytes=total, source=src)


# --------------------------------------------------------------------------
# 9. feed wire-format candidates
# --------------------------------------------------------------------------

# first-use primitives that prove a feed value is only ever cast or
# affinely renormalized before real compute touches it — the static
# evidence it could cross the link in a narrower wire dtype and decode
# on device (data/wire.py) with identical results
_WIRE_FIRST_USES = frozenset({"convert_element_type", "add", "sub", "mul",
                              "div"})


def _is_const_like(var, constvar_ids, producers, _depth: int = 0) -> bool:
    """Literal, trace-time constant, or a broadcast/convert chain over
    one — the "other operand" shape of a normalize like (x-127)/64."""
    from .walker import is_literal

    if _depth > 8:
        return False
    if is_literal(var) or id(var) in constvar_ids:
        return True
    eqn = producers.get(id(var))
    if eqn is not None and eqn.primitive.name in ("broadcast_in_dim",
                                                  "convert_element_type",
                                                  "reshape"):
        return _is_const_like(eqn.invars[0], constvar_ids, producers,
                              _depth + 1)
    return False


def check_feed_wire(closed_flat, invar_names, report: LintReport,
                    already_wired=()) -> None:
    """``feed:wire-candidate`` — a float32 feed input whose every
    first use is a dtype cast or a constant affine normalize
    (``(x - mean) / std`` and friends): the program itself proves the
    field could ship as uint8 (quantized) or bf16 (truncated) wire —
    4×/2× fewer host→device bytes — with the decode fused into the step
    for free. Fields already covered by the trainer's ``feed_wire``
    table are skipped; integer feeds (labels/ids) are never candidates.
    """
    jaxpr = closed_flat.jaxpr
    constvar_ids = {id(v) for v in getattr(jaxpr, "constvars", ())}
    producers = producer_map(jaxpr)
    all_eqns = list(iter_eqns(jaxpr))
    for var, (kind, name) in zip(jaxpr.invars, invar_names):
        if kind not in ("arg", "kwarg") or name in already_wired:
            continue
        aval = getattr(var, "aval", None)
        dt = _np_dtype(getattr(aval, "dtype", None)) if aval is not None else None
        if dt != np.float32:
            continue
        consumers = [eqn for eqn, _path in all_eqns
                     if any(iv is var for iv in eqn.invars)]
        if not consumers:
            continue  # dead feed: not this rule's finding
        casts_only = True
        for eqn in consumers:
            pname = eqn.primitive.name
            if pname not in _WIRE_FIRST_USES:
                casts_only = False
                break
            if pname != "convert_element_type":
                others = [iv for iv in eqn.invars if iv is not var]
                if not all(_is_const_like(iv, constvar_ids, producers)
                           for iv in others):
                    casts_only = False
                    break
        if not casts_only:
            continue
        nbytes = aval_bytes(aval)
        arithmetic = any(e.primitive.name != "convert_element_type"
                         for e in consumers)
        suggestion = ("WireSpec.quantize('uint8', scale, zero_point) — ~4x"
                      if arithmetic else "WireSpec.cast('bfloat16') — 2x")
        report.add(
            "feed:wire-candidate", "info",
            f"feed {name!r} (float32, {nbytes / 1e6:.3f} MB/batch) is only "
            f"cast/normalized before use ({sorted({e.primitive.name for e in consumers})}) "
            f"— it can cross the host→device link in a narrower wire dtype "
            f"with the decode fused into the step: {suggestion} fewer wire "
            "bytes (Trainer(feed_wire={...}), data/wire.py). Never quantize "
            "label/id fields.",
            where=name, bytes_per_batch=nbytes,
            first_uses=sorted({e.primitive.name for e in consumers}))


def check_cacheable_dataset(sample_feed, feed_wire, num_epochs,
                            dataset_batches, residual_hbm_bytes,
                            report: LintReport,
                            cache_enabled: bool = False) -> None:
    """``feed:cacheable-dataset`` — a multi-epoch ``fit`` whose
    dataset's ENCODED wire bytes (``dataset_batches`` ×
    ``feed_wire_nbytes`` of the sample batch) fit the residual-HBM
    estimate (device budget minus the advisor's params + opt state +
    activations appetite), running with the device cache OFF: every
    epoch after the first re-sends bytes the device could simply keep
    (``fit(device_cache=True)``, data/device_cache.py). Advisory
    severity, like ``feed:wire-candidate`` — the reader must be
    epoch-stable for the cache to be sound, which only the caller
    knows."""
    if cache_enabled or not num_epochs or int(num_epochs) <= 1:
        return
    if not dataset_batches or residual_hbm_bytes is None \
            or not sample_feed:
        return
    from ..data.wire import feed_wire_nbytes
    per_batch = feed_wire_nbytes(sample_feed, feed_wire)
    total = per_batch * int(dataset_batches)
    if total <= 0 or total > int(residual_hbm_bytes):
        return
    report.add(
        "feed:cacheable-dataset", "info",
        f"{num_epochs}-epoch fit streams the full dataset "
        f"({dataset_batches} batches × {per_batch / 1e6:.3f} MB wire = "
        f"{total / 1e6:.1f} MB) across the host→device link EVERY "
        f"epoch, but it fits the {residual_hbm_bytes / 1e6:.1f} MB "
        "residual-HBM estimate — fit(device_cache=True) would keep the "
        "encoded epoch on device and feed epoch 2+ device-to-device "
        "with zero h2d bytes (requires an epoch-stable reader; see "
        "MIGRATION.md \"Device-resident data path\")",
        where="device_cache", dataset_wire_bytes=int(total),
        residual_hbm_bytes=int(residual_hbm_bytes),
        num_epochs=int(num_epochs), dataset_batches=int(dataset_batches))


# --------------------------------------------------------------------------
# 10. MoE routing capacity
# --------------------------------------------------------------------------


def _phi(z: float) -> float:
    import math
    return math.exp(-0.5 * z * z) / math.sqrt(2.0 * math.pi)


def _Phi(z: float) -> float:
    import math
    return 0.5 * (1.0 + math.erf(z / math.sqrt(2.0)))


def expected_moe_drop_rate(tokens: int, top_k: int, num_experts: int,
                           capacity: int) -> float:
    """Expected fraction of routed (token, choice) assignments dropped
    by the static per-expert capacity, under the *uniform random
    routing* model (each of the ``tokens * top_k`` assignments lands on
    one of ``num_experts`` experts independently — what an untrained or
    collapsed router looks like; the load-balance aux loss pushes
    TOWARD this distribution, so it is the right static prior).

    Per-expert load L ~ Binomial(T=tokens*top_k, 1/E); expected overflow
    is E[max(L - C, 0)], evaluated with the normal approximation
    ``(mu - C) * Phi(-z) + sigma * phi(z)``, ``z = (C - mu) / sigma``.
    The total drop rate is ``E * overflow / T``. Exact at the
    deterministic limit (sigma -> 0: rate = max(mu - C, 0) * E / T,
    i.e. ``1 - capacity_factor`` for capacity_factor < 1)."""
    import math
    t_assign = tokens * top_k
    if t_assign <= 0 or num_experts <= 0:
        return 0.0
    p = 1.0 / num_experts
    mu = t_assign * p
    var = t_assign * p * (1.0 - p)
    if var <= 0.0:
        overflow = max(mu - capacity, 0.0)
    else:
        sigma = math.sqrt(var)
        z = (capacity - mu) / sigma
        overflow = (mu - capacity) * _Phi(-z) + sigma * _phi(z)
    rate = num_experts * max(overflow, 0.0) / t_assign
    return min(max(rate, 0.0), 1.0)


def check_moe_capacity(moe_configs, report: LintReport,
                       drop_threshold: float = 0.05) -> None:
    """``moe:capacity`` — a routed-expert layer whose static
    ``capacity_factor``/``top_k`` combo implies an expected token drop
    rate above ``drop_threshold``. Dropped tokens pass through the MoE
    block with a zero combine weight — silent quality loss that no
    runtime error ever surfaces; the capacity is fully determined by
    the traced shapes (``parallel.moe`` computes it before any device
    work), so this is knowable before the first step.

    ``moe_configs`` is the record list a
    ``parallel.moe.capture_moe_configs()`` block collected around the
    program trace."""
    for cfg in moe_configs or ():
        rate = expected_moe_drop_rate(cfg["tokens"], cfg["top_k"],
                                      cfg["num_experts"], cfg["capacity"])
        if rate <= drop_threshold:
            continue
        lever = (f"raise capacity_factor above "
                 f"{cfg['capacity_factor']:g} (capacity scales "
                 "linearly) or lower top_k")
        report.add(
            "moe:capacity", "warning",
            f"expert capacity {cfg['capacity']} (capacity_factor="
            f"{cfg['capacity_factor']:g}, top_k={cfg['top_k']}, "
            f"{cfg['num_experts']} experts, {cfg['tokens']} tokens"
            + (f"/device over ep={cfg['ep']}" if cfg.get("ep", 1) > 1
               else "")
            + f") drops an expected {rate:.1%} of routed tokens under "
            f"uniform routing (threshold {drop_threshold:.1%}) — dropped "
            f"tokens skip the expert FFN with zero combine weight, a "
            f"silent quality loss; {lever}",
            where=cfg.get("name", "moe"),
            expected_drop_rate=rate,
            capacity=cfg["capacity"], top_k=cfg["top_k"],
            num_experts=cfg["num_experts"], tokens=cfg["tokens"],
            capacity_factor=cfg["capacity_factor"])


# --------------------------------------------------------------------------
# 11. replicated optimizer state (the ZeRO trigger)
# --------------------------------------------------------------------------


def check_replicated_optstate(params, opt_state, mesh, rules,
                              report: LintReport,
                              replicated_optstate_bytes: int = 64 << 20,
                              zero_sharding: bool = False) -> None:
    """``sharding:replicated-optstate`` — per-parameter optimizer
    accumulators (Adam moments etc.) that every device along a
    data-parallel axis holds a full copy of, totalling more than
    ``replicated_optstate_bytes`` per device.

    In this framework optimizer accums inherit their parameter's
    sharding spec (``parallel.api.shard_scope``), and data axes shard
    only the batch — so under plain dp the ENTIRE optimizer state is
    replicated N ways. That is exactly the redundancy the ZeRO /
    cross-replica-sharded weight update removes (each replica owns a
    1/N shard of opt state, all-gathers fresh params once per step):
    this lint is the static trigger for that optimization.

    With ``zero_sharding=True`` (``DistStrategy.zero_sharding`` — the
    optimization has been APPLIED) the trigger goes quiet and the
    companion info verdict ``sharding:zero-active`` reports the
    REALIZED per-device opt-state bytes instead (from the live arrays'
    shard shapes, not a projection)."""
    if mesh is None or opt_state is None or not params:
        return
    from ..parallel import mesh as mesh_lib

    data_axes = tuple(a for a in mesh_lib.data_axis_names(mesh)
                      if mesh.shape[a] > 1)
    data_n = mesh_lib.data_parallel_size(mesh)
    if data_n <= 1:
        return
    if zero_sharding:
        per_dev = 0
        leaves = 0
        for v in jax.tree.leaves(opt_state):
            shape = tuple(getattr(v, "shape", ()))
            sharding = getattr(v, "sharding", None)
            local = (sharding.shard_shape(shape)
                     if sharding is not None and shape else shape)
            per_dev += int(np.prod(local or (1,))) * np.dtype(v.dtype).itemsize
            leaves += 1
        axes_desc = "x".join(f"{a}={mesh.shape[a]}" for a in data_axes)
        report.add(
            "sharding:zero-active", "info",
            f"ZeRO weight-update sharding is on: optimizer state is "
            f"partitioned 1/{data_n} across the data axis ({axes_desc}) "
            f"— {per_dev / 1e6:.1f} MB/device realized across "
            f"{leaves} leaves",
            where="opt_state",
            opt_state_bytes_per_device=int(per_dev),
            data_shards=data_n, leaves=leaves)
        return
    from ..parallel.api import _rules as _adapt
    table = _adapt(rules, mesh)
    data_axis_set = set(data_axes)
    repl_bytes = 0.0   # per-device bytes carrying data-axis redundancy
    saved_bytes = 0.0  # what a ZeRO 1/data_n shard would reclaim
    leaves = 0
    for pname, acc in (opt_state.get("accums") or {}).items():
        if pname not in params:
            continue
        pshape = tuple(params[pname].shape)
        spec = table.spec_for(pname, pshape, mesh)
        spec_axes = [a for e in spec if e is not None
                     for a in (e if isinstance(e, tuple) else (e,))
                     if a in mesh.axis_names]
        sharded_n = int(np.prod([mesh.shape[a] for a in spec_axes] or [1]))
        sharded_data_n = int(np.prod([mesh.shape[a] for a in spec_axes
                                      if a in data_axis_set] or [1]))
        for v in jax.tree.leaves(acc):
            shape = tuple(getattr(v, "shape", ()))
            nbytes = int(np.prod(shape or (1,))) * np.dtype(v.dtype).itemsize
            # only leaves sharing the param's shape inherit its spec
            # (shard_scope's contract); scalars/step counters replicate
            inherit = shape == pshape
            per_dev = nbytes / (sharded_n if inherit else 1)
            # redundancy is what remains across the data axes AFTER the
            # spec's own data-axis sharding: an fsdp-style rule that
            # already shards along a data axis carries none there
            repl = data_n // (sharded_data_n if inherit else 1)
            if repl <= 1:
                continue
            repl_bytes += per_dev
            saved_bytes += per_dev * (repl - 1) / repl
            leaves += 1
    if leaves == 0 or repl_bytes < replicated_optstate_bytes:
        return
    axes_desc = "x".join(f"{a}={mesh.shape[a]}" for a in data_axes)
    report.add(
        "sharding:replicated-optstate", "warning",
        f"{repl_bytes / 1e6:.1f} MB/device of optimizer state "
        f"({leaves} accumulator tensors) is replicated across the "
        f"{data_n}-way data axis ({axes_desc}) — a ZeRO-style "
        f"cross-replica sharded update (each replica owns a 1/{data_n} "
        f"shard of opt state and the update, params all-gathered once "
        f"per step) reclaims {saved_bytes / 1e6:.1f} MB/device of HBM",
        where="opt_state",
        replicated_bytes_per_device=int(repl_bytes),
        zero_saving_bytes=int(saved_bytes),
        data_shards=data_n, leaves=leaves)
