"""Fusion-level attribution of a compiled step program.

"Operator Fusion in XLA" (PAPERS.md) shows step time on XLA backends is
only explainable at the *optimized-HLO fusion* level — the jaxpr the
``analysis`` lints walk is pre-fusion, so a bench regression or an HBM
blowup has no name there. This module parses the compiled executable's
optimized HLO text (the same artifact ``debugger.program_hlo(
optimized=True)`` dumps) into per-fusion **units**, attributes bytes
and FLOPs to each, maps every fusion back to the source-level op names
XLA recorded in its ``metadata={op_name=...}``, and names the top-k by
a roofline cost estimate.

Design notes:

- The parse is TEXT-level on purpose: the HLO module protobuf API is
  not stable across jaxlib pins, the text form is (it is the format
  XLA's own tools consume), and ``debugger._parse_hlo_collectives``
  set the precedent.
- A **unit** is one instruction of an *executed-in-place* computation:
  the ENTRY computation, while bodies/conditions, and conditional
  branches. Computations absorbed into a caller (``calls=`` fusions,
  ``to_apply=`` reducers) are folded into the calling instruction's
  FLOPs — a fusion's cost is the whole fused subgraph's.
- Bytes per unit = operand bytes + result bytes: exactly the HBM
  traffic a fusion pays (its internals live in registers/vmem) — the
  quantity the paper shows dominates fusion runtime.
- FLOPs are analytic (dot/conv from shapes + contracting dims, one per
  output element for elementwise/transcendental) so the numbers exist
  on every backend; the XLA aggregate ``cost_analysis()`` totals ride
  along for cross-checking when the backend exposes them.
- Instructions inside while bodies are tagged ``in_loop`` — their
  static cost counts ONE iteration (the trip count is not in the HLO
  text); the fused K-step program's model body shows up this way.
"""

from __future__ import annotations

import dataclasses
import re
from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

# dtype byte widths as HLO spells them (shared convention with
# debugger._DTYPE_BYTES; duplicated literally so neither module imports
# the other at module scope)
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_ELEM_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
# params may be tuple-typed — "(param.26: (s32[], f32[8,10]))" — so the
# arg list is matched greedily up to the "->"
_COMP_HEAD_RE = re.compile(
    r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*\S.*\{\s*$")
_INSTR_HEAD_RE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_ARRAY_SHAPE_RE = re.compile(r"\w+\[[^\]]*\](?:\{[^}]*\})?")
_OPCODE_RE = re.compile(r"\s*([\w\-]+)\(")
_OPERAND_SHAPE_RE = re.compile(r"(\w+\[[0-9,]*\])(?:\{[^}]*\})?\s+%")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
_CALLS_RE = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_BODY_RE = re.compile(
    r"(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_DIMS_RE = {
    "lhs_contracting": re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}"),
    "lhs_batch": re.compile(r"lhs_batch_dims=\{([0-9,]*)\}"),
}
_KIND_RE = re.compile(r"kind=k(\w+)")
_TARGET_RE = re.compile(r'custom_call_target="([^"]*)"')
_DIM_LABELS_RE = re.compile(r"dim_labels=([\w?]+)_([\w?]+)->([\w?]+)")

# ops that move/alias data without arithmetic
_ZERO_FLOP_OPS = frozenset({
    "parameter", "constant", "broadcast", "reshape", "bitcast", "copy",
    "copy-start", "copy-done", "transpose", "tuple", "get-tuple-element",
    "iota", "slice", "dynamic-slice", "dynamic-update-slice", "concatenate",
    "pad", "reverse", "gather", "scatter", "after-all", "partition-id",
    "replica-id", "rng-bit-generator", "optimization-barrier", "domain",
    "send", "send-done", "recv", "recv-done", "infeed", "outfeed",
})

_COLLECTIVE_OPS = frozenset({
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
})

# HBM bandwidth table (bytes/s) for the roofline ranking, keyed like
# flops._PEAK_BF16 by device_kind substring (public TPU spec sheets).
_HBM_BW = [
    ("v6 lite", 1640e9), ("v6e", 1640e9),
    ("v5 lite", 819e9), ("v5e", 819e9),
    ("v5p", 2765e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
]
# unknown backends (CPU in CI): fixed constants — the report only needs
# RELATIVE cost for ranking, and fixed values keep it deterministic
_FALLBACK_PEAK = 5e12
_FALLBACK_BW = 100e9


def _shape_bytes(s: str) -> int:
    """Total byte size of every array inside an HLO shape string."""
    total = 0
    for m in _SHAPE_ELEM_RE.finditer(s):
        dt, dims = m.group(1), m.group(2)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def _shape_elems(s: str) -> int:
    """Element count of the FIRST array in an HLO shape string."""
    m = _SHAPE_ELEM_RE.search(s)
    if not m:
        return 0
    n = 1
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    return n


def _shape_dims(s: str) -> Tuple[int, ...]:
    m = _SHAPE_ELEM_RE.search(s)
    if not m:
        return ()
    return tuple(int(d) for d in m.group(2).split(",") if d)


def _operand_segment(line: str, op_end: int) -> str:
    """The operand text between the opcode's parens (handles nested
    tuple-typed operands)."""
    depth = 0
    for i in range(op_end - 1, len(line)):
        c = line[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return line[op_end:i]
    return line[op_end:]


@dataclasses.dataclass
class Instruction:
    name: str
    opcode: str
    shape: str                    # result shape string
    operand_shapes: List[str]
    attrs: str                    # text after the operand parens
    op_name: str = ""             # metadata op_name (source mapping)
    operands: Tuple[str, ...] = ()  # operand instruction names

    @property
    def out_bytes(self) -> int:
        return _shape_bytes(self.shape)

    @property
    def operand_bytes(self) -> int:
        return sum(_shape_bytes(s) for s in self.operand_shapes)


@dataclasses.dataclass
class Computation:
    name: str
    is_entry: bool
    instructions: List[Instruction]


@dataclasses.dataclass
class Unit:
    """One attributable cost unit: an instruction of an executed
    computation, with any absorbed (fused / reducer) computations'
    FLOPs folded in."""

    name: str
    op: str                       # opcode ("-start" stripped for async)
    kind: str                     # fusion kind (loop/input/output) or op
    computation: str              # computation the instruction lives in
    in_loop: bool                 # computation is (inside) a while body
    flops: float
    bytes: int                    # operand + result bytes (HBM traffic)
    out_bytes: int
    source_ops: List[str]         # cleaned metadata op_names, ranked
    cost: float = 0.0             # roofline seconds estimate
    cost_frac: float = 0.0

    @property
    def key(self) -> str:
        """Stable identity for cross-run diffing: top source op +
        opcode + result shape (instruction NAMES are not stable across
        compiles; source structure is)."""
        src = self.source_ops[0] if self.source_ops else ""
        return f"{self.op}|{src}|{self.shape_sig}"

    shape_sig: str = ""


def _match_instruction(line: str):
    """``(name, result shape, opcode, end of "opcode(")`` of an instruction
    line, or None. A tuple-typed result may nest to any depth (an
    asynchronous ``slice-start`` gives ``((f32[..]{..T(8,128)}), f32[..],
    s32[])``), so its parentheses are counted, not matched by pattern."""
    head = _INSTR_HEAD_RE.match(line)
    if not head:
        return None
    at = head.end()
    if line.startswith("(", at):
        depth, end = 0, at
        for end in range(at, len(line)):
            depth += {"(": 1, ")": -1}.get(line[end], 0)
            if depth == 0:
                break
        else:
            return None
        end += 1
    else:
        shape = _ARRAY_SHAPE_RE.match(line, at)
        if not shape:
            return None
        end = shape.end()
    op = _OPCODE_RE.match(line, end)
    if not op:
        return None
    return head.group(1), line[at:end], op.group(1), op.end()


def parse_hlo_module(text: str) -> Dict[str, Computation]:
    """Parse optimized-HLO text into ``{name: Computation}``."""
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in text.splitlines():
        if cur is None:
            m = _COMP_HEAD_RE.match(line)
            if m:
                cur = Computation(name=m.group(2),
                                  is_entry=m.group(1) is not None,
                                  instructions=[])
            continue
        if line.startswith("}"):
            comps[cur.name] = cur
            cur = None
            continue
        m = _match_instruction(line)
        if not m:
            continue
        name, shape, opcode, end = m
        seg = _operand_segment(line, end)
        operands = [s.group(1) for s in _OPERAND_SHAPE_RE.finditer(seg)]
        attrs = line[end + len(seg):]
        op_name = ""
        nm = _OP_NAME_RE.search(line)
        if nm:
            op_name = nm.group(1)
        cur.instructions.append(Instruction(
            name=name, opcode=opcode, shape=shape,
            operand_shapes=operands, attrs=attrs, op_name=op_name,
            operands=tuple(_OPERAND_NAME_RE.findall(seg))))
    if cur is not None:  # unterminated tail (defensive)
        comps[cur.name] = cur
    return comps


def _instr_flops(ins: Instruction) -> float:
    """Analytic FLOPs of one instruction (undercount-never-overcount,
    the core/flops.py convention): matmul/conv from shapes, one FLOP
    per output element for elementwise/transcendental math, zero for
    data movement."""
    op = ins.opcode
    if op in _ZERO_FLOP_OPS or op in ("fusion", "while", "conditional",
                                     "call", "reduce", "reduce-window",
                                     "sort", "custom-call", "select-and-scatter"):
        # handled by the caller (absorbed computations) or below
        if op == "reduce" or op == "reduce-window":
            return float(sum(_shape_elems(s) for s in ins.operand_shapes))
        if op == "custom-call":
            return _custom_call_flops(ins)
        return 0.0
    out = float(_shape_elems(ins.shape))
    if op == "dot":
        m = _DIMS_RE["lhs_contracting"].search(ins.attrs)
        contract = 1
        if m and ins.operand_shapes:
            lhs = _shape_dims(ins.operand_shapes[0])
            for d in m.group(1).split(","):
                if d and int(d) < len(lhs):
                    contract *= lhs[int(d)]
        return 2.0 * out * contract
    if op == "convolution":
        if len(ins.operand_shapes) >= 2:
            kernel = _shape_dims(ins.operand_shapes[1])
            ktotal = float(np.prod(kernel or (1,)))
            dl = _DIM_LABELS_RE.search(ins.attrs)
            cout = 1.0
            if dl and kernel:
                o_idx = dl.group(2).find("o")
                if 0 <= o_idx < len(kernel):
                    cout = float(kernel[o_idx])
            return 2.0 * out * ktotal / max(cout, 1.0)
        return 2.0 * out
    # elementwise / compare / transcendental / convert / rng ...
    return out


def _custom_call_flops(ins: Instruction) -> float:
    """Backend library calls (oneDNN matmul on CPU, cublas on GPU):
    recover matmul FLOPs heuristically from two rank-2 operands."""
    t = _TARGET_RE.search(ins.attrs)
    target = t.group(1).lower() if t else ""
    if any(k in target for k in ("matmul", "gemm", "dot")):
        shapes = [_shape_dims(s) for s in ins.operand_shapes[:2]]
        if len(shapes) == 2 and all(len(s) >= 2 for s in shapes):
            k = shapes[0][-1]
            return 2.0 * _shape_elems(ins.shape) * k
    return 0.0


def _referenced(ins: Instruction, kind: str) -> List[str]:
    """Computations ``ins`` references, split by execution class:
    ``absorb`` = folded into this instruction's cost (fusion ``calls=``,
    reducer ``to_apply=``); ``control`` = executed in place, their
    instructions are units of their own (while bodies/conditions,
    conditional branches, and ``call`` targets — XLA:CPU unrolls small
    scans into ``call`` computations, whose collectives/fusions must
    not vanish into one opaque call unit)."""
    calls = _CALLS_RE.findall(ins.attrs)
    control = _BODY_RE.findall(ins.attrs)
    b = _BRANCH_RE.search(ins.attrs)
    if b:
        control += [n.strip().lstrip("%") for n in b.group(1).split(",")
                    if n.strip()]
    if ins.opcode == "call":
        control += calls
        calls = []
    return calls if kind == "absorb" else control


def _clean_op_name(op_name: str) -> str:
    """Source mapping: drop jit(...) scope wrappers from the recorded
    op_name path and keep the informative tail (``transpose(jvp(...))``
    components are kept — they distinguish backward from forward).
    Loop-body membership must survive the truncation — the
    ``collective:hlo-unrolled-loop`` lint keys on ``while/body`` in the
    cleaned source — so a dropped ``while`` prefix is re-marked."""
    parts = [p for p in op_name.split("/")
             if p and not re.fullmatch(r"jit\(.*\)", p)]
    if not parts:
        return op_name
    name = "/".join(parts[-3:])
    if "while" in parts[:-3]:
        name = "while/body/" + name
    return name


def _comp_metrics(comps: Dict[str, Computation]):
    """Per-computation absorbed totals: (flops, source-op counter),
    folding in computations referenced via calls=/to_apply=."""
    memo: Dict[str, Tuple[float, Counter]] = {}

    def total(name: str, stack=()) -> Tuple[float, Counter]:
        if name in memo:
            return memo[name]
        if name in stack or name not in comps:
            return 0.0, Counter()
        f, names = 0.0, Counter()
        for ins in comps[name].instructions:
            f += _instr_flops(ins)
            if ins.op_name and ins.opcode not in ("parameter", "constant"):
                names[_clean_op_name(ins.op_name)] += 1
            for sub in _referenced(ins, "absorb"):
                sf, sn = total(sub, stack + (name,))
                f += sf
                names += sn
        memo[name] = (f, names)
        return memo[name]

    return total


def executed_computations(comps: Dict[str, Computation]) -> Dict[str, bool]:
    """``{computation: in_loop}`` of the computations executed in place:
    the entry, while bodies and conditions, conditional branches and
    ``call`` targets, walked from the entry so nested whiles inherit loop
    membership. Computations absorbed into a caller (``calls=`` fusions,
    ``to_apply=`` reducers) are not among them."""
    absorbed = set()
    control: Dict[str, bool] = {}    # name -> in_loop
    for comp in comps.values():
        for ins in comp.instructions:
            for sub in _referenced(ins, "absorb"):
                absorbed.add(sub)
    entry = [c for c in comps.values() if c.is_entry]
    # walk the control-flow tree from entry so nested whiles inherit
    # loop membership; anything absorbed never becomes a unit source
    stack = [(c.name, False) for c in entry]
    seen = set()
    while stack:
        name, in_loop = stack.pop()
        if name in seen or name not in comps or name in absorbed:
            # absorbed computations' FLOPs are folded into their
            # calling unit — visiting one via a control edge too would
            # double-count it
            continue
        seen.add(name)
        control[name] = in_loop
        for ins in comps[name].instructions:
            is_while = ins.opcode == "while"
            for sub in _referenced(ins, "control"):
                stack.append((sub, in_loop or is_while))
    return control


def module_units(comps: Dict[str, Computation]) -> List[Unit]:
    """Flatten a parsed module into cost units: instructions of the
    entry computation plus while bodies/conditions and conditional
    branches (tagged ``in_loop`` when under a while), with absorbed
    fusion/reducer computations folded into their calling unit."""
    control = executed_computations(comps)
    total = _comp_metrics(comps)
    units: List[Unit] = []
    for name, in_loop in control.items():
        for ins in comps[name].instructions:
            if ins.opcode in ("parameter", "constant", "tuple",
                              "get-tuple-element", "bitcast", "after-all"):
                continue
            if ins.opcode in ("while", "conditional", "call"):
                # container: its body's instructions are their own units
                continue
            flops = _instr_flops(ins)
            names: Counter = Counter()
            if ins.op_name:
                names[_clean_op_name(ins.op_name)] += 1
            for sub in _referenced(ins, "absorb"):
                sf, sn = total(sub)
                flops += sf
                names += sn
            km = _KIND_RE.search(ins.attrs)
            op = ins.opcode
            if op.endswith("-start"):
                op = op[:-len("-start")]
            elif op.endswith("-done"):
                continue  # async second half: counted at -start
            units.append(Unit(
                name=ins.name, op=op,
                kind=(km.group(1).lower() if km else op),
                computation=name, in_loop=in_loop,
                flops=flops,
                bytes=ins.operand_bytes + ins.out_bytes,
                out_bytes=ins.out_bytes,
                source_ops=[n for n, _ in names.most_common(4)],
                shape_sig=re.sub(r"\{[^}]*\}", "", ins.shape),
            ))
    return units


def _device_roofline(device=None) -> Tuple[float, float, str]:
    """(peak FLOP/s, HBM bytes/s, source) for the ranking roofline.
    Table-driven and fixed-fallback so reports are deterministic."""
    import jax

    device = device or jax.devices()[0]
    kind = getattr(device, "device_kind", "").lower()
    from ..core.flops import _PEAK_BF16
    peak = next((p for sub, p in _PEAK_BF16 if sub in kind), _FALLBACK_PEAK)
    bw = next((b for sub, b in _HBM_BW if sub in kind), _FALLBACK_BW)
    src = "table" if kind and any(s in kind for s, _ in _HBM_BW) else "fallback"
    return peak, bw, src


def attribute_units(units: List[Unit], peak_flops: float,
                    mem_bw: float) -> List[Unit]:
    """Assign each unit its roofline cost estimate and cost fraction;
    returns units sorted most-expensive first (ties broken by the
    stable key so the ordering is deterministic)."""
    for u in units:
        u.cost = max(u.flops / peak_flops, u.bytes / mem_bw)
    total = sum(u.cost for u in units) or 1.0
    for u in units:
        u.cost_frac = u.cost / total
    return sorted(units, key=lambda u: (-u.cost, u.key))


def _xla_cost_totals(compiled) -> Dict[str, Optional[float]]:
    """Aggregate XLA cost_analysis totals (None when the backend hides
    them); handles the list-of-dicts and plain-dict API shapes."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {"xla_flops": None, "xla_bytes_accessed": None}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {"xla_flops": None, "xla_bytes_accessed": None}
    return {"xla_flops": ca.get("flops"),
            "xla_bytes_accessed": ca.get("bytes accessed")}


def unit_row(u: Unit) -> Dict[str, Any]:
    """JSON-ready rendering of one unit (a ``top_fusions`` row;
    ``key`` is stable across compiles of the same program)."""
    return {
        "key": u.key,
        "name": u.name,
        "op": u.op,
        "kind": u.kind,
        "computation": u.computation,
        "in_loop": u.in_loop,
        "flops": float(u.flops),
        "bytes": int(u.bytes),
        "out_bytes": int(u.out_bytes),
        "source_ops": list(u.source_ops),
        "cost_frac": round(float(u.cost_frac), 6),
    }


def fusion_report_from_text(text: str, top_k: int = 8, device=None,
                            compiled=None) -> Dict[str, Any]:
    """The fusion report over already-dumped optimized HLO text."""
    comps = parse_hlo_module(text)
    units = module_units(comps)
    peak, bw, src = _device_roofline(device)
    units = attribute_units(units, peak, bw)
    top = units[:max(1, int(top_k))]
    out = {
        "n_units": len(units),
        "n_in_loop": sum(1 for u in units if u.in_loop),
        "total_flops": float(sum(u.flops for u in units)),
        "total_bytes": int(sum(u.bytes for u in units)),
        "peak_flops": peak,
        "mem_bw": bw,
        "roofline_source": src,
        "top_fusions": [unit_row(u) for u in top],
        "coverage_top_k": round(sum(u.cost_frac for u in top), 6),
    }
    if compiled is not None:
        out.update(_xla_cost_totals(compiled))
    else:
        out.update({"xla_flops": None, "xla_bytes_accessed": None})
    return out


def fusion_report(trainer, feed, top_k: int = 8) -> Dict[str, Any]:
    """Fusion-level cost attribution of the Trainer's compiled train
    step for the current scope + feed shapes: parses the optimized HLO
    (the executable XLA actually runs), folds fused computations into
    their fusion instruction, and names the top-k units by roofline
    cost with their bytes, FLOPs and source-level op names.

    Note this explicitly re-lowers and re-compiles the step program
    (the jit call path's executable is not reachable from Python) —
    same cost profile as ``debugger.collective_report``. Enable the
    persistent compile cache (``compile_cache_dir``) to amortize."""
    from ..debugger import _lower_step

    compiled = _lower_step(trainer, feed).compile()
    dev = (trainer.mesh.devices.flat[0] if trainer.mesh is not None
           else trainer.place.device())
    rep = fusion_report_from_text(compiled.as_text(), top_k=top_k,
                                  device=dev, compiled=compiled)
    ma = None
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        rep["temp_mb"] = ma.temp_size_in_bytes / 1e6
    return rep


# -- instruction name -> the program's own scope --------------------------------

# name-stack components that say how an operation was reached, not which
# layer it belongs to
_STRUCTURAL = frozenset({
    "call_exported", "closed_call", "while", "cond", "checkpoint",
    "rematted_computation", "shard_map", "pjit", "custom_jvp_call",
    "custom_vjp_call", "custom_vjp_call_jaxpr", "core_call", "remat",
})
_BRANCH_N_RE = re.compile(r"branch_\d+(_fun)?$")
_UNPLACED_OPS = ("parameter", "constant")   # arguments and literals: no scope
_SCOPE_NAME_RE = re.compile(r"[\w.\-]+$")
_WRAPPED_RE = re.compile(r"^(\w+)\((.*)\)$")
_GROUPS_RE = re.compile(
    r"(replica_groups|source_target_pairs)=\{((?:\{[0-9,]*\},?)*)\}")
_IOTA_GROUPS_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")


@dataclasses.dataclass(frozen=True)
class ScopeRow:
    """Where one instruction of an executed computation belongs."""
    path: Tuple[str, ...]         # the named_scope components, in order
    remat: bool                   # of a checkpoint's second forward
    backward: bool                # reached through transpose(...)
    axes: Optional[str]           # a collective's mesh axes ("dp", "dp,tp",
                                  # "" without a mesh); None: no collective
    opcode: str
    op_name: str                  # its own, or its donor's where inherited
    inherited: bool = False       # no name stack of its own: placed where
                                  # the instruction it feeds (or is fed by) is


def _split_top(s: str, sep: str = "/") -> List[str]:
    """Split at ``sep`` outside parentheses."""
    out, depth, at = [], 0, 0
    for i, c in enumerate(s):
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif c == sep and depth == 0:
            out.append(s[at:i])
            at = i + 1
    out.append(s[at:])
    return out


def scope_path(op_name: str) -> Tuple[str, ...]:
    """The ``named_scope`` components of an ``op_name``, in order: the
    primitive (the last component), ``jit(...)`` / ``pjit(...)`` wrappers
    and the structural words are taken out, ``jvp(...)`` / ``transpose(...)``
    / ``vmap(...)`` are opened. ``jit(step)/transpose(jvp(gpt))/while/body/
    checkpoint/rematted_computation/attn/dot_general`` -> ``("gpt", "attn")``."""
    path: List[str] = []
    before = ""
    # XLA joins the names of merged instructions with ";": the first
    op_name = op_name.split(";", 1)[0]
    if "[" in op_name:      # an argument's own name (``params['gpt/h/w']``)
        return ()
    for part in _split_top(op_name)[:-1]:
        in_while, before = before == "while", part
        if in_while and part in ("body", "cond"):
            continue
        m = _WRAPPED_RE.match(part)
        while m:
            if m.group(1) in ("jit", "pjit"):
                part = ""
                break
            part = m.group(2)
            m = _WRAPPED_RE.match(part)
        path += [p for p in part.split("/")
                 if _SCOPE_NAME_RE.match(p) and p not in _STRUCTURAL
                 and not _BRANCH_N_RE.match(p)]
    return tuple(path)


def replica_groups(attrs: str, pairs: bool = False
                   ) -> Optional[List[List[int]]]:
    """The device groups of a collective's ``replica_groups``, explicit
    (``{{0,1},{2,3}}``) or iota (``[2,2]<=[4]``, ``[2,2]<=[2,2]T(1,0)``);
    ``[]`` for the empty ``{}`` that means every device; with ``pairs``
    also a ``collective-permute``'s ``source_target_pairs``. None where
    the text has none."""
    m = _IOTA_GROUPS_RE.search(attrs)
    if m:
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            ids = ids.transpose([int(x) for x in m.group(4).split(",")])
        return ids.reshape(int(m.group(1)), int(m.group(2))).tolist()
    for m in _GROUPS_RE.finditer(attrs):
        if pairs or m.group(1) == "replica_groups":
            return [[int(x) for x in g.split(",") if x]
                    for g in re.findall(r"\{([0-9,]*)\}", m.group(2))]
    return None


def collective_axes(attrs: str, mesh_axes: Sequence[Tuple[str, int]]
                    ) -> Optional[str]:
    """The mesh axes a collective's groups (``replica_groups`` with
    ``pairs``) run over: a member's number is its place in the mesh's
    devices, row-major, so its coordinates are ``unravel_index``; an axis
    counts where two members of one group differ along it. ``mesh_axes``:
    ``((name, size), ...)`` in the mesh's order; empty gives ``""``. An
    empty ``replica_groups={}`` is every device: every axis of size > 1."""
    if not mesh_axes:
        return ""
    names = [n for n, _ in mesh_axes]
    shape = tuple(s for _, s in mesh_axes)
    n = int(np.prod(shape))
    groups = replica_groups(attrs, pairs=True)
    if groups is None:
        return None
    varies = np.zeros(len(shape), bool)
    for g in groups or [list(range(n))]:
        if g and max(g) >= n:
            return None             # not this mesh's numbering
        coords = np.asarray(np.unravel_index(np.asarray(g, int), shape)).T
        if len(coords):
            varies |= (coords != coords[0]).any(axis=0)
    return ",".join(nm for nm, v in zip(names, varies) if v)


def scope_table(text: str, mesh_axes: Sequence[Tuple[str, int]] = ()
                ) -> Dict[str, ScopeRow]:
    """``{instruction name: ScopeRow}`` over every instruction of an
    executed computation of the optimized HLO ``text``. An instruction
    whose ``op_name`` is a name stack is placed by it; a fusion without
    one takes the (path, remat, backward) that most of its fused
    instructions carry, weighed by the roofline cost ``attribute_units``
    ranks by; any other instruction without one (the compiler's own
    copies, asynchronous slices and kernels) takes the place of the first
    instruction it feeds, else of one that feeds it: its row says
    ``inherited`` and carries the donor's ``op_name``. A fusion is indivisible:
    what XLA merged across two scopes lies whole under the one its
    metadata names. A collective (also an asynchronous ``-done``, through
    its ``-start``, and a fusion that holds one) gets the mesh axes its
    groups run over."""
    comps = parse_hlo_module(text)
    by_name = {i.name: i for c in comps.values() for i in c.instructions}

    def tags(op_name: str):
        return (scope_path(op_name), "rematted_computation" in op_name,
                "transpose(" in op_name)

    def fused(name: str, seen=()) -> List[Instruction]:
        if name not in comps or name in seen:
            return []
        out = []
        for ins in comps[name].instructions:
            out.append(ins)
            for sub in _referenced(ins, "absorb"):
                out += fused(sub, seen + (name,))
        return out

    def vote(ins: Instruction):
        """((path, remat, backward), op_name) of the costliest tags among
        the instructions fused into ``ins``, the op_name that of the
        costliest single one; ``((), False, False), ""`` without any."""
        cost_of: Dict[tuple, float] = {}
        top: Dict[tuple, Tuple[float, str]] = {}
        for sub in _referenced(ins, "absorb"):
            for f in fused(sub):
                if "/" in f.op_name and f.opcode not in _UNPLACED_OPS:
                    cost = max(_instr_flops(f) / _FALLBACK_PEAK,
                               (f.operand_bytes + f.out_bytes) / _FALLBACK_BW)
                    t = tags(f.op_name)
                    cost_of[t] = cost_of.get(t, 0.0) + cost
                    top[t] = max(top.get(t, (-1.0, "")), (cost, f.op_name))
        if not cost_of:
            return ((), False, False), ""
        best = max(cost_of, key=lambda t: (cost_of[t], t))
        return best, top[best][1]

    def axes_of(ins: Instruction, depth: int = 0) -> Optional[str]:
        op = ins.opcode
        base = op[:-6] if op.endswith("-start") else \
            op[:-5] if op.endswith("-done") else op
        if base in _COLLECTIVE_OPS:
            if op.endswith("-done") and depth < 4:
                for o in ins.operands:
                    if o in by_name:
                        return axes_of(by_name[o], depth + 1)
            return collective_axes(ins.attrs, mesh_axes)
        if op == "fusion":
            found = [collective_axes(f.attrs, mesh_axes)
                     for sub in _referenced(ins, "absorb") for f in fused(sub)
                     if f.opcode.split("-start")[0] in _COLLECTIVE_OPS]
            found = [a for a in found if a is not None]
            if found:
                return ",".join(dict.fromkeys(
                    x for a in found for x in a.split(",") if x))
        return None

    table: Dict[str, ScopeRow] = {}
    for comp in executed_computations(comps):
        instrs = comps[comp].instructions
        placed: Dict[str, tuple] = {}     # name -> ((path, remat, backward), src)
        inherited = set()
        users: Dict[str, List[str]] = {}
        for ins in instrs:
            for o in ins.operands:
                users.setdefault(o, []).append(ins.name)
            if "/" in ins.op_name:        # a name stack: jit(f)/.../primitive
                placed[ins.name] = (tags(ins.op_name), ins.op_name)
            elif ins.opcode not in _UNPLACED_OPS:
                got = vote(ins)
                if got[1]:
                    placed[ins.name] = got
        # what the compiler made itself carries no name stack (a layout
        # copy, an asynchronous slice, a kernel XLA wrote for ragged-dot):
        # it lies with the first instruction it feeds, or else with what
        # feeds it; the text is in schedule order, operands first
        for order, near in ((reversed(instrs), lambda i: users.get(i.name, ())),
                            (instrs, lambda i: i.operands)):
            for ins in order:
                if ins.name not in placed and ins.opcode not in _UNPLACED_OPS:
                    donor = next((n for n in near(ins) if n in placed), None)
                    if donor is not None:
                        placed[ins.name] = placed[donor]
                        inherited.add(ins.name)
        for ins in instrs:
            (path, remat, backward), src = placed.get(
                ins.name, (((), False, False), ins.op_name))
            table[ins.name] = ScopeRow(path, remat, backward, axes_of(ins),
                                       ins.opcode, src,
                                       ins.name in inherited)
    return table
