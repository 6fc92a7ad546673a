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
from typing import Any, Dict, List, Optional, Tuple

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
_INSTR_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
    r"(\((?:[^()]|\([^()]*\))*\)|\w+\[[^\]]*\](?:\{[^}]*\})?)\s*"
    r"([\w\-]+)\(")
_OPERAND_SHAPE_RE = re.compile(r"(\w+\[[0-9,]*\])(?:\{[^}]*\})?\s+%")
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
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, shape, opcode = m.group(1), m.group(2), m.group(3)
        seg = _operand_segment(line, m.end())
        operands = [s.group(1) for s in _OPERAND_SHAPE_RE.finditer(seg)]
        attrs = line[m.end() + len(seg):]
        op_name = ""
        nm = _OP_NAME_RE.search(line)
        if nm:
            op_name = nm.group(1)
        cur.instructions.append(Instruction(
            name=name, opcode=opcode, shape=shape,
            operand_shapes=operands, attrs=attrs, op_name=op_name))
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


def module_units(comps: Dict[str, Computation]) -> List[Unit]:
    """Flatten a parsed module into cost units: instructions of the
    entry computation plus while bodies/conditions and conditional
    branches (tagged ``in_loop`` when under a while), with absorbed
    fusion/reducer computations folded into their calling unit."""
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
