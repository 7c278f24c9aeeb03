"""Trip-count-aware HLO cost model (the dry-run "profiler").

`compiled.cost_analysis()` counts a `while` body ONCE, so any scanned-layer
model under-reports FLOPs/bytes/collectives by ~n_layers x (verified in
tests/test_hlo_cost.py).  This module re-derives costs from the post-SPMD
`compiled.as_text()` with loop multipliers:

  1. parse computations and each instruction's result shape,
  2. build the call graph (while body/condition, fusion calls, conditionals),
  3. extract while trip counts from the loop-condition constant,
  4. multiplier(comp) = product of trip counts on the call path from ENTRY,
  5. FLOPs: dot instructions (2 * prod(out) * prod(contracting dims)),
     convolutions (crude window model), rare on this workload;
  6. bytes: per instruction result + operand bytes at fusion/top-level
     granularity (fusion internals stay in registers/VMEM — matching the
     "bytes accessed" HBM-traffic semantics);
  7. collectives: ring-model bytes (see analysis.collective_bytes) times the
     multiplier of the computation they sit in.

Conditional branches are counted at the max over branches (a scanned-layer
`cond` executes exactly one branch per iteration).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1,
    "bf16": 2, "f16": 2, "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_COMP_START = re.compile(r"^(?:ENTRY\s+)?%([\w\.\-]+)\s*\(.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.*)$")
# result type (tuple or single, with optional layout braces) followed by op
_SHAPE = re.compile(
    r"^(\(.*?\)|[a-z0-9_]+\[[0-9,]*\](?:\{[^}]*\})?)\s+([a-z][\w\-]*)\(")
_ONE_SHAPE = re.compile(r"([a-z0-9_]+)\[([0-9,]*)\]")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w\.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_WHILE = re.compile(r"\bwhile\(")
_DOT_ATTR = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_DOT_BATCH = re.compile(r"lhs_batch_dims=\{([0-9,]*)\}")
_CONST_INT = re.compile(r"constant\((\d+)\)")
_COLL_KIND = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\b")
_GROUPS_RE = re.compile(r"replica_groups=\{(\{[^}]*\})")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _parse_shapes(text: str) -> List[Tuple[str, List[int]]]:
    return [(dt, [int(d) for d in dims.split(",") if d])
            for dt, dims in _ONE_SHAPE.findall(text)]


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _parse_shapes(text):
        if dt in _DTYPE_BYTES:
            n = 1
            for d in dims:
                n *= d
            total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class Instr:
    name: str
    op: str
    result_type: str
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    instrs: List[Instr]
    shapes: Dict[str, str]          # instr name -> result type string


def parse_computations(hlo: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        if cur is None:
            m = _COMP_START.match(line.strip())
            if m:
                cur = Computation(m.group(1), [], {})
            continue
        if line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        sm = _SHAPE.match(rest)
        if not sm:
            continue
        result_type, op = sm.group(1), sm.group(2)
        cur.instrs.append(Instr(name, op, result_type, line))
        cur.shapes[name] = result_type
    return comps


def _operands(line: str) -> List[str]:
    """Operand instruction names of a call like op(%a, %b.2, s32[] %c)."""
    m = re.search(r"\b[a-z][\w\-]*\((.*)$", line)
    if not m:
        return []
    args = m.group(1)
    # cut at the closing paren of the operand list (attrs follow after "),")
    depth = 1
    out = []
    for i, ch in enumerate(args):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                args = args[:i]
                break
    return re.findall(r"%([\w\.\-]+)", args)


def _trip_count(cond: Computation) -> int:
    """Largest integer constant in the loop condition = scan length bound."""
    best = 1
    for ins in cond.instrs:
        for c in _CONST_INT.findall(ins.line):
            best = max(best, int(c))
    return best


def _multipliers(comps: Dict[str, Computation]
                 ) -> Tuple[Dict[str, float], set]:
    """Returns (multiplier per computation, fusion-internal computations).

    Fusion-internal comps (reached via calls=/to_apply=) stay in registers —
    their FLOPs are real but their operands/results are not HBM traffic (the
    enclosing fusion instruction accounts for that)."""
    entry = None
    for name in comps:
        # jax entry is usually 'main.N'; fall back to the last computation
        if name.startswith("main"):
            entry = name
    if entry is None:
        entry = list(comps)[-1]
    mult: Dict[str, float] = {name: 0.0 for name in comps}
    mult[entry] = 1.0
    internal: set = set()

    # iterate to fixpoint (call graph is a DAG; few passes suffice)
    for _ in range(len(comps)):
        changed = False
        for cname, comp in comps.items():
            base = mult.get(cname, 0.0)
            if base == 0.0:
                continue
            for ins in comp.instrs:
                trips = 1.0
                called = _CALLS.findall(ins.line)
                if _WHILE.search(ins.line):
                    cond_m = re.search(r"condition=%?([\w\.\-]+)", ins.line)
                    if cond_m and cond_m.group(1) in comps:
                        trips = float(_trip_count(comps[cond_m.group(1)]))
                for target in called:
                    if target not in comps:
                        continue
                    line_n = ins.line.replace("%", "")
                    is_body = f"body={target}" in line_n
                    is_fusion = (f"calls={target}" in line_n
                                 or f"to_apply={target}" in line_n)
                    if is_fusion and target not in internal:
                        internal.add(target)
                        changed = True
                    m_new = base * (trips if is_body else 1.0)
                    if m_new > mult.get(target, 0.0):
                        mult[target] = m_new
                        changed = True
                bm = _BRANCHES.search(ins.line)
                if bm:
                    for target in re.findall(r"%?([\w\.\-]+)", bm.group(1)):
                        if target in comps and base > mult.get(target, 0.0):
                            mult[target] = base
                            changed = True
        if not changed:
            break
    return mult, internal


def _dot_flops(ins: Instr, shapes: Dict[str, str]) -> float:
    out_elems = 1
    for _, dims in _parse_shapes(ins.result_type):
        for d in dims:
            out_elems *= d
    ops = _operands(ins.line)
    if not ops:
        return 0.0
    lhs_type = shapes.get(ops[0], "")
    lhs_shapes = _parse_shapes(lhs_type)
    if not lhs_shapes:
        return 0.0
    lhs_dims = lhs_shapes[0][1]
    cm = _DOT_ATTR.search(ins.line)
    contract = 1
    if cm:
        for idx in cm.group(1).split(","):
            if idx and int(idx) < len(lhs_dims):
                contract *= lhs_dims[int(idx)]
    return 2.0 * out_elems * contract


_SKIP_BYTES_OPS = ("tuple", "get-tuple-element", "parameter", "constant",
                   "bitcast", "while", "call", "iota", "after-all",
                   "conditional", "custom-call")

# ops that move no data under XLA's own HloCostAnalysis accounting
_SKIP_BYTES_OPS_XLA = ("tuple", "get-tuple-element", "parameter", "constant",
                       "bitcast")


def _instr_bytes_xla(ins: Instr, shapes: Dict[str, str]) -> float:
    """XLA-compatible bytes for one instruction: result + every operand,
    no HBM-traffic modelling (no gather/update discounts, scalars counted).
    This reproduces Compiled.cost_analysis()["bytes accessed"] on unrolled
    graphs — the ground truth the tests compare against — while
    :func:`_instr_bytes` keeps the HBM-approximation the roofline uses."""
    if ins.op in _SKIP_BYTES_OPS_XLA:
        return 0.0
    return _shape_bytes(ins.result_type) + sum(
        _shape_bytes(shapes.get(o, "")) for o in _operands(ins.line))


def _instr_bytes(ins: Instr, shapes: Dict[str, str]) -> float:
    """Approximate HBM bytes for one instruction (matches XLA's
    bytes-accessed semantics for the patterns this workload emits):

      * slice-like ops (dynamic-slice / gather, incl. fusions rooted at
        them): 2 x slice size — the big operand is NOT streamed;
      * update-like ops (dynamic-update-slice, scatter, incl. fusions):
        2 x smallest non-scalar operand (the update) — the result aliases
        the big buffer in place;
      * everything else: result + operands (post-fusion HLO, so elementwise
        chains are single instructions and intermediates don't hit HBM).
    """
    if ins.op in _SKIP_BYTES_OPS:
        return 0.0
    tag = ins.name + " " + ins.op
    result = _shape_bytes(ins.result_type)
    op_bytes = [_shape_bytes(shapes.get(o, "")) for o in _operands(ins.line)]
    op_bytes = [b for b in op_bytes if b > 4]       # drop scalars/indices
    if "dynamic-update-slice" in tag or "scatter" in tag:
        upd = min(op_bytes) if op_bytes else result
        return 2.0 * upd
    if "dynamic-slice" in tag or "gather" in tag:
        return 2.0 * result
    return result + sum(op_bytes)


def _group_size(line: str, default: int) -> int:
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return max(1, m.group(1).count(",") + 1)
    return default


@dataclasses.dataclass
class HloCost:
    flops: float                    # per chip, loop-corrected
    bytes_accessed: float           # per chip, loop-corrected (HBM approx)
    bytes_accessed_xla: float       # loop-corrected, XLA visitor accounting
    collective: Dict[str, float]    # per chip bytes moved, by kind
    collective_total: float
    dots: int
    loops: Dict[str, float]         # multiplier per computation (diagnostics)


def analyze(hlo: str, n_chips: int) -> HloCost:
    comps = parse_computations(hlo)
    mult, internal = _multipliers(comps)
    flops = 0.0
    bytes_acc = 0.0
    bytes_xla = 0.0
    coll: Dict[str, float] = {"all-gather": 0.0, "all-reduce": 0.0,
                              "reduce-scatter": 0.0, "all-to-all": 0.0,
                              "collective-permute": 0.0}
    n_dots = 0
    seen_async: set = set()
    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        if m <= 0.0:
            continue
        for ins in comp.instrs:
            if ins.op == "dot":
                f = _dot_flops(ins, comp.shapes)
                flops += m * f
                n_dots += 1
            if cname not in internal:
                bytes_acc += m * _instr_bytes(ins, comp.shapes)
                bytes_xla += m * _instr_bytes_xla(ins, comp.shapes)
            km = _COLL_KIND.search(ins.line)
            if km and "-done" not in ins.line.split("=")[1][:60]:
                kind = km.group(1)
                key = (cname, ins.name.replace("-start", ""))
                if key in seen_async:
                    continue
                seen_async.add(key)
                size = _shape_bytes(ins.result_type)
                g = _group_size(ins.line, n_chips)
                if g <= 1:
                    continue
                if kind == "all-gather":
                    moved = size * (g - 1) / g
                elif kind == "reduce-scatter":
                    moved = size * (g - 1)
                elif kind == "all-reduce":
                    moved = 2 * size * (g - 1) / g
                elif kind == "all-to-all":
                    moved = size * (g - 1) / g
                else:
                    moved = size
                coll[kind] += m * moved
    return HloCost(
        flops=flops, bytes_accessed=bytes_acc, bytes_accessed_xla=bytes_xla,
        collective=coll, collective_total=sum(coll.values()), dots=n_dots,
        loops={k: v for k, v in mult.items() if v > 1.0})
