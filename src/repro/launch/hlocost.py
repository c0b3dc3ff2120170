"""Trip-count-aware cost extraction from post-optimization HLO text.

``compiled.cost_analysis()`` counts each ``while`` body ONCE regardless of
trip count (verified empirically), so for scan-over-layers models it
undercounts FLOPs/bytes by ~num_layers x.  This module re-derives the three
roofline inputs directly from the HLO text, weighting every computation by
the product of enclosing loop trip counts (XLA records
``known_trip_count`` in each while's backend_config):

  * FLOPs       — from dot ops (2 * prod(output dims) * contracted size,
                  batch dims excluded from output product... they are part
                  of the output shape, so included exactly once) plus a
                  convolution estimate; dots inside fusion computations are
                  attributed to the computation that references the fusion.
  * HBM bytes   — fusion-boundary traffic: for each executable instruction,
                  output bytes + operand bytes, with slice-type ops
                  (dynamic-slice / dynamic-update-slice / gather / scatter)
                  counted at their *slice* size, and free ops (tuple, GTE,
                  parameter, bitcast, while) at zero.  Fusion internals are
                  registers/VMEM by construction and contribute no bytes.
  * collective  — wire bytes per device with ring-algorithm factors and
                  replica-group sizes (see roofline.py), trip-weighted.

Executable computations = ENTRY + while bodies/conditions + conditional
branches; fusion/reducer computations are internal (flops-only).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1, "token": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.+?)\s+"
                     r"([\w\-]+)\(")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{")
_TRIP_RE = re.compile(r'known_trip_count\\?":\{\\?"n\\?":\\?"(\d+)')
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_EXPL_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")

_FREE_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
             "bitcast", "while", "after-all", "optimization-barrier",
             "conditional", "call", "custom-call", "partition-id",
             "replica-id", "iota", "rng-bit-generator"}
_SLICE_OUT_OPS = {"dynamic-slice", "gather", "slice"}
_SLICE_IN_OPS = {"dynamic-update-slice", "scatter"}
_COLLECTIVES = {"all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute"}
_WIRE_FACTOR = {
    "all-reduce": lambda n: 2.0 * (n - 1) / n,
    "all-gather": lambda n: (n - 1) / n,
    "reduce-scatter": lambda n: float(n - 1),
    "all-to-all": lambda n: (n - 1) / n,
    "collective-permute": lambda n: 1.0,
}


def xla_cost_analysis(compiled) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` as a dict (empty when XLA reports
    nothing)."""
    return dict(compiled.cost_analysis() or {})


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(text: str) -> List[int]:
    m = _SHAPE_RE.search(text)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Instr:
    name: str
    out_type: str
    op: str
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    instrs: List[Instr] = dataclasses.field(default_factory=list)


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in text.splitlines():
        m = _COMP_RE.match(line.strip())
        if m and ("->" in line):
            cur = Computation(m.group(1))
            comps[cur.name] = cur
            continue
        if cur is None:
            continue
        dm = _DEF_RE.match(line)
        if dm:
            cur.instrs.append(Instr(dm.group(1), dm.group(2), dm.group(3),
                                    line))
    return comps


def _build_symbols(comps: Dict[str, Computation]) -> Dict[str, str]:
    sym: Dict[str, str] = {}
    for c in comps.values():
        for ins in c.instrs:
            sym[ins.name] = ins.out_type
    return sym


def _operands(ins: Instr) -> List[str]:
    """Operand names inside the op's parens (attribute refs excluded)."""
    start = ins.line.find(ins.op + "(")
    if start < 0:
        return []
    depth = 0
    seg = []
    for ch in ins.line[start + len(ins.op):]:
        if ch == "(":
            depth += 1
            if depth == 1:
                continue
        elif ch == ")":
            depth -= 1
            if depth == 0:
                break
        seg.append(ch)
    return _OPERAND_RE.findall("".join(seg))


def _dot_flops(ins: Instr, sym: Dict[str, str]) -> float:
    out_dims = _shape_dims(ins.out_type)
    out_prod = 1
    for d in out_dims:
        out_prod *= d
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.line)
    ops = _operands(ins)
    if not m or not ops:
        return 2.0 * out_prod  # degenerate
    lhs_dims = _shape_dims(sym.get(ops[0], ""))
    contracted = 1
    for i in [int(x) for x in m.group(1).split(",") if x]:
        if i < len(lhs_dims):
            contracted *= lhs_dims[i]
    return 2.0 * out_prod * contracted


def _conv_flops(ins: Instr, sym: Dict[str, str]) -> float:
    out_dims = _shape_dims(ins.out_type)
    ops = _operands(ins)
    out_prod = 1
    for d in out_dims:
        out_prod *= d
    if len(ops) < 2:
        return 2.0 * out_prod
    k_dims = _shape_dims(sym.get(ops[1], ""))
    k_prod = 1
    for d in k_dims:
        k_prod *= d
    out_feat = out_dims[-1] if out_dims else 1
    return 2.0 * out_prod * max(k_prod // max(out_feat, 1), 1)


def _group_size(line: str) -> int:
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _EXPL_GROUPS_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip()])
    return 2


@dataclasses.dataclass
class CompCost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    wire_bytes: float = 0.0
    coll_out_bytes: float = 0.0
    coll_counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    children: List[Tuple[str, int]] = dataclasses.field(
        default_factory=list)       # (computation, trip_weight)
    fusion_refs: List[str] = dataclasses.field(default_factory=list)


def _fusion_dus_bytes(comp: Computation, sym: Dict[str, str]
                      ) -> Optional[float]:
    """If a fusion computation performs dynamic-update-slices (the donated
    in-place KV-cache pattern), its real traffic is the update slices, not
    the full aliased buffer.  Returns None for ordinary fusions."""
    dus = [i for i in comp.instrs if i.op in _SLICE_IN_OPS]
    if not dus:
        return None
    total = 0.0
    for ins in dus:
        ops = _operands(ins)
        upd = (_shape_bytes(sym.get(ops[1], "")) if len(ops) > 1
               else _shape_bytes(ins.out_type))
        total += 2.0 * upd
    return total


def _local_cost(comp: Computation, sym: Dict[str, str],
                comps: Dict[str, Computation]) -> CompCost:
    cost = CompCost()
    for ins in comp.instrs:
        op = ins.op
        if op == "dot":
            cost.flops += _dot_flops(ins, sym)
        elif op == "convolution":
            cost.flops += _conv_flops(ins, sym)
        elif op == "fusion":
            m = re.search(r"calls=%([\w\.\-]+)", ins.line)
            if m:
                cost.fusion_refs.append(m.group(1))
                callee = comps.get(m.group(1))
                if callee is not None:
                    dus_b = _fusion_dus_bytes(callee, sym)
                    if dus_b is not None:
                        # in-place update: slice writes + non-buffer reads
                        out_b = _shape_bytes(ins.out_type)
                        reads = sum(
                            _shape_bytes(sym.get(n, ""))
                            for n in _operands(ins)
                            if _shape_bytes(sym.get(n, "")) < out_b)
                        cost.hbm_bytes += dus_b + reads
                        continue
        elif op == "while":
            mb = re.search(r"body=%([\w\.\-]+)", ins.line)
            mc = re.search(r"condition=%([\w\.\-]+)", ins.line)
            mt = _TRIP_RE.search(ins.line)
            trip = int(mt.group(1)) if mt else 1
            if mb:
                cost.children.append((mb.group(1), trip))
            if mc:
                cost.children.append((mc.group(1), trip))
        elif op == "conditional":
            for m in re.finditer(r"%([\w\.\-]+)", ins.line.split(
                    "branch_computations")[-1]):
                if m.group(1) in sym:
                    continue
                cost.children.append((m.group(1), 1))

        base = op.removesuffix("-start").removesuffix("-done")
        if base in _COLLECTIVES and not op.endswith("-done"):
            out_b = _shape_bytes(ins.out_type)
            n = _group_size(ins.line)
            cost.wire_bytes += _WIRE_FACTOR[base](max(n, 2)) * out_b
            cost.coll_out_bytes += out_b
            cost.coll_counts[base] = cost.coll_counts.get(base, 0) + 1
            cost.hbm_bytes += 2.0 * out_b
            continue

        # ---- HBM traffic model ----
        if op in _FREE_OPS:
            continue
        out_b = _shape_bytes(ins.out_type)
        if op in _SLICE_OUT_OPS:
            cost.hbm_bytes += 2.0 * out_b
        elif op in _SLICE_IN_OPS:
            upd = _operands(ins)
            upd_b = (_shape_bytes(sym.get(upd[1], "")) if len(upd) > 1
                     else out_b)
            cost.hbm_bytes += 2.0 * upd_b
        else:
            cost.hbm_bytes += out_b
            for name in _operands(ins):
                cost.hbm_bytes += _shape_bytes(sym.get(name, ""))
    return cost


@dataclasses.dataclass
class ModuleCost:
    flops: float
    hbm_bytes: float
    wire_bytes: float
    coll_out_bytes: float
    coll_counts: Dict[str, int]
    trip_weighted: bool = True

    def to_dict(self):
        return dataclasses.asdict(self)


def analyze_text(text: str, entry: Optional[str] = None) -> ModuleCost:
    comps = parse_module(text)
    sym = _build_symbols(comps)
    local = {name: _local_cost(c, sym, comps) for name, c in comps.items()}

    # attribute fusion-computation dot flops to the referrer (fusions can
    # nest; resolve with memoization)
    def fusion_flops(name: str, seen=None) -> float:
        seen = seen or set()
        if name in seen or name not in local:
            return 0.0
        seen.add(name)
        c = local[name]
        return c.flops + sum(fusion_flops(r, seen) for r in c.fusion_refs)

    if entry is None:
        entry = next((n for n in comps if n.startswith("main")), None) \
            or next(iter(comps))

    total = ModuleCost(0.0, 0.0, 0.0, 0.0, {})

    def walk(name: str, weight: float, stack: Tuple[str, ...] = ()):
        if name not in local or name in stack:
            return
        c = local[name]
        total.flops += weight * (
            c.flops + sum(fusion_flops(r) for r in c.fusion_refs))
        total.hbm_bytes += weight * c.hbm_bytes
        total.wire_bytes += weight * c.wire_bytes
        total.coll_out_bytes += weight * c.coll_out_bytes
        for op, n in c.coll_counts.items():
            total.coll_counts[op] = (total.coll_counts.get(op, 0)
                                     + int(weight * n))
        for child, trip in c.children:
            walk(child, weight * trip, stack + (name,))

    walk(entry, 1.0)
    return total
