"""Loop-aware cost model over optimized (post-SPMD) HLO text.

``compiled.cost_analysis()`` visits each while-loop body **once**, so
scanned-layer models (all the deep configs here) undercount FLOPs/bytes
by the trip count.  This module re-derives the three roofline inputs
from ``compiled.as_text()`` with:

* ``known_trip_count`` multipliers on while bodies (fallback: the
  loop-condition comparison constant),
* fusion-boundary byte accounting (fusion internals are VMEM-resident:
  only fusion operands/results touch HBM),
* in-place update handling (dynamic-update-slice / scan carries alias
  their buffer: traffic is the update, not the buffer),
* collective-traffic accounting per kind with replica-group sizes
  (bytes each device puts on the interconnect).

Validated against ``cost_analysis()`` on loop-free programs in
``tests/test_hlo_cost.py``.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["analyze_module", "HloCost"]

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+)$")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count.....n.:.(\d+)')
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_OPERANDS_RE = re.compile(r"\(([^()]*(?:\([^()]*\)[^()]*)*)\)")

COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
              "collective-permute")

_TRANSCENDENTAL = {
    "exponential", "log", "tanh", "sqrt", "rsqrt", "power", "logistic",
    "exponential-minus-one", "log-plus-one", "cosine", "sine", "atan2",
    "erf", "cbrt",
}
_ELEMENTWISE = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "and", "or", "xor", "not", "negate", "abs", "sign", "compare",
    "select", "clamp", "floor", "ceil", "round-nearest-afz",
    "round-nearest-even", "remainder", "shift-left",
    "shift-right-logical", "shift-right-arithmetic", "is-finite",
}
_ZERO_COST = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota", "reshape",
    "bitcast-convert", "copy-start", "copy-done", "domain",
    "opt-barrier", "custom-call",
}


@dataclasses.dataclass
class Shape:
    parts: List[Tuple[str, Tuple[int, ...]]]

    @property
    def bytes(self) -> float:
        total = 0.0
        for dt, dims in self.parts:
            n = 1
            for d in dims:
                n *= d
            total += n * DTYPE_BYTES.get(dt, 4)
        return total

    @property
    def elements(self) -> float:
        return sum(float(_prod(dims)) for _, dims in self.parts)


def _prod(dims) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def _parse_shape(text: str) -> Shape:
    parts = [(dt, tuple(int(x) for x in dims.split(",")) if dims else ())
             for dt, dims in _SHAPE_RE.findall(text)]
    return Shape(parts)


@dataclasses.dataclass
class Op:
    name: str
    kind: str
    shape: Shape
    operands: List[str]
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    ops: List[Op]
    symtab: Dict[str, Shape]
    root: Optional[Op] = None


@dataclasses.dataclass
class HloCost:
    flops: float = 0.0
    transcendentals: float = 0.0
    bytes: float = 0.0
    coll_bytes: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {k: 0.0 for k in COLL_KINDS})
    coll_ops: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {k: 0.0 for k in COLL_KINDS})

    def scaled(self, m: float) -> "HloCost":
        return HloCost(
            self.flops * m, self.transcendentals * m, self.bytes * m,
            {k: v * m for k, v in self.coll_bytes.items()},
            {k: v * m for k, v in self.coll_ops.items()})

    def add(self, o: "HloCost") -> None:
        self.flops += o.flops
        self.transcendentals += o.transcendentals
        self.bytes += o.bytes
        for k in COLL_KINDS:
            self.coll_bytes[k] += o.coll_bytes[k]
            self.coll_ops[k] += o.coll_ops[k]

    @property
    def coll_total(self) -> float:
        return sum(self.coll_bytes.values())


def _split_operands(argstr: str) -> List[str]:
    """Operand names from an op's argument list (ignores literals).

    XLA's HLO text carries *typed* operand lists —
    ``dot(f32[256,256]{1,0} %Arg_0.1, ...)`` — where naive
    comma-splitting yields dtype tokens (``f32``) instead of names, so
    every symtab lookup missed and dot contractions collapsed to 1
    (the recalibration bug behind the old test_hlo_cost xfails).  When
    ``%``-prefixed names are present they are authoritative; the bare
    fallback keeps hand-written HLO fixtures working."""
    if "%" in argstr:
        return re.findall(r"%([\w.\-]+)", argstr)
    out = []
    for tok in argstr.split(","):
        tok = tok.strip()
        m = re.match(r"([A-Za-z_][\w.\-]*)", tok)
        if m:
            out.append(m.group(1))
    return out


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        s = line.strip()
        if cur is None:
            m = _COMP_HDR_RE.match(line)
            if m and line.endswith("{"):
                cur = Computation(m.group(1), [], {})
                # parameters from the signature
                for pm in re.finditer(r"%?([\w.\-]+)\s*:\s*([^,()]+(?:\([^)]*\))?)",
                                      m.group(2)):
                    cur.symtab[pm.group(1)] = _parse_shape(pm.group(2))
            continue
        if s == "}" or s.startswith("} "):
            comps[cur.name] = cur
            cur = None
            continue
        dm = _DEF_RE.match(s)
        if not dm:
            continue
        name, rhs = dm.group(1), dm.group(2)
        # rhs = "<shape> <kind>(<args>), attrs..."  (shape may be a tuple)
        km = re.match(r"(\(.*?\)|[a-z0-9]+\[[\d,]*\](?:\{[^}]*\})?)\s+"
                      r"([\w\-]+)", rhs)
        if not km:
            continue
        shape = _parse_shape(km.group(1))
        kind = km.group(2)
        rest = rhs[km.end():]
        am = _OPERANDS_RE.search(rest)
        operands = _split_operands(am.group(1)) if am else []
        op = Op(name, kind, shape, operands, s)
        cur.symtab[name] = shape
        cur.ops.append(op)
        if s.startswith("ROOT"):
            cur.root = op
    return comps


def _group_size(line: str, world: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_LIST_RE.search(line)
    if m:
        ids = [t for t in m.group(1).split(",") if t.strip()]
        if ids:
            return len(ids)
    return world


def _collective_cost(op: Op, world: int) -> Tuple[str, float]:
    base = op.kind.replace("-start", "")
    n = _group_size(op.line, world)
    size = op.shape.bytes
    if base == "all-reduce":
        moved = 2.0 * size * (n - 1) / n
    elif base == "all-gather":
        moved = size * (n - 1) / n
    elif base == "reduce-scatter":
        moved = size * (n - 1)
    elif base == "all-to-all":
        moved = size * (n - 1) / n
    else:
        moved = size
    return base, moved


def _trip_count(op: Op, comps: Dict[str, Computation]) -> int:
    m = _TRIP_RE.search(op.line)
    if m:
        return int(m.group(1))
    cm = _COND_RE.search(op.line)
    if cm and cm.group(1) in comps:
        cond = comps[cm.group(1)]
        consts = re.findall(r"constant\((\d+)\)", "\n".join(
            o.line for o in cond.ops))
        if consts:
            return int(consts[-1])
    return 1


class _Analyzer:
    def __init__(self, comps: Dict[str, Computation], world: int):
        self.comps = comps
        self.world = world
        self._memo: Dict[Tuple[str, bool], HloCost] = {}

    def comp_cost(self, name: str, inside_fusion: bool) -> HloCost:
        key = (name, inside_fusion)
        if key in self._memo:
            return self._memo[key]
        self._memo[key] = HloCost()          # cycle guard
        comp = self.comps[name]
        total = HloCost()
        for op in comp.ops:
            total.add(self.op_cost(op, comp, inside_fusion))
        self._memo[key] = total
        return total

    def _operand_bytes(self, op: Op, comp: Computation) -> float:
        return sum(comp.symtab[o].bytes for o in op.operands
                   if o in comp.symtab)

    def _param_slice_bytes(self, called: Computation) -> Dict[int, float]:
        """For each fusion parameter consumed *only* through
        dynamic-slice (a windowed read of a big stacked buffer — the
        scan-residual pattern), the true traffic is the slice, not the
        buffer.  Returns {param_index: effective_bytes}."""
        out: Dict[int, float] = {}
        params = [o for o in called.ops if o.kind == "parameter"]
        for idx, pop in enumerate(params):
            consumers = [o for o in called.ops
                         if pop.name in o.operands]
            if consumers and all(o.kind == "dynamic-slice"
                                 for o in consumers):
                out[idx] = sum(o.shape.bytes for o in consumers)
        return out

    def _fusion_io_bytes(self, op: Op, comp: Computation,
                         called: Optional[Computation]) -> float:
        out_b = op.shape.bytes
        slice_bytes = self._param_slice_bytes(called) if called else {}
        io = 0.0
        aliased = False
        root = called.root if called else None
        has_dus = called is not None and any(
            o.kind == "dynamic-update-slice" for o in called.ops)
        for i, name in enumerate(op.operands):
            sh = comp.symtab.get(name)
            if sh is None:
                continue
            if i in slice_bytes:
                io += slice_bytes[i]            # windowed read
            elif (has_dus and not aliased and sh.bytes == out_b
                  and root is not None):
                # in-place update of the scan-carry buffer: traffic is
                # the update window (read-modify-write), not the buffer
                aliased = True
                io += 2 * _update_bytes(called)
            else:
                io += sh.bytes
        if not aliased:
            io += out_b                          # result write
        return io

    def op_cost(self, op: Op, comp: Computation,
                inside_fusion: bool) -> HloCost:
        c = HloCost()
        kind = op.kind
        out_b = op.shape.bytes
        out_e = op.shape.elements

        if kind in _ZERO_COST:
            return c
        if kind == "while":
            bm = _BODY_RE.search(op.line)
            trips = _trip_count(op, self.comps)
            if bm and bm.group(1) in self.comps:
                c.add(self.comp_cost(bm.group(1), False).scaled(trips))
            return c
        if kind == "conditional":
            branches = re.findall(r"branch_computations=\{([^}]*)\}", op.line)
            names = []
            if branches:
                names = [b.strip().lstrip("%")
                         for b in branches[0].split(",")]
            else:
                names = re.findall(r"(?:true|false)_computation=%?([\w.\-]+)",
                                   op.line)
            costs = [self.comp_cost(n, False) for n in names
                     if n in self.comps]
            if costs:
                big = max(costs, key=lambda x: x.flops + x.bytes)
                c.add(big)
            return c
        if kind in ("call", "async-start"):
            cm = _CALLS_RE.search(op.line) or re.search(
                r"to_apply=%?([\w.\-]+)", op.line)
            if cm and cm.group(1) in self.comps:
                c.add(self.comp_cost(cm.group(1), inside_fusion))
            return c
        if kind == "fusion":
            cm = _CALLS_RE.search(op.line)
            called = self.comps.get(cm.group(1)) if cm else None
            if called is not None:
                inner = self.comp_cost(called.name, True)
                c.flops += inner.flops
                c.transcendentals += inner.transcendentals
            if not inside_fusion:
                c.bytes += self._fusion_io_bytes(op, comp, called)
            return c
        if kind.startswith(tuple(k for k in COLL_KINDS)) and \
                not kind.endswith("-done"):
            base, moved = _collective_cost(op, self.world)
            c.coll_bytes[base] += moved
            c.coll_ops[base] += 1
            if not inside_fusion:
                c.bytes += out_b
            return c
        if kind.endswith("-done"):
            return c

        # ---- FLOPs ------------------------------------------------------
        if kind in ("dot", "dot-general"):
            contracted = _dot_contracted(op, comp)
            c.flops += 2.0 * out_e * contracted
        elif kind == "convolution":
            k_elems = _conv_kernel_elems(op, comp)
            c.flops += 2.0 * out_e * k_elems
        elif kind in _TRANSCENDENTAL:
            c.transcendentals += out_e
            c.flops += out_e
        elif kind in _ELEMENTWISE or kind == "map":
            c.flops += out_e
        elif kind in ("reduce", "reduce-window"):
            in_e = sum(comp.symtab[o].elements for o in op.operands[:1]
                       if o in comp.symtab)
            c.flops += in_e
        elif kind == "sort":
            import math as _m
            c.flops += out_e * max(_m.log2(max(out_e, 2)), 1)
        elif kind in ("scatter",):
            upd = (comp.symtab[op.operands[2]].elements
                   if len(op.operands) > 2 and op.operands[2] in comp.symtab
                   else out_e)
            c.flops += upd

        # ---- bytes (HBM traffic at op granularity) -----------------------
        if not inside_fusion:
            if kind == "dynamic-update-slice":
                upd = (comp.symtab[op.operands[1]].bytes
                       if len(op.operands) > 1 and op.operands[1]
                       in comp.symtab else 0.0)
                c.bytes += 2 * upd
            elif kind == "dynamic-slice":
                c.bytes += 2 * out_b
            elif kind == "gather":
                idx = (comp.symtab[op.operands[1]].bytes
                       if len(op.operands) > 1 and op.operands[1]
                       in comp.symtab else 0.0)
                c.bytes += 2 * out_b + idx
            elif kind == "scatter":
                upd_b = (comp.symtab[op.operands[2]].bytes
                         if len(op.operands) > 2 and op.operands[2]
                         in comp.symtab else out_b)
                c.bytes += 3 * upd_b
            else:
                c.bytes += self._operand_bytes(op, comp) + out_b
        return c


def _update_bytes(comp: Optional[Computation]) -> float:
    if comp is None or comp.root is None:
        return 0.0
    root = comp.root
    if root.kind == "dynamic-update-slice" and len(root.operands) > 1:
        sh = comp.symtab.get(root.operands[1])
        if sh:
            return sh.bytes
    # root wraps a dus (bitcast chains): find any dus op
    for op in comp.ops:
        if op.kind == "dynamic-update-slice" and len(op.operands) > 1:
            sh = comp.symtab.get(op.operands[1])
            if sh:
                return sh.bytes
    return 0.0


def _dot_contracted(op: Op, comp: Computation) -> float:
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.line)
    lhs = comp.symtab.get(op.operands[0]) if op.operands else None
    if not m or lhs is None or not lhs.parts:
        return 1.0
    dims = lhs.parts[0][1]
    idx = [int(x) for x in m.group(1).split(",") if x.strip()]
    return float(_prod([dims[i] for i in idx if i < len(dims)]) or 1)


def _conv_kernel_elems(op: Op, comp: Computation) -> float:
    if len(op.operands) > 1 and op.operands[1] in comp.symtab:
        rhs = comp.symtab[op.operands[1]]
        if rhs.parts:
            dims = rhs.parts[0][1]
            # kernel spatial * input-feature elems (all but out-features)
            return float(_prod(dims) / max(dims[-1], 1)) \
                if dims else 1.0
    return 1.0


def top_contributors(text: str, world: int, n: int = 25,
                     by: str = "bytes") -> List[Tuple[str, float]]:
    """Per-op contributions (loop multipliers applied) sorted by
    ``by`` in {"bytes", "flops"} — the profile for the hypothesis loop."""
    comps = parse_module(text)
    if not comps:
        return []
    called = set()
    for comp in comps.values():
        for op in comp.ops:
            for pat in (_CALLS_RE, _BODY_RE, _COND_RE):
                m = pat.search(op.line)
                if m:
                    called.add(m.group(1))
    roots = [nm for nm in comps if nm not in called]
    entry = next((nm for nm in roots if "main" in nm), roots[0])
    an = _Analyzer(comps, world)
    rows: List[Tuple[str, float]] = []

    def walk(comp_name: str, mult: float, prefix: str):
        comp = comps[comp_name]
        for op in comp.ops:
            if op.kind == "while":
                bm = _BODY_RE.search(op.line)
                trips = _trip_count(op, comps)
                if bm and bm.group(1) in comps:
                    walk(bm.group(1), mult * trips, prefix + op.name + "/")
                continue
            c = an.op_cost(op, comp, False)
            val = (c.bytes if by == "bytes" else
                   c.coll_total if by == "coll" else c.flops)
            if val:
                opnds = [comp.symtab[o].parts for o in op.operands[:4]
                         if o in comp.symtab]
                rows.append((prefix + f"{op.kind}:{op.name} "
                             f"out={op.shape.parts} in={opnds}",
                             val * mult))
    walk(entry, 1.0, "")
    rows.sort(key=lambda r: -r[1])
    return rows[:n]


def analyze_module(text: str, world: int, entry: Optional[str] = None
                   ) -> HloCost:
    comps = parse_module(text)
    if not comps:
        return HloCost()
    if entry is None:
        # heuristic: ENTRY computation is the one named main-ish, else the
        # one not called by anyone
        called = set()
        for comp in comps.values():
            for op in comp.ops:
                for pat in (_CALLS_RE, _BODY_RE, _COND_RE):
                    m = pat.search(op.line)
                    if m:
                        called.add(m.group(1))
        roots = [n for n in comps if n not in called]
        entry = next((n for n in roots if "main" in n), roots[0])
    return _Analyzer(comps, world).comp_cost(entry, False)
