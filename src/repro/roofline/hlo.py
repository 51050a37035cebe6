"""Structural cost model over optimized (post-SPMD) HLO text.

XLA's ``compiled.cost_analysis()`` counts while-loop bodies ONCE, so any
model built on ``lax.scan`` (every arch here — layers, flash-attention
chunks, grad accumulation) is undercounted by ~the layer count, and the
same holds for collectives that live inside scan bodies. This module
re-derives per-device costs by walking the HLO computation graph:

- ``dot`` FLOPs: 2 x |result| x |contracted dims| (MXU convention),
  multiplied through enclosing while trip counts
  (``backend_config known_trip_count``, with a loop-condition fallback);
- HBM bytes: operands + results of top-level (fusion-boundary) ops —
  fusion internals stay in registers/VMEM and are not counted;
- collective wire bytes per category with a ring model:
  all-reduce 2x operand, all-gather/reduce-scatter (gather/scatter
  delta), all-to-all and collective-permute 1x.

Everything is *per device*: post-SPMD shapes are shard shapes.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "opaque": 0,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_FREE_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast",
             "constant", "iota", "after-all", "partition-id", "replica-id"}


@dataclasses.dataclass
class Shape:
    dtype: str
    dims: Tuple[int, ...]

    @property
    def nbytes(self) -> int:
        n = _DTYPE_BYTES.get(self.dtype, 0)
        for d in self.dims:
            n *= d
        return n

    @property
    def nelems(self) -> int:
        n = 1
        for d in self.dims:
            n *= d
        return n


_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def _parse_shapes(s: str) -> List[Shape]:
    out = []
    for dt, dims in _SHAPE_RE.findall(s):
        if dt in _DTYPE_BYTES:
            out.append(Shape(dt, tuple(int(x) for x in dims.split(",") if x)))
    return out


@dataclasses.dataclass
class Op:
    name: str
    opcode: str
    result: List[Shape]
    operands: List[str]          # %names
    attrs: str                   # raw text after the operand list


@dataclasses.dataclass
class Computation:
    name: str
    shapes: Dict[str, List[Shape]]         # %name -> result shape(s)
    ops: List[Op]


_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->")
_PARAM_RE = re.compile(r"([\w.\-]+)\s*:\s*((?:\([^)]*\))|(?:[a-z][a-z0-9]*"
                       r"\[[0-9,]*\](?:\{[^}]*\})?))")
_OPND_RE = re.compile(r"%([\w.\-]+)")


def _split_opcode(rhs: str) -> Tuple[List[Shape], str, str]:
    """rhs: '<shape> opcode(operands...), attrs...'"""
    rhs = rhs.strip()
    if rhs.startswith("("):                      # tuple result shape
        depth, i = 0, 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape_s, rest = rhs[: i + 1], rhs[i + 1:]
    else:
        sp = rhs.index(" ")
        shape_s, rest = rhs[:sp], rhs[sp:]
    rest = rest.strip()
    m = re.match(r"([\w\-]+)\(", rest)
    opcode = m.group(1) if m else rest.split("(")[0]
    return _parse_shapes(shape_s), opcode, rest


def parse_module(text: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry: Optional[str] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if not line or line.lstrip().startswith("//"):
            continue
        stripped = line.strip()
        # computation header (column 0, contains '->' and ends with '{')
        if (not raw.startswith(" ") and "->" in line
                and stripped.endswith("{")):
            m = _COMP_RE.match(stripped)
            if m:
                cur = Computation(m.group(1), {}, [])
                comps[cur.name] = cur
                if stripped.startswith("ENTRY"):
                    entry = cur.name
                # header params give shapes for %param names
                hdr = stripped[stripped.index("(") + 1:]
                for pname, pshape in _PARAM_RE.findall(hdr.split("->")[0]):
                    cur.shapes[pname] = _parse_shapes(pshape)
                continue
        if cur is None:
            continue
        m = _DEF_RE.match(stripped)
        if not m:
            continue
        name = m.group(1)
        rhs = stripped[m.end():]
        try:
            result, opcode, rest = _split_opcode(rhs)
        except (ValueError, IndexError):
            continue
        # operands: %refs inside the first balanced paren group after opcode
        paren = rest.find("(")
        depth, j = 0, paren
        for j in range(paren, len(rest)):
            depth += rest[j] == "("
            depth -= rest[j] == ")"
            if depth == 0:
                break
        opnd_s, attrs = rest[paren + 1: j], rest[j + 1:]
        operands = _OPND_RE.findall(opnd_s)
        op = Op(name, opcode, result, operands, attrs)
        cur.shapes[name] = result
        cur.ops.append(op)
    return comps, entry


_TRIP_RE = re.compile(r'known_trip_count[^0-9]*?(\d+)')
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_DIMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_GROUPS_RE = re.compile(r"replica_groups=\{([^}]*(?:\},\{[^}]*)*)\}\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _group_size(attrs: str) -> int:
    m = _GROUPS_IOTA_RE.search(attrs)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([0-9,]+)\}", attrs)
    if m:
        return len(m.group(1).split(","))
    return 1


def _trip_count(op: Op, comps) -> int:
    m = _TRIP_RE.search(op.attrs)
    if m:
        return int(m.group(1))
    # fallback: largest literal in the loop-condition computation
    m = _COND_RE.search(op.attrs)
    if m and m.group(1) in comps:
        best = 1
        for o in comps[m.group(1)].ops:
            for c in re.findall(r"constant\((\d+)\)", o.attrs):
                best = max(best, int(c))
        # also scan the raw constant defs
        return best
    return 1


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    coll_operand_bytes: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {c: 0.0 for c in COLLECTIVES})
    coll_wire_bytes: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {c: 0.0 for c in COLLECTIVES})
    coll_counts: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {c: 0.0 for c in COLLECTIVES})

    def add(self, other: "Cost", mult: float = 1.0):
        self.flops += other.flops * mult
        self.hbm_bytes += other.hbm_bytes * mult
        for c in COLLECTIVES:
            self.coll_operand_bytes[c] += other.coll_operand_bytes[c] * mult
            self.coll_wire_bytes[c] += other.coll_wire_bytes[c] * mult
            self.coll_counts[c] += other.coll_counts[c] * mult


def _operand_bytes(op: Op, comp: Computation) -> float:
    total = 0.0
    for name in op.operands:
        for sh in comp.shapes.get(name, []):
            total += sh.nbytes
    return total


def _nth_operand_bytes(op: Op, comp: Computation, i: int) -> float:
    if i >= len(op.operands):
        return 0.0
    return sum(sh.nbytes for sh in comp.shapes.get(op.operands[i], []))


def _op_hbm_bytes(op: Op, comp: Computation) -> float:
    """HBM traffic of one top-level op: only the *touched* region counts.
    Slicing ops read/write their result-sized window, not the whole
    buffer (a KV cache updated in place moves O(token) bytes per step,
    not O(cache))."""
    oc = op.opcode
    res = sum(s.nbytes for s in op.result)
    if oc in ("dynamic-slice", "slice", "gather", "pad", "broadcast",
              "reshape", "reverse"):
        return res
    if oc == "dynamic-update-slice":
        return 2.0 * _nth_operand_bytes(op, comp, 1)   # read+write window
    if oc == "scatter":
        return 2.0 * _nth_operand_bytes(op, comp, 2) \
            + _nth_operand_bytes(op, comp, 1)
    if oc in ("copy", "transpose", "convert"):
        return 2.0 * res
    return _operand_bytes(op, comp) + res


def _fusion_hbm_bytes(op: Op, comp: Computation,
                      comps: Dict[str, Computation]) -> float:
    """Fused-kernel traffic: each fusion parameter is charged its
    *accessed window* (a body dynamic-slice/gather of a parameter only
    reads the slice; an in-place DUS root only writes the update
    window), everything else is read/written once."""
    m = _CALLS_RE.search(op.attrs)
    body = comps.get(m.group(1)) if m else None
    res = sum(s.nbytes for s in op.result)
    if body is None:
        return _operand_bytes(op, comp) + res
    # default charge: full size per parameter
    charge: Dict[str, float] = {}
    by_name = {o.name: o for o in body.ops}
    for pname in body.shapes:
        o = by_name.get(pname)
        if (o is not None and o.opcode == "parameter") \
                or pname.startswith("param"):
            charge[pname] = sum(s.nbytes for s in body.shapes[pname])

    def resolve(name: str) -> str:
        """Follow convert/bitcast/copy chains to the producing source
        (XLA-CPU bf16 emulation wraps loop carries in f32 round-trips
        that have no TPU analogue)."""
        seen = set()
        while name in by_name and name not in seen:
            seen.add(name)
            o = by_name[name]
            if o.opcode in ("convert", "bitcast", "copy") and o.operands:
                name = o.operands[0]
            else:
                break
        return name

    root = body.ops[-1] if body.ops else None
    root_src = resolve(root.name) if root is not None else None
    out_bytes = res
    for o in body.ops:
        if o.opcode in ("dynamic-slice", "gather", "slice") and o.operands:
            tgt = resolve(o.operands[0])
            if tgt in charge:
                w = sum(s.nbytes for s in o.result)
                charge[tgt] = min(charge[tgt], w)
        if o.opcode == "dynamic-update-slice" and o.operands:
            tgt = resolve(o.operands[0])
            upd = _nth_operand_bytes(o, body, 1)
            if tgt in charge:
                charge[tgt] = min(charge[tgt], upd)
            if root_src == o.name:
                out_bytes = 2.0 * upd        # in-place windowed write
    return sum(charge.values()) + out_bytes


def _dot_flops(op: Op, comp: Computation) -> float:
    out = sum(s.nelems for s in op.result)
    m = _DIMS_RE.search(op.attrs)
    contracted = 1
    if m and op.operands:
        lhs = comp.shapes.get(op.operands[0])
        if lhs:
            for d in m.group(1).split(","):
                if d:
                    contracted *= lhs[0].dims[int(d)]
    return 2.0 * out * contracted


def _coll_base(opcode: str) -> Optional[str]:
    for c in COLLECTIVES:
        if opcode == c or opcode == c + "-start":
            return c
    return None


def _cost_of(cname: str, comps: Dict[str, Computation],
             memo: Dict[str, Cost], in_fusion: bool = False) -> Cost:
    key = cname + ("#f" if in_fusion else "")
    if key in memo:
        return memo[key]
    memo[key] = Cost()          # cycle guard
    comp = comps.get(cname)
    if comp is None:
        return memo[key]
    cost = Cost()
    for op in comp.ops:
        oc = op.opcode
        if oc == "while":
            trip = _trip_count(op, comps)
            m = _BODY_RE.search(op.attrs)
            if m:
                cost.add(_cost_of(m.group(1), comps, memo), trip)
            mc = _COND_RE.search(op.attrs)
            if mc:
                cost.add(_cost_of(mc.group(1), comps, memo), trip)
            continue
        if oc in ("fusion",):
            m = _CALLS_RE.search(op.attrs)
            if m:
                inner = _cost_of(m.group(1), comps, memo, in_fusion=True)
                # fusion internals: count flops, not HBM traffic
                c2 = Cost(flops=inner.flops)
                for c in COLLECTIVES:
                    c2.coll_operand_bytes[c] = inner.coll_operand_bytes[c]
                    c2.coll_wire_bytes[c] = inner.coll_wire_bytes[c]
                    c2.coll_counts[c] = inner.coll_counts[c]
                cost.add(c2)
            if not in_fusion:
                cost.hbm_bytes += _fusion_hbm_bytes(op, comp, comps)
            continue
        if oc in ("call", "conditional", "async-start"):
            for m in re.finditer(
                    r"(?:to_apply|calls|branch_computations=\{|true_computation|"
                    r"false_computation)=?%?([\w.\-]+)", op.attrs):
                cost.add(_cost_of(m.group(1), comps, memo, in_fusion))
            continue
        base = _coll_base(oc)
        if base is not None:
            ob = _operand_bytes(op, comp)
            rb = sum(s.nbytes for s in op.result)
            g = _group_size(op.attrs)
            if base == "all-reduce":
                wire = 2.0 * ob * (g - 1) / max(g, 1)
            elif base == "all-gather":
                wire = max(rb - ob, 0.0)
            elif base == "reduce-scatter":
                wire = max(ob - rb, 0.0)
            elif base == "all-to-all":
                wire = ob * (g - 1) / max(g, 1)
            else:                      # collective-permute
                wire = ob
            cost.coll_operand_bytes[base] += ob
            cost.coll_wire_bytes[base] += wire
            cost.coll_counts[base] += 1
            if not in_fusion:
                cost.hbm_bytes += ob + rb
            continue
        if oc in ("dot", "convolution"):
            cost.flops += _dot_flops(op, comp)
            if not in_fusion:
                cost.hbm_bytes += _operand_bytes(op, comp) + sum(
                    s.nbytes for s in op.result)
            continue
        if oc in _FREE_OPS:
            continue
        # generic elementwise / slicing / copy — windowed traffic model
        if not in_fusion:
            cost.hbm_bytes += _op_hbm_bytes(op, comp)
    memo[key] = cost
    return cost


def module_cost(text: str) -> Dict:
    """Loop-aware per-device cost of an optimized HLO module."""
    comps, entry = parse_module(text)
    if entry is None:
        return {"flops": 0.0, "hbm_bytes": 0.0}
    memo: Dict[str, Cost] = {}
    c = _cost_of(entry, comps, memo)
    return {
        "flops": c.flops,
        "hbm_bytes": c.hbm_bytes,
        "collectives": {
            "per_op_bytes": c.coll_operand_bytes,
            "wire_bytes_per_op": c.coll_wire_bytes,
            "counts": c.coll_counts,
            "total_operand_bytes": sum(c.coll_operand_bytes.values()),
            "wire_bytes": sum(c.coll_wire_bytes.values()),
        },
    }


# ---------------------------------------------------------------------------
# diagnostics: where do the flops / bytes / collectives come from?
# ---------------------------------------------------------------------------


def top_contributors(text: str, k: int = 25):
    """Top-k ops by trip-multiplied flops and HBM bytes, with metadata
    op_name provenance — the profile stand-in the §Perf loop reads."""
    comps, entry = parse_module(text)
    rows = []

    def walk(cname: str, mult: float, in_fusion: bool, seen):
        comp = comps.get(cname)
        if comp is None or cname in seen:
            return
        seen = seen | {cname}
        for op in comp.ops:
            oc = op.opcode
            if oc == "while":
                trip = _trip_count(op, comps)
                m = _BODY_RE.search(op.attrs)
                if m:
                    walk(m.group(1), mult * trip, in_fusion, seen)
                continue
            if oc == "fusion":
                m = _CALLS_RE.search(op.attrs)
                if m:
                    walk(m.group(1), mult, True, seen)
                if not in_fusion:
                    b = _fusion_hbm_bytes(op, comp, comps)
                    rows.append((op, cname, mult, 0.0, b))
                continue
            if oc in ("call", "conditional", "async-start"):
                for m in re.finditer(
                        r"(?:to_apply|calls|branch_computations=\{|"
                        r"true_computation|false_computation)=?%?([\w.\-]+)",
                        op.attrs):
                    walk(m.group(1), mult, in_fusion, seen)
                continue
            fl = _dot_flops(op, comp) if oc in ("dot", "convolution") else 0.0
            b = 0.0 if (in_fusion or oc in _FREE_OPS) else \
                _op_hbm_bytes(op, comp)
            base = _coll_base(oc)
            if fl or b or base:
                rows.append((op, cname, mult, fl, b))

    walk(entry, 1.0, False, frozenset())

    def meta(op):
        m = re.search(r'op_name="([^"]+)"', op.attrs)
        return m.group(1) if m else op.name

    def fmt(op, cname, mult, fl, b):
        shape = "x".join(str(d) for s in op.result for d in s.dims) or "()"
        return {"op": op.opcode, "result": shape, "trips": mult,
                "flops": fl * mult, "bytes": b * mult,
                "where": f"{cname}", "name": meta(op)[:160]}

    by_flops = sorted(rows, key=lambda r: -(r[3] * r[2]))[:k]
    by_bytes = sorted(rows, key=lambda r: -(r[4] * r[2]))[:k]
    return ([fmt(*r) for r in by_flops if r[3] > 0],
            [fmt(*r) for r in by_bytes if r[4] > 0])


_PALLAS_TPU_CALL = re.compile(
    r"%(\w+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"")


def pallas_kernels(text: str) -> Dict[str, int]:
    """Pallas TPU kernels in compiled HLO text: how many
    ``tpu_custom_call`` instructions carry each kernel name (the
    ``name=`` of its ``pallas_call``; this repo's are ``nq_*``). Counts
    instructions, not executions: a kernel inside a layer scan counts
    once."""
    counts: Dict[str, int] = {}
    for m in _PALLAS_TPU_CALL.finditer(text):
        counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts
