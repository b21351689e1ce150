"""While-aware cost model over optimized HLO text.

``compiled.cost_analysis()`` counts a while/scan body ONCE regardless of
trip count (verified empirically — see EXPERIMENTS.md §Dry-run methodology),
which undercounts scanned-layer models by the product of scan trip counts.
This module re-derives per-device costs from the partitioned HLO text with
execution counts:

  - computation graph: ENTRY + while bodies/conditions (trip count parsed
    from the loop-condition constant), conditional branches;
  - exec_count(computation) = product of enclosing trip counts;
  - dot FLOPs from operand shapes x contracting dims x exec_count;
  - HBM traffic model: operand+result bytes of top-level fusion / dot /
    convolution / copy / sort / scatter / gather / reduce instructions
    (XLA fuses elementwise chains, so fusion boundaries approximate actual
    HBM round-trips) x exec_count;
  - collective bytes: operand bytes of all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute x exec_count.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

__all__ = ["parse_hlo", "HloCost", "xla_cost_dict"]


def xla_cost_dict(cost) -> dict:
    """``compiled.cost_analysis()`` as a plain dict (``None`` — a backend
    without a cost model — gives ``{}``)."""
    return dict(cost) if cost is not None else {}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_HBM_OPS = ("fusion", "dot", "convolution", "copy", "sort", "scatter",
            "gather", "reduce", "transpose", "reshape", "broadcast",
            "dynamic-slice", "dynamic-update-slice", "concatenate", "pad",
            "slice", "select-and-scatter", "iota", "rng", "compare",
            "add", "multiply", "subtract", "divide", "exponential",
            "tanh", "convert", "cholesky", "triangular-solve")

_COMP_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s+\(.*\)\s*->\s*.+\{\s*$")
_INSTR_HEAD = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*")
_SIMPLE_TYPE = re.compile(r"[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?")
_OPCODE = re.compile(r"\s*([a-zA-Z][\w\-]*)\(")


def _parse_instr_line(line: str):
    """Robust instruction parse handling tuple types with /*index=N*/
    comments and nested parens."""
    m = _INSTR_HEAD.match(line)
    if not m:
        return None
    i = m.end()
    if i >= len(line):
        return None
    if line[i] == "(":
        depth, j = 1, i + 1
        while j < len(line) and depth:
            if line[j] == "(":
                depth += 1
            elif line[j] == ")":
                depth -= 1
            j += 1
        type_str = line[i:j]
    else:
        m2 = _SIMPLE_TYPE.match(line, i)
        if not m2:
            return None
        type_str = m2.group(0)
        j = m2.end()
    m3 = _OPCODE.match(line, j)
    if not m3:
        return None
    return m.group(1), type_str, m3.group(1), line[m3.end():]
_TYPED = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_OPERAND = re.compile(r"%([\w\.\-]+)")
_SHAPE_ONLY = re.compile(r"^([a-z0-9]+)\[([0-9,]*)\]")


def _dims(dims_str: str) -> list[int]:
    return [int(d) for d in dims_str.split(",") if d.strip()]


def _type_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _TYPED.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in _dims(dims):
            n *= d
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class _Instr:
    name: str
    type_str: str
    opcode: str
    rest: str  # text after the opcode's '('


@dataclasses.dataclass
class _Comp:
    name: str
    entry: bool
    instrs: list
    fused: bool = False  # called via fusion `calls=` — no HBM accounting


@dataclasses.dataclass
class HloCost:
    dot_flops: float
    hbm_bytes: float
    collective_bytes: dict
    collective_counts: dict
    while_trips: dict
    raw_once: dict

    def to_dict(self):
        return dataclasses.asdict(self)


def _split_computations(text: str) -> list[_Comp]:
    comps = []
    cur = None
    for line in text.splitlines():
        m = _COMP_HEADER.match(line.strip()) if line and not line.startswith(" ") else None
        if m and line.rstrip().endswith("{"):
            cur = _Comp(name=m.group(2), entry=bool(m.group(1)), instrs=[])
            comps.append(cur)
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        parsed = _parse_instr_line(line)
        if parsed:
            cur.instrs.append(_Instr(*parsed))
    return comps


def _operands_region(rest: str) -> str:
    depth, i = 1, 0
    while i < len(rest) and depth:
        if rest[i] == "(":
            depth += 1
        elif rest[i] == ")":
            depth -= 1
        i += 1
    return rest[:i - 1]


def parse_hlo(text: str) -> HloCost:
    comps = _split_computations(text)
    by_name = {c.name: c for c in comps}

    # instruction result shapes (global: names unique per module in practice)
    shapes: dict[str, str] = {}
    for c in comps:
        for ins in c.instrs:
            shapes[ins.name] = ins.type_str

    # mark fusion-called computations (do not re-count their innards)
    for c in comps:
        for ins in c.instrs:
            if ins.opcode == "fusion":
                m = re.search(r"calls=%?([\w\.\-]+)", ins.rest)
                if m and m.group(1) in by_name:
                    by_name[m.group(1)].fused = True

    # execution-count propagation: ENTRY=1; while body/cond x trip count;
    # conditional branches x1; call to_apply x1.
    exec_count: dict[str, float] = defaultdict(float)
    trips: dict[str, int] = {}

    def trip_of(cond_name: str) -> int:
        cond = by_name.get(cond_name)
        if cond is None:
            return 1
        best = 1
        for ins in cond.instrs:
            if ins.opcode == "constant":
                m = re.match(r"\s*(\d+)\s*\)", ins.rest)
                if m:
                    best = max(best, int(m.group(1)))
        return best

    roots = [c for c in comps if c.entry] or comps[:1]
    stack = [(c.name, 1.0) for c in roots]
    seen_pairs = set()
    while stack:
        name, count = stack.pop()
        exec_count[name] += count
        c = by_name.get(name)
        if c is None:
            continue
        for ins in c.instrs:
            if ins.opcode == "while":
                mb = re.search(r"body=%?([\w\.\-]+)", ins.rest)
                mc = re.search(r"condition=%?([\w\.\-]+)", ins.rest)
                # prefer XLA's own trip-count annotation
                mt = re.search(r"known_trip_count[^0-9]*?(\d+)", ins.rest)
                if mt:
                    t = int(mt.group(1))
                else:
                    t = trip_of(mc.group(1)) if mc else 1
                trips[ins.name] = t
                if mb:
                    key = (name, mb.group(1))
                    if key not in seen_pairs:
                        seen_pairs.add(key)
                        stack.append((mb.group(1), count * t))
            elif ins.opcode == "conditional":
                for m in re.finditer(r"%([\w\.\-]+)", ins.rest):
                    if m.group(1) in by_name and by_name[m.group(1)] is not c:
                        pass  # branches counted once via call below
                mb = re.search(r"branch_computations=\{([^}]*)\}", ins.rest)
                names = []
                if mb:
                    names = _OPERAND.findall(mb.group(1))
                else:
                    for k in ("true_computation", "false_computation"):
                        mk = re.search(rf"{k}=%?([\w\.\-]+)", ins.rest)
                        if mk:
                            names.append(mk.group(1))
                for n in names:
                    stack.append((n, count))
            elif ins.opcode == "call":
                mk = re.search(r"to_apply=%?([\w\.\-]+)", ins.rest)
                if mk:
                    stack.append((mk.group(1), count))

    dot_flops = 0.0
    hbm = 0.0
    coll_bytes: dict = defaultdict(float)
    coll_counts: dict = defaultdict(float)
    raw_once: dict = defaultdict(float)

    for c in comps:
        if c.fused:
            continue
        count = exec_count.get(c.name, 0.0)
        if count == 0.0:
            continue
        for ins in c.instrs:
            operands_str = _operands_region(ins.rest)
            out_b = _type_bytes(ins.type_str)
            in_b = _type_bytes(operands_str)
            if in_b == 0:
                in_b = sum(_type_bytes(shapes.get(nm, ""))
                           for nm in _OPERAND.findall(operands_str))
            kind = next((k for k in _COLLECTIVES if ins.opcode.startswith(k)), None)
            if kind is not None and not ins.opcode.endswith("-done"):
                coll_bytes[kind] += in_b * count
                coll_counts[kind] += count
                raw_once[kind] += in_b
                hbm += (in_b + out_b) * count
                continue
            if ins.opcode == "dot":
                flops = _dot_flops(ins, shapes)
                dot_flops += flops * count
                hbm += (in_b + out_b) * count
                continue
            base = ins.opcode.split(".")[0]
            if any(base.startswith(h) for h in ("fusion", "convolution", "copy",
                                                "sort", "scatter", "gather",
                                                "reduce", "dynamic-slice",
                                                "dynamic-update-slice",
                                                "concatenate", "pad", "slice",
                                                "transpose", "bitcast-convert",
                                                "convert", "select",
                                                "rng", "cholesky")):
                hbm += (in_b + out_b) * count

    coll_bytes["total"] = sum(coll_bytes[k] for k in _COLLECTIVES if k in coll_bytes)
    return HloCost(dot_flops=dot_flops, hbm_bytes=hbm,
                   collective_bytes=dict(coll_bytes),
                   collective_counts=dict(coll_counts),
                   while_trips=dict(trips), raw_once=dict(raw_once))


def _dot_flops(ins: _Instr, shapes: dict) -> float:
    """2 x prod(result dims) x prod(lhs contracting dim sizes)."""
    m = _SHAPE_ONLY.match(ins.type_str.strip())
    if not m:
        return 0.0
    out_elems = 1
    for d in _dims(m.group(2)):
        out_elems *= d
    ops = _OPERAND.findall(_operands_region(ins.rest))
    if not ops:
        return 0.0
    lhs_shape = shapes.get(ops[0], "")
    ml = _SHAPE_ONLY.match(lhs_shape.strip())
    if not ml:
        return 0.0
    lhs_dims = _dims(ml.group(2))
    mc = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", ins.rest)
    contract = 1
    if mc:
        for i in _dims(mc.group(1)):
            if i < len(lhs_dims):
                contract *= lhs_dims[i]
    return 2.0 * out_elems * contract
