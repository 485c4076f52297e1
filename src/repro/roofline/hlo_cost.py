"""While-loop-aware cost model over optimized HLO text.

XLA's ``compiled.cost_analysis()`` counts a while-loop body **once**, so any
scanned model (layers via lax.scan, flash key-block loops, loss chunking,
microbatching) is undercounted by the trip count — 35× for a 48-layer stack.
This module re-derives FLOPs / HBM bytes / collective bytes from the
optimized HLO *with call-graph multiplicities*:

  * computations are parsed into instruction lists;
  * ``while`` ops contribute ``known_trip_count`` (XLA annotates scans; a
    condition-constant fallback covers the rest) to their body/condition;
  * ``fusion``/``call``/``conditional`` propagate multiplicity 1;
  * FLOPs: 2·|result|·|contraction| summed over ``dot`` ops in every
    computation, scaled by the computation's multiplicity;
  * bytes: per *executable* computation (entry / while bodies — fusion
    internals are on-chip and do not touch HBM), each top-level instruction
    contributes result + operand bytes, skipping parameters / GTEs / tuples /
    constants / bitcasts (no data movement);
  * collective bytes: as roofline.analysis, but scaled by multiplicity.

Validated against analytic per-layer counts in tests/test_roofline.py.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1, "u1": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(\(.*\))\s*->")
# the result type may be a tuple containing `/*index=N*/` comments (which
# include '='), so match it lazily up to the first " op(" boundary.
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+"
                    r"([\w\-]+)\((.*)$")
_SKIP_BYTES = {"parameter", "get-tuple-element", "tuple", "bitcast",
               "constant", "iota", "after-all", "partition-id", "replica-id",
               # loop carries alias in place; their bodies' writes are
               # already charged inside the body computation
               "while", "conditional", "optimization-barrier"}
_COLL_OPS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute")


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()``, or ``{}`` when the backend gives none."""
    return compiled.cost_analysis() or {}


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _dims(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Instr:
    name: str
    type_str: str
    op: str
    rest: str       # everything after the opening paren of operands
    line: str
    root: bool = False


@dataclasses.dataclass
class Computation:
    name: str
    params: dict            # param name -> type str
    instrs: list
    is_entry: bool = False

    def shapes(self) -> dict:
        out = dict(self.params)
        for i in self.instrs:
            out[i.name] = i.type_str
        return out


def parse_hlo(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        if not line.startswith(" "):  # computation header or closing brace
            m = _COMP_HDR.match(line.strip())
            if m and line.rstrip().endswith("{"):
                params = {}
                for pm in re.finditer(r"%?([\w.\-]+)\s*:\s*([^,)]+)",
                                      m.group(2)):
                    params[pm.group(1)] = pm.group(2)
                cur = Computation(m.group(1), params, [],
                                  is_entry=line.lstrip().startswith("ENTRY"))
                comps[cur.name] = cur
            elif line.strip() == "}":
                cur = None
            continue
        if cur is None:
            continue
        m = _INSTR.match(line)
        if m:
            cur.instrs.append(Instr(m.group(1), m.group(2).strip(),
                                    m.group(3), m.group(4), line,
                                    root="ROOT" in line.split("=")[0]))
    return comps


_CALL_ATTRS = re.compile(
    r"(?:calls|to_apply|body|condition|branch_computations)=\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")
_TRIP = re.compile(r'known_trip_count[^\d]*(\d+)')


def _trip_count(instr: Instr, comps: dict) -> int:
    m = _TRIP.search(instr.line)
    if m:
        return int(m.group(1))
    # fallback: largest s32 constant in the condition computation
    cm = re.search(r"condition=%?([\w.\-]+)", instr.line)
    if cm and cm.group(1) in comps:
        best = 1
        for i in comps[cm.group(1)].instrs:
            c = re.match(r"s32\[\]", i.type_str)
            k = re.search(r"constant\((\d+)\)", i.line)
            if c and k:
                best = max(best, int(k.group(1)))
        return best
    return 1


def multiplicities(comps: dict[str, Computation]) -> dict[str, float]:
    entry = next((c for c in comps.values() if c.is_entry), None)
    mult: dict[str, float] = defaultdict(float)
    if entry is None:
        return {name: 1.0 for name in comps}
    seen_stack: set = set()

    def visit(name: str, m: float):
        if name not in comps or m <= 0 or name in seen_stack:
            return
        mult[name] += m
        seen_stack.add(name)
        comp = comps[name]
        for i in comp.instrs:
            if i.op == "while":
                trips = _trip_count(i, comps)
                for attr in ("body", "condition"):
                    am = re.search(attr + r"=%?([\w.\-]+)", i.line)
                    if am:
                        visit(am.group(1), m * trips)
            else:
                am = _CALL_ATTRS.search(i.line)
                if am:
                    for callee in re.split(r",\s*", am.group(1)):
                        visit(callee.lstrip("%"), m)
        seen_stack.discard(name)

    visit(entry.name, 1.0)
    return dict(mult)


def _dot_flops(instr: Instr, shapes: dict) -> float:
    out_elems = 1
    for d in _dims(instr.type_str):
        out_elems *= d
    # contraction size from lhs shape + lhs_contracting_dims
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", instr.line)
    seg = instr.rest.split(")")[0]
    # newer XLA dumps inline the operand type (`dot(f32[a,b]{1,0} %x, ...)`)
    # — read the lhs shape straight off the first operand when present;
    # otherwise resolve the operand name against the computation's shapes.
    inline = re.match(r"\s*(\w+\[[0-9,]*\])", seg)
    if inline:
        ldims = _dims(inline.group(1))
    else:
        ops = [o.strip().lstrip("%") for o in
               re.split(r",\s*(?![^{]*\})", seg) if o.strip()]
        ldims = _dims(shapes.get(ops[0], "")) if ops else []
    contract = 1
    if m:
        for idx in m.group(1).split(","):
            if idx and int(idx) < len(ldims):
                contract *= ldims[int(idx)]
    return 2.0 * out_elems * contract


def _group_size(line: str) -> int:
    m = re.search(r"replica_groups=\{\{([0-9,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    return 1


def _operands(instr: Instr) -> list[str]:
    # operand list: names at the start of `rest` until the closing paren
    depth = 1
    buf = []
    for ch in instr.rest:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                break
        buf.append(ch)
    return re.findall(r"%([\w.\-]+)", "".join(buf))


def _operand_bytes(instr: Instr, shapes: dict) -> int:
    return sum(_type_bytes(shapes.get(n, "")) for n in _operands(instr))


def _write_bytes(instr: Instr, shapes: dict, comps: dict) -> int:
    """Effective HBM bytes *written* by one top-level instruction.

    dynamic-update-slice is in-place: only the update slice is written —
    counting the full result would charge the whole scan-carry buffer (e.g.
    a 16 GiB remat stack) on every loop iteration.  DUS-rooted fusions get
    the same treatment via their computation's root.
    """
    if instr.op == "dynamic-update-slice":
        ops = _operands(instr)
        if len(ops) >= 2:
            return _type_bytes(shapes.get(ops[1], ""))
    if instr.op == "fusion":
        am = re.search(r"calls=%?([\w.\-]+)", instr.line)
        if am and am.group(1) in comps:
            fc = comps[am.group(1)]
            root = next((i for i in fc.instrs if i.root), None)
            if root is not None and root.op == "dynamic-update-slice":
                fshapes = fc.shapes()
                ops = _operands(root)
                if len(ops) >= 2:
                    return _type_bytes(fshapes.get(ops[1], ""))
    return _type_bytes(instr.type_str)


@dataclasses.dataclass
class HloCost:
    flops: float
    bytes_accessed: float
    coll_moved: float
    coll_by_type: dict
    coll_counts: dict
    while_trips: dict


def analyse_hlo(text: str) -> HloCost:
    comps = parse_hlo(text)
    mult = multiplicities(comps)
    # executable contexts: entry + while bodies/conds (things with mult that
    # are not pure fusion callees).  Fusion computations never contain
    # collectives and their internals don't touch HBM.
    fusion_callees: set = set()
    while_comps: set = set()
    trips: dict = {}
    for c in comps.values():
        for i in c.instrs:
            if i.op == "fusion":
                am = re.search(r"calls=%?([\w.\-]+)", i.line)
                if am:
                    fusion_callees.add(am.group(1))
            if i.op == "while":
                t = _trip_count(i, comps)
                for attr in ("body", "condition"):
                    am = re.search(attr + r"=%?([\w.\-]+)", i.line)
                    if am:
                        while_comps.add(am.group(1))
                        trips[am.group(1)] = t

    flops = 0.0
    bytes_acc = 0.0
    coll = defaultdict(float)
    counts = defaultdict(int)
    for name, comp in comps.items():
        m = mult.get(name, 0.0)
        if m <= 0:
            continue
        shapes = comp.shapes()
        executable = comp.is_entry or name in while_comps
        for i in comp.instrs:
            if i.op == "dot":
                flops += m * _dot_flops(i, shapes)
            if not executable:
                continue
            base = i.op.replace("-start", "").replace("-done", "")
            if base in _COLL_OPS and not i.op.endswith("-done"):
                b = _type_bytes(i.type_str)
                n = _group_size(i.line)
                if base == "all-reduce":
                    moved = 2.0 * b * max(n - 1, 0) / max(n, 1)
                elif base == "all-gather":
                    moved = 1.0 * b * max(n - 1, 0) / max(n, 1)
                elif base == "reduce-scatter":
                    moved = float(b) * max(n - 1, 0)
                else:
                    moved = float(b)
                coll[base] += m * moved
                counts[base] += 1
            if i.op in _SKIP_BYTES or i.op.endswith("-done"):
                continue
            # read+write model: each materialised buffer is written once and
            # read ~once; DUS-adjusted (see _write_bytes).  Validated within
            # ~2x of analytic per-layer traffic in tests/test_roofline.py.
            bytes_acc += m * 2 * _write_bytes(i, shapes, comps)
    return HloCost(
        flops=flops,
        bytes_accessed=bytes_acc,
        coll_moved=sum(coll.values()),
        coll_by_type=dict(coll),
        coll_counts=dict(counts),
        while_trips=trips,
    )
