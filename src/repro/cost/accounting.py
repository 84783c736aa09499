"""Instruction-level FLOP / HBM-byte / collective accounting over HLO.

Policy layer: given a parsed module (``cost.parser``), attribute to every
*executed* instruction (loop trip counts multiplied through) the memory
traffic it actually generates.  The paper's argument hinges on charging
software memory management for the bytes it MOVES, not the buffers it
TOUCHES:

  * ``dynamic-update-slice`` writes the update slice in place -- bill
    2 x update bytes (read update + write slice), never the full buffer;
  * ``dynamic-slice`` / ``gather`` move the slice/gathered rows -- bill
    2 x result bytes (+ index reads for gather/scatter);
  * fusions are billed at their HBM boundary (internals live in
    registers/cache): parameter reads + root write, with two aliasing
    refinements -- a fusion rooted in ``dynamic-update-slice`` updates
    its target in place (bill the update, skip the aliased operand),
    and a parameter consumed only through ``gather``/``dynamic-slice``
    is charged for the rows actually read, not the whole operand;
  * ``while`` is a control construct: its body/condition are billed
    once per trip, the instruction itself moves nothing (the carry is
    aliased in place by XLA);
  * ``call`` is inlining -- recurse fully; ``conditional`` takes the
    most expensive branch.

Every byte lands in a category (``Cost.by_op``) so a reader can see *what kind* of traffic dominates: the paper-relevant
split is matmul vs. gather (block-table indirection) vs.
dynamic-update-slice (block copies) vs. collective vs. everything else.
"""

from __future__ import annotations

import collections
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from repro.cost.parser import (ENTRY, Computation, Instr, Module,
                               parse_module, shape_bytes, shape_dims)

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                  "all-to-all", "collective-permute")

# opcodes that move no data themselves (metadata / aliasing / control)
_FREE_OPS = {"parameter", "constant", "get-tuple-element", "tuple",
             "bitcast", "after-all", "partition-id", "replica-id",
             "while", "conditional", "call", "custom-call-start"}

# ops whose result shape understates nothing: billed 2x result
_SLICE_READ_OPS = ("dynamic-slice", "gather")

#: categories reported in ``Cost.by_op`` (stable keys for reports)
CATEGORY_MATMUL = "matmul"
CATEGORY_DUS = "dynamic-update-slice"
CATEGORY_DSLICE = "dynamic-slice"
CATEGORY_GATHER = "gather"
CATEGORY_SCATTER = "scatter"
CATEGORY_COLLECTIVE = "collective"
CATEGORY_COPY = "copy"
CATEGORY_FUSION = "fusion"
CATEGORY_OTHER = "other"


def _collective_kind(opcode: str) -> Optional[str]:
    base = opcode
    for suf in ("-start", "-done"):
        if base.endswith(suf):
            base = base[: -len(suf)]
    return base if base in COLLECTIVE_OPS else None


def dominant_category(by_op: Optional[Dict[str, float]]) -> str:
    """Largest-bytes category of a ``Cost.by_op`` dict ('-' when empty)."""
    if not by_op:
        return "-"
    return max(by_op, key=by_op.get)


def _category(opcode: str) -> str:
    if opcode in ("dot", "convolution"):
        return CATEGORY_MATMUL
    if opcode == "dynamic-update-slice":
        return CATEGORY_DUS
    if opcode == "dynamic-slice":
        return CATEGORY_DSLICE
    if opcode == "gather":
        return CATEGORY_GATHER
    if opcode == "scatter":
        return CATEGORY_SCATTER
    if _collective_kind(opcode):
        return CATEGORY_COLLECTIVE
    if opcode in ("copy", "copy-start"):
        return CATEGORY_COPY
    if opcode == "fusion":
        return CATEGORY_FUSION
    return CATEGORY_OTHER


@dataclasses.dataclass
class Cost:
    """Roofline quantities with a per-op-category byte breakdown."""
    flops: float = 0.0
    bytes: float = 0.0
    coll: Optional[Dict[str, float]] = None
    by_op: Optional[Dict[str, float]] = None

    def __post_init__(self):
        if self.coll is None:
            self.coll = {k: 0.0 for k in COLLECTIVE_OPS}
        if self.by_op is None:
            self.by_op = {}

    def add(self, other: "Cost", times: float = 1.0):
        self.flops += other.flops * times
        self.bytes += other.bytes * times
        for k in COLLECTIVE_OPS:
            self.coll[k] += other.coll[k] * times
        for k, v in other.by_op.items():
            self.by_op[k] = self.by_op.get(k, 0.0) + v * times

    def add_bytes(self, category: str, n: float):
        self.bytes += n
        self.by_op[category] = self.by_op.get(category, 0.0) + n

    @property
    def coll_total(self) -> float:
        return sum(self.coll.values())

    def dominant_op(self) -> str:
        return dominant_category(self.by_op)


class HloCostModel:
    """Walks a parsed module, multiplying loop bodies by trip counts."""

    def __init__(self, hlo_text: str):
        self.module: Module = parse_module(hlo_text)
        self.comps = self.module.comps
        self._memo: Dict[str, Cost] = {}

    # ---- flops ---------------------------------------------------------

    def _dot_flops(self, ins: Instr, sym: Dict[str, str]) -> float:
        res = 1
        for _, dims in shape_dims(ins.shape):
            for d in dims:
                res *= d
        lhs = sym.get(ins.operands[0]) if ins.operands else None
        contract = 1
        if lhs:
            m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.attrs)
            ldims = shape_dims(lhs)
            if m and ldims:
                dims = ldims[0][1]
                for i in (int(x) for x in m.group(1).split(",") if x):
                    if i < len(dims):
                        contract *= dims[i]
        return 2.0 * res * contract

    def _conv_flops(self, ins: Instr, sym: Dict[str, str]) -> float:
        """Exact convolution FLOPs: every output element is a dot of
        length (kernel spatial product x per-group input channels), so

            flops = 2 * result_elements * prod(kernel_spatial) * C_in_grp

        The kernel operand's 'i' dimension in HLO is ALREADY divided by
        ``feature_group_count``, so grouped/depthwise convs need no
        extra correction.  Falls back to the old 2x-result-elements
        approximation only when the kernel shape or dim_labels cannot be
        resolved.
        """
        res = 1
        for d in shape_dims(ins.shape)[0][1] if shape_dims(ins.shape) else []:
            res *= d
        rhs = (sym.get(ins.operands[1])
               if len(ins.operands) > 1 else None)
        m = re.search(r"dim_labels=([\w?]+)_([\w?]+)->([\w?]+)", ins.attrs)
        if rhs and m:
            kdims = shape_dims(rhs)
            if kdims:
                klabels, kshape = m.group(2), kdims[0][1]
                if len(klabels) == len(kshape):
                    spatial = 1
                    in_ch = 1
                    for lbl, dim in zip(klabels, kshape):
                        if lbl.isdigit():
                            spatial *= dim
                        elif lbl == "i":
                            in_ch *= dim
                    return 2.0 * res * spatial * in_ch
        return 2.0 * res

    def _called(self, ins: Instr) -> List[str]:
        out = []
        for m in re.finditer(
                r"(?:calls|to_apply|branch_computations)="
                r"\{?%?([\w\.\-,% ]+)\}?", ins.attrs):
            out.extend(re.findall(r"[\w\.\-]+", m.group(1)))
        return out

    def _fusion_traffic(self, ins: Instr) -> List[Tuple[str, float]]:
        """HBM boundary of a fusion: root write(s) + parameter reads,
        with in-place DUS and sliced-read (gather/dynamic-slice)
        refinements.  Multi-output fusions (root ``tuple``) are billed
        per element, so a fused K+V cache write is two slice-sized DUS
        bills, not two pool-sized ones."""
        called = self._called(ins)
        comp = self.comps.get(called[0]) if called else None
        if comp is None:
            return [(CATEGORY_FUSION, float(shape_bytes(ins.shape)))]
        sym = comp.symtab()
        byname = comp.by_name()
        root = comp.root
        out: List[Tuple[str, float]] = []

        # see through layout-only ops so a pool->bitcast->gather chain
        # (or a bitcast-wrapped DUS target) still resolves to the pool
        # parameter
        alias: Dict[str, str] = {}
        for bi in comp.instrs:
            if bi.opcode in ("bitcast", "reshape", "copy") and bi.operands:
                src = bi.operands[0]
                if src in byname and byname[src].opcode == "parameter":
                    alias[bi.name] = src
                elif src in alias:
                    alias[bi.name] = alias[src]

        def resolve_param(name: Optional[str]) -> Optional[str]:
            if name is None:
                return None
            if name in byname and byname[name].opcode == "parameter":
                return name
            return alias.get(name)

        roots: List[Instr] = []
        if root is not None and root.opcode == "tuple":
            roots = [byname[o] for o in root.operands if o in byname]
        elif root is not None:
            roots = [root]
        aliased: set = set()
        for r in roots:
            if r.opcode == "dynamic-update-slice":
                upd = r.operands[1] if len(r.operands) > 1 else None
                upd_b = shape_bytes(sym.get(upd, "")) if upd else 0
                out.append((CATEGORY_DUS, float(upd_b)))  # in-place write
                p = resolve_param(r.operands[0] if r.operands else None)
                if p:
                    aliased.add(p)                         # not re-read
            else:
                out.append((CATEGORY_FUSION, float(shape_bytes(r.shape))))

        uses: Dict[str, List[Instr]] = collections.defaultdict(list)
        for bi in comp.instrs:
            for o in bi.operands:
                p = (o if o in byname and byname[o].opcode == "parameter"
                     else alias.get(o))
                if p and alias.get(bi.name) != p:
                    uses[p].append(bi)
        for pi in comp.instrs:
            if pi.opcode != "parameter" or pi.name in aliased:
                continue
            pu = uses.get(pi.name, [])
            if not pu:
                continue
            sliced = all(
                u.opcode in _SLICE_READ_OPS and u.operands
                and (u.operands[0] == pi.name
                     or alias.get(u.operands[0]) == pi.name)
                for u in pu)
            if sliced:
                for u in pu:
                    cat = (CATEGORY_GATHER if u.opcode == "gather"
                           else CATEGORY_DSLICE)
                    out.append((cat, float(shape_bytes(u.shape))))
            else:
                out.append((CATEGORY_FUSION,
                            float(shape_bytes(pi.shape))))
        return out

    def instr_traffic(self, ins: Instr,
                      sym: Dict[str, str]) -> List[Tuple[str, float]]:
        """(category, bytes) contributions of one executed instruction.

        Control-flow ops return [] -- their bodies are billed by the
        walker.  This is the single byte-attribution rule table; both
        ``cost_of`` and ``attribute`` consume it.
        """
        op = ins.opcode
        kind = _collective_kind(op)
        if kind:
            # async pairs: the '-start' result is a tuple that carries
            # the input too -- bill the output once, at the '-done'
            if op.endswith("-start"):
                return []
            return [(CATEGORY_COLLECTIVE, float(shape_bytes(ins.shape)))]
        if op in _FREE_OPS or op.endswith("-done"):
            return []
        if op == "fusion":
            return self._fusion_traffic(ins)
        if op == "dynamic-update-slice":
            # in-place: read update + write slice, NOT the whole buffer
            # (XLA aliases operand 0)
            upd = (shape_bytes(sym[ins.operands[1]])
                   if len(ins.operands) > 1 and ins.operands[1] in sym
                   else shape_bytes(ins.shape))
            return [(CATEGORY_DUS, 2.0 * upd)]
        if op == "dynamic-slice":
            return [(CATEGORY_DSLICE, 2.0 * shape_bytes(ins.shape))]
        if op == "gather":
            idx = (shape_bytes(sym[ins.operands[1]])
                   if len(ins.operands) > 1 and ins.operands[1] in sym
                   else 0)
            return [(CATEGORY_GATHER, 2.0 * shape_bytes(ins.shape) + idx)]
        if op == "scatter":
            upd = (shape_bytes(sym[ins.operands[2]])
                   if len(ins.operands) > 2 and ins.operands[2] in sym
                   else shape_bytes(ins.shape))
            idx = (shape_bytes(sym[ins.operands[1]])
                   if len(ins.operands) > 1 and ins.operands[1] in sym
                   else 0)
            return [(CATEGORY_SCATTER, 2.0 * upd + idx)]
        # generic: result write + operand reads
        b = float(shape_bytes(ins.shape))
        for o in ins.operands:
            if o in sym:
                b += shape_bytes(sym[o])
        return [(_category(op), b)]

    # ---- walker --------------------------------------------------------

    def cost_of(self, name: str) -> Cost:
        if name in self._memo:
            return self._memo[name]
        comp = self.comps.get(name)
        total = Cost()
        self._memo[name] = total        # cycle guard
        if comp is None:
            return total
        sym = comp.symtab()
        for ins in comp.instrs:
            op = ins.opcode
            if op == "dot":
                total.flops += self._dot_flops(ins, sym)
            elif op == "convolution":
                total.flops += self._conv_flops(ins, sym)
            elif op == "while":
                trips = self.module.trip_count(ins)
                body = re.search(r"body=%?([\w\.\-]+)", ins.attrs)
                cond = re.search(r"condition=%?([\w\.\-]+)", ins.attrs)
                if body:
                    total.add(self.cost_of(body.group(1)), trips)
                if cond:
                    total.add(self.cost_of(cond.group(1)), trips)
            elif op == "call":
                for c in self._called(ins):
                    total.add(self.cost_of(c))
            elif op == "conditional":
                branches = [self.cost_of(c) for c in self._called(ins)]
                if branches:
                    total.add(max(branches, key=lambda c: c.bytes))
            elif op in ("fusion", "map", "reduce", "reduce-window",
                        "sort", "scatter", "select-and-scatter"):
                # internals: flops + collectives yes, bytes no (billed at
                # the boundary by instr_traffic)
                for c in self._called(ins):
                    sub = self.cost_of(c)
                    total.flops += sub.flops
                    for k in COLLECTIVE_OPS:
                        total.coll[k] += sub.coll[k]
            kind = _collective_kind(op)
            if kind and not op.endswith("-start"):
                # '-start' skipped: its tuple shape carries the input;
                # the output is billed once at the '-done' (or bare op)
                total.coll[kind] += shape_bytes(ins.shape)
            for cat, b in self.instr_traffic(ins, sym):
                total.add_bytes(cat, b)
        self._memo[name] = total
        return total

    def entry_cost(self) -> Cost:
        comp = self.module.entry()
        if comp is None:
            return Cost()
        return self.cost_of(comp.name if not comp.is_entry else ENTRY)

    # ---- profiling -----------------------------------------------------

    def attribute(self, top: int = 20, min_bytes: float = 1e11):
        """Per-(opcode, shape) byte tally with trip multipliers -- the
        §Perf profiling view (what dominates the memory term?)."""
        tally: collections.Counter = collections.Counter()

        def walk(name: str, mult: float):
            comp = self.comps.get(name)
            if comp is None:
                return
            sym = comp.symtab()
            for ins in comp.instrs:
                if ins.opcode == "while":
                    t = self.module.trip_count(ins)
                    b = re.search(r"body=%?([\w\.\-]+)", ins.attrs)
                    c = re.search(r"condition=%?([\w\.\-]+)", ins.attrs)
                    if b:
                        walk(b.group(1), mult * t)
                    if c:
                        walk(c.group(1), mult * t)
                    continue
                if ins.opcode == "call":
                    for c in self._called(ins):
                        walk(c, mult)
                    continue
                if ins.opcode == "conditional":
                    # mirror cost_of: bill the most expensive branch
                    branches = self._called(ins)
                    if branches:
                        walk(max(branches,
                                 key=lambda b: self.cost_of(b).bytes),
                             mult)
                    continue
                b = sum(v for _, v in self.instr_traffic(ins, sym))
                if not b:
                    continue
                bm = b * mult
                key = (ins.opcode,
                       ins.shape[:48] if bm > min_bytes else "(small)")
                tally[key] += bm

        comp = self.module.entry()
        if comp is not None:
            walk(comp.name, 1)
        return tally.most_common(top)
