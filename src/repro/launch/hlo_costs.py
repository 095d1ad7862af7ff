"""Loop-aware cost extraction from optimized HLO text.

XLA's `compiled.cost_analysis()` counts while-loop bodies ONCE, which
silently underestimates any scanned program (layer scans, n_d/n_g SGD
loops, microbatch accumulation) by the trip count. The optimized HLO
carries `known_trip_count` on while ops, so we parse the module into
computations, build the call graph, and aggregate costs with each while
body multiplied by its trip count.

Extracted per program:
  flops            dot/convolution FLOPs (2*M*N*K), trip-corrected
  hbm_bytes        Σ over materializing instructions of operand+result
                   bytes (fusions are XLA's memory-traffic units; this is
                   a no-reuse traffic model), trip-corrected
  collective_bytes Σ operand bytes of all-gather / all-reduce /
                   reduce-scatter / all-to-all / collective-permute
                   (all-reduce counted twice: RS+AG), trip-corrected
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*((?:\([^)]*\)|[\w\[\],{}\s/*]+?))\s+"
    r"([\w\-]+)\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_TRIP_RE = re.compile(r'known_trip_count[":{]+n[":]+(\d+)')
_CALLED_RE = re.compile(r"(?:body|to_apply|calls)=%?([\w.\-]+)")

_SKIP_OPS = {"parameter", "constant", "get-tuple-element", "tuple",
             "bitcast", "copy", "after-all", "partition-id", "replica-id",
             "reshape"}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _shape_elems(type_str: str):
    m = _SHAPE_RE.search(type_str)
    if not m:
        return None, None
    dims = [int(d) for d in m.group(2).split(",") if d] or [1]
    return m.group(1), dims


@dataclasses.dataclass
class _Instr:
    name: str
    type_str: str
    op: str
    line: str


class HloModule:
    def __init__(self, text: str):
        self.computations: dict[str, list[_Instr]] = {}
        self.entry: str | None = None
        cur = None
        for line in text.splitlines():
            if not line.strip():
                continue
            if not line.startswith(" ") and ("->" in line) and line.rstrip().endswith("{"):
                m = _COMP_RE.match(line.strip())
                if m:
                    cur = m.group(1)
                    self.computations[cur] = []
                    if line.lstrip().startswith("ENTRY"):
                        self.entry = cur
                    continue
            if cur is None:
                continue
            if line.strip() == "}":
                cur = None
                continue
            m = _INSTR_RE.match(line)
            if m:
                self.computations[cur].append(
                    _Instr(m.group(1), m.group(2), m.group(3), line))

        # result-shape table for operand size lookups (global namespace is
        # fine: names are unique within the module dump)
        self.shape_of: dict[str, str] = {}
        for instrs in self.computations.values():
            for ins in instrs:
                self.shape_of[ins.name] = ins.type_str

    # ------------------------------------------------------------------
    def _dot_flops(self, ins: _Instr) -> float:
        # FLOPs = 2 * prod(result dims) * contraction size
        _, rdims = _shape_elems(ins.type_str)
        if rdims is None:
            return 0.0
        operands = re.findall(r"%([\w.\-]+)", ins.line.split("(", 1)[1])
        if not operands:
            return 0.0
        lhs = self.shape_of.get(operands[0], "")
        _, ldims = _shape_elems(lhs)
        if ldims is None:
            return 0.0
        cdims = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.line)
        contract = 1
        if cdims and cdims.group(1):
            for d in cdims.group(1).split(","):
                contract *= ldims[int(d)]
        rprod = 1
        for d in rdims:
            rprod *= d
        return 2.0 * rprod * contract

    def _conv_flops(self, ins: _Instr) -> float:
        _, rdims = _shape_elems(ins.type_str)
        operands = re.findall(r"%([\w.\-]+)", ins.line.split("(", 1)[1])
        if rdims is None or len(operands) < 2:
            return 0.0
        _, kdims = _shape_elems(self.shape_of.get(operands[1], ""))
        if kdims is None:
            return 0.0
        kprod = 1
        for d in kdims:
            kprod *= d
        rprod = 1
        for d in rdims:
            rprod *= d
        # 2 * out_elems * (kernel_elems / out_channels); out channel is the
        # last result dim under our NHWC convention — approximate.
        return 2.0 * rprod * max(kprod // max(rdims[-1], 1), 1)

    def _instr_costs(self, ins: _Instr):
        """(flops, hbm_bytes, collective_bytes_by_kind, called, trip)."""
        flops = 0.0
        hbm = 0.0
        coll: dict[str, float] = {}
        called, trip = None, 1

        if ins.op == "while":
            called = re.search(r"body=%?([\w.\-]+)", ins.line)
            called = called.group(1) if called else None
            t = _TRIP_RE.search(ins.line)
            if t:
                trip = int(t.group(1))
            else:
                cond = re.search(r"condition=%?([\w.\-]+)", ins.line)
                trip = self._trip_from_condition(cond.group(1)) if cond else 1
            return flops, hbm, coll, called, trip
        if ins.op in ("fusion", "call"):
            m = _CALLED_RE.search(ins.line)
            called = m.group(1) if m else None
        if ins.op == "conditional":
            # take the first branch computation as representative
            m = re.search(r"branch_computations=\{%?([\w.\-]+)", ins.line)
            if m:
                called = m.group(1)

        if ins.op == "dot":
            flops = self._dot_flops(ins)
        elif ins.op == "convolution":
            flops = self._conv_flops(ins)

        kind = next((c for c in COLLECTIVES if ins.op.startswith(c)), None)
        if kind and not ins.op.endswith("-done"):
            operands = re.findall(r"%([\w.\-]+)", ins.line.split("(", 1)[1])
            nbytes = sum(_shape_bytes(self.shape_of.get(o, ""))
                         for o in operands)
            if nbytes == 0:
                nbytes = _shape_bytes(ins.type_str)
            if kind == "all-gather":
                nbytes = max(nbytes, _shape_bytes(ins.type_str))
            if kind == "all-reduce":
                nbytes *= 2
            coll[kind] = coll.get(kind, 0.0) + nbytes

        if ins.op not in _SKIP_OPS and ins.op != "while":
            operands = re.findall(r"%([\w.\-]+)", ins.line.split("(", 1)[1])
            result_bytes = _shape_bytes(ins.type_str)
            op_bytes = [_shape_bytes(self.shape_of.get(o, ""))
                        for o in operands]
            root = ins.op
            if ins.op == "fusion" and called in self.computations:
                body = self.computations[called]
                if body:
                    root = body[-1].op   # ROOT is last
            if root == "dynamic-update-slice" or ins.op == "dynamic-update-slice":
                # in-place update (XLA aliases the buffer): traffic is the
                # modified region + small inputs, not the whole cache.
                hbm = 2.0 * sum(bb for bb in op_bytes if bb != result_bytes)
            elif ins.op in ("dynamic-slice", "gather"):
                hbm = 2.0 * result_bytes
            else:
                hbm = result_bytes + sum(op_bytes)

        return flops, hbm, coll, called, trip

    def _trip_from_condition(self, cond_name: str) -> int:
        """Recover a scan's trip count from its `lt(i, N)` condition:
        take the largest integer constant in the condition computation."""
        best = 1
        for ins in self.computations.get(cond_name, []):
            if ins.op == "constant":
                m = re.search(r"constant\((\d+)\)", ins.line)
                if m:
                    best = max(best, int(m.group(1)))
        return best

    # ------------------------------------------------------------------
    def runs(self) -> dict[str, int]:
        """How many times each computation runs per call of the entry:
        as often as its callers, times the trip count of the while loop
        whose body it is."""
        out: dict[str, int] = defaultdict(int)

        def visit(name: str, n: int):
            out[name] += n
            for ins in self.computations.get(name, []):
                _, _, _, called, trip = self._instr_costs(ins)
                if called in self.computations:
                    visit(called, n * trip)

        assert self.entry, "no ENTRY computation found"
        visit(self.entry, 1)
        return dict(out)

    # ------------------------------------------------------------------
    def totals(self):
        memo: dict[str, tuple] = {}

        def comp_totals(name: str):
            if name in memo:
                return memo[name]
            memo[name] = (0.0, 0.0, {}, {})  # cycle guard
            flops_t, hbm_t = 0.0, 0.0
            coll_t: dict[str, float] = defaultdict(float)
            cnt_t: dict[str, int] = defaultdict(int)
            for ins in self.computations.get(name, []):
                flops, hbm, coll, called, trip = self._instr_costs(ins)
                flops_t += flops
                hbm_t += hbm
                for k, v in coll.items():
                    coll_t[k] += v
                    cnt_t[k] += 1
                if called and called in self.computations:
                    cf, ch, cc, cn = comp_totals(called)
                    flops_t += trip * cf
                    # fusions are XLA's memory-traffic unit: their internal
                    # ops live in registers/cache — count only the call
                    # site's operands+result (already in `hbm` above).
                    if ins.op != "fusion":
                        hbm_t += trip * ch
                    for k, v in cc.items():
                        coll_t[k] += trip * v
                    for k, v in cn.items():
                        cnt_t[k] += trip * v
            memo[name] = (flops_t, hbm_t, dict(coll_t), dict(cnt_t))
            return memo[name]

        assert self.entry, "no ENTRY computation found"
        flops, hbm, coll, counts = comp_totals(self.entry)
        return {
            "flops": flops,
            "hbm_bytes": hbm,
            "collective_bytes": float(sum(coll.values())),
            "bytes_by_kind": coll,
            "counts": counts,
        }


def hlo_costs(hlo_text: str) -> dict:
    return HloModule(hlo_text).totals()
