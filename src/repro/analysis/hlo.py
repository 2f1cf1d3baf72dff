"""Loop-aware cost analysis of optimized HLO text.

``compiled.cost_analysis()`` counts every ``while`` body ONCE, regardless
of trip count — useless for scan-over-layers models (verified: a 10-step
scanned matmul reports 1 matmul of FLOPs).  This module re-derives the
three roofline inputs by parsing ``compiled.as_text()``:

* **FLOPs**  — 2*M*N*K for every ``dot`` (batch dims included), found in
  all computations (including fusion bodies), multiplied up by the trip
  count of every enclosing ``while``.
* **bytes**  — per-op surface traffic (result + operands) for ops in
  non-fused computations; fusion ops contribute their boundary bytes only
  (post-fusion traffic); ``dynamic-(update-)slice`` contributes the slice,
  not the sliced buffer (XLA updates in place); bitcast/tuple/gte free.
* **collective bytes** — per-device link traffic with ring-algorithm
  factors: all-reduce 2(n-1)/n, all-gather/reduce-scatter/all-to-all
  (n-1)/n, collective-permute 1; n = replica-group size parsed per op.

Trip counts come from the ``while`` condition computation: jax scans emit
``compare(iter, constant(N)), direction=LT`` — we take that N.

Validated in tests/test_hlo_analysis.py against hand-counted programs.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Optional

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2,
                "f8e4m3fn": 1, "f8e5m2": 1,
                "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1,
                "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(f8e4m3fn|f8e5m2|bf16|f16|f32|f64|pred|s4|s8|s16|"
                       r"s32|s64|u4|u8|u16|u32|u64|c64|c128)\[([\d,]*)\]")

_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+?)\s+"
                    r"([\w\-]+)\((.*)$")

_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)(?:\.clone)?\s*\((.*?)\)"
                          r"\s*->")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_ZERO_BYTE_OPS = {"bitcast", "tuple", "get-tuple-element", "parameter",
                  "constant", "after-all", "add-dependency", "while",
                  "conditional", "call", "partition-id", "replica-id",
                  "optimization-barrier"}

# ops a TPU-class fusion pass melts into producers/consumers: counted as
# zero HBM traffic in the default "fused" bytes model (the CPU backend
# leaves many of these unfused, which would otherwise overcount ~10x)
_FUSE_FREE_OPS = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "exponential", "exponential-minus-one", "log", "log-plus-one", "tanh",
    "negate", "abs", "sign", "compare", "select", "and", "or", "xor", "not",
    "convert", "broadcast", "iota", "rsqrt", "sqrt", "cbrt", "power",
    "clamp", "floor", "ceil", "round-nearest-afz", "round-nearest-even",
    "cosine", "sine", "tan", "atan2", "is-finite", "reduce-precision",
    "bitcast-convert", "remainder", "shift-left", "shift-right-logical",
    "shift-right-arithmetic", "population-count", "count-leading-zeros",
    "real", "imag", "complex", "expm1", "log1p", "logistic", "erf",
    "stochastic-convert", "map", "reverse",
}


def _shape_bytes(sig: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(sig):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(sig: str) -> list[int]:
    m = _SHAPE_RE.search(sig)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclass
class _Op:
    name: str
    result_sig: str
    opcode: str
    rest: str            # everything after the opening paren
    operands: list[str] = field(default_factory=list)


@dataclass
class _Computation:
    name: str
    ops: list[_Op] = field(default_factory=list)
    param_sigs: dict = field(default_factory=dict)
    fused: bool = False  # reached via fusion `calls=` (bytes not counted)


class HloCostModel:
    def __init__(self, hlo_text: str):
        self.comps: dict[str, _Computation] = {}
        self.entry: Optional[str] = None
        self._parse(hlo_text)
        self._mark_fused()
        self._memo_flops: dict[str, float] = {}
        self._memo_bytes: dict[str, float] = {}
        self._memo_coll: dict[str, dict] = {}

    # -- parsing -----------------------------------------------------------
    def _parse(self, text: str) -> None:
        cur: Optional[_Computation] = None
        for raw in text.splitlines():
            line = raw.rstrip()
            if not line:
                continue
            if not line.startswith(" ") and line.endswith("{"):
                m = _COMP_HDR_RE.match(line.strip())
                if m:
                    cur = _Computation(m.group(1))
                    for p in re.finditer(
                            r"([\w.\-]+)\s*:\s*"
                            r"((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\]"
                            r"(?:\{[0-9,]*\})?))",
                            m.group(2)):
                        cur.param_sigs[p.group(1)] = p.group(2)
                    self.comps[cur.name] = cur
                    if line.strip().startswith("ENTRY"):
                        self.entry = cur.name
                continue
            if line.strip() == "}":
                continue
            if cur is None:
                continue
            m = _OP_RE.match(line)
            if m:
                op = _Op(m.group(1), m.group(2), m.group(3), m.group(4))
                op.operands = re.findall(r"%([\w.\-]+)", m.group(4))
                cur.ops.append(op)

    def _mark_fused(self) -> None:
        for comp in self.comps.values():
            for op in comp.ops:
                if op.opcode == "fusion":
                    for callee in re.findall(r"calls=%?([\w.\-]+)", op.rest):
                        if callee in self.comps:
                            self.comps[callee].fused = True
                # reduce/sort/map/scatter appliers: tiny, mark fused so we
                # skip their byte accounting
                for callee in re.findall(r"to_apply=%?([\w.\-]+)", op.rest):
                    if callee in self.comps:
                        self.comps[callee].fused = True

    # -- helpers -----------------------------------------------------------
    def _result_bytes_of(self, comp: _Computation, name: str) -> int:
        if name in comp.param_sigs:
            return _shape_bytes(comp.param_sigs[name])
        for op in comp.ops:
            if op.name == name:
                return _shape_bytes(op.result_sig)
        return 0

    def _result_dims_of(self, comp: _Computation, name: str) -> list[int]:
        if name in comp.param_sigs:
            return _shape_dims(comp.param_sigs[name])
        for op in comp.ops:
            if op.name == name:
                return _shape_dims(op.result_sig)
        return []

    def _trip_count(self, cond_name: str) -> int:
        """jax scans: condition compares the s32 counter against a
        constant with direction=LT; take the largest such constant."""
        comp = self.comps.get(cond_name)
        if comp is None:
            return 1
        consts = []
        for op in comp.ops:
            if op.opcode == "constant" and "s32[]" in op.result_sig:
                m = re.search(r"constant\((-?\d+)\)", "constant(" + op.rest)
                if m:
                    consts.append(int(m.group(1)))
            m = re.match(r"constant\((-?\d+)\)", op.opcode + "(" + op.rest) \
                if False else None
        # also catch inline constant(N) text anywhere in the condition
        if not consts:
            for op in comp.ops:
                for m in re.finditer(r"constant\((\d+)\)", op.rest):
                    consts.append(int(m.group(1)))
        return max(consts) if consts else 1

    def _called(self, op: _Op) -> list[tuple[str, float]]:
        """(callee, multiplier) pairs for control-flow ops."""
        out = []
        if op.opcode == "while":
            body = re.search(r"body=%?([\w.\-]+)", op.rest)
            cond = re.search(r"condition=%?([\w.\-]+)", op.rest)
            trips = self._trip_count(cond.group(1)) if cond else 1
            if body:
                out.append((body.group(1), float(max(trips, 1))))
            if cond:
                out.append((cond.group(1), float(max(trips, 1))))
        elif op.opcode in ("fusion", "call", "map", "reduce", "reduce-window",
                           "scatter", "sort", "select-and-scatter"):
            for callee in re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)",
                                     op.rest):
                out.append((callee, 1.0))
        elif op.opcode == "conditional":
            for m in re.finditer(r"branch_computations=\{([^}]*)\}", op.rest):
                for c in re.findall(r"%?([\w.\-]+)", m.group(1)):
                    out.append((c, 1.0))  # upper bound: all branches
        return out

    # -- FLOPs ---------------------------------------------------------------
    def _dot_flops(self, comp: _Computation, op: _Op) -> float:
        out_elems = 1
        for d in _shape_dims(op.result_sig):
            out_elems *= d
        lhs = op.operands[0] if op.operands else None
        k = 1
        m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.rest)
        if lhs is not None and m:
            dims = self._result_dims_of(comp, lhs)
            for i in [int(x) for x in m.group(1).split(",") if x]:
                if i < len(dims):
                    k *= dims[i]
        return 2.0 * out_elems * k

    def flops(self, comp_name: Optional[str] = None) -> float:
        name = comp_name or self.entry
        if name in self._memo_flops:
            return self._memo_flops[name]
        comp = self.comps.get(name)
        if comp is None:
            return 0.0
        total = 0.0
        for op in comp.ops:
            if op.opcode in ("dot", "convolution"):
                total += self._dot_flops(comp, op)
            for callee, mult in self._called(op):
                total += mult * self.flops(callee)
        self._memo_flops[name] = total
        return total

    # -- bytes ---------------------------------------------------------------
    def _op_bytes(self, comp: _Computation, op: _Op) -> float:
        """TPU-fusion-aware HBM traffic model: elementwise chains are free
        (they fuse); data-movement and matmul ops pay result + operands."""
        if op.opcode in _ZERO_BYTE_OPS or op.opcode in _FUSE_FREE_OPS:
            return 0.0
        res = _shape_bytes(op.result_sig)
        if op.opcode == "dynamic-update-slice":
            upd = (self._result_bytes_of(comp, op.operands[1])
                   if len(op.operands) > 1 else 0)
            return 2.0 * upd
        if op.opcode in ("dynamic-slice", "slice", "gather", "pad",
                         "copy", "transpose", "reshape"):
            return 2.0 * res
        if op.opcode in ("reduce", "reduce-window"):
            return res + self._result_bytes_of(comp, op.operands[0]) \
                if op.operands else res
        if op.opcode == "fusion":
            return self._fusion_bytes(comp, op)
        ops_b = sum(self._result_bytes_of(comp, o) for o in op.operands[:8])
        return res + ops_b

    _CAST_ONLY = {"convert", "bitcast", "parameter", "constant", "tuple",
                  "get-tuple-element", "copy-start", "copy-done"}

    def _fusion_bytes(self, comp: _Computation, op: _Op) -> float:
        """Fusion traffic with two TPU-realism corrections:

        * cast-only fusions (convert/bitcast of a whole buffer) are free —
          the CPU backend materializes f32 copies of bf16 buffers that a
          bf16-native TPU never would;
        * fusions containing a dynamic-update-slice are in-place updates:
          they pay for the updated slice (+ sliced reads), not the buffer.
        """
        callee_m = re.search(r"calls=%?([\w.\-]+)", op.rest)
        callee = self.comps.get(callee_m.group(1)) if callee_m else None
        res = _shape_bytes(op.result_sig)
        if callee is None:
            return res + sum(self._result_bytes_of(comp, o)
                             for o in op.operands[:8])
        kinds = {o.opcode for o in callee.ops}
        if kinds <= self._CAST_ONLY:
            return 0.0
        if "dynamic-update-slice" in kinds:
            total = 0.0
            for o in callee.ops:
                if o.opcode == "dynamic-update-slice" and len(o.operands) > 1:
                    total += 2.0 * self._result_bytes_of(callee,
                                                         o.operands[1])
                elif o.opcode in ("dynamic-slice", "slice", "gather", "pad",
                                  "copy", "transpose", "reshape"):
                    total += 2.0 * _shape_bytes(o.result_sig)
            return total
        return res + self._fusion_operand_bytes(comp, op, callee)

    def _fusion_operand_bytes(self, comp: _Computation, op: _Op,
                              callee: Optional[_Computation] = None) -> float:
        """Operand traffic of a fusion: an operand that is only
        (dynamic-)sliced inside the fused computation pays the slice sizes,
        not the full buffer (scan bodies slice stacked params in fusions)."""
        if callee is None:
            callee_m = re.search(r"calls=%?([\w.\-]+)", op.rest)
            callee = self.comps.get(callee_m.group(1)) if callee_m else None
        # fusion operands map positionally to callee params param_0..param_N
        total = 0.0
        for i, operand in enumerate(op.operands):
            full = self._result_bytes_of(comp, operand)
            if callee is None:
                total += full
                continue
            pname_prefix = f"param_{i}"
            consumers = [o for o in callee.ops
                         if any(x == pname_prefix
                                or x.startswith(pname_prefix + ".")
                                for x in o.operands[:1] + o.operands[1:2])]
            if consumers and all(c.opcode in ("dynamic-slice", "slice",
                                              "gather")
                                 for c in consumers):
                total += sum(2.0 * _shape_bytes(c.result_sig)
                             for c in consumers)
            else:
                total += full
        return total

    def bytes_accessed(self, comp_name: Optional[str] = None) -> float:
        name = comp_name or self.entry
        if name in self._memo_bytes:
            return self._memo_bytes[name]
        comp = self.comps.get(name)
        if comp is None:
            return 0.0
        total = 0.0
        for op in comp.ops:
            if not comp.fused:
                total += self._op_bytes(comp, op)
            for callee, mult in self._called(op):
                if op.opcode == "fusion":
                    continue  # fusion internals: boundary already counted
                total += mult * self.bytes_accessed(callee)
        self._memo_bytes[name] = total
        return total

    # -- collectives -----------------------------------------------------------
    def _group_size(self, op: _Op) -> int:
        m = re.search(r"replica_groups=\{\{([\d,]*)\}", op.rest)
        if m:
            return len([x for x in m.group(1).split(",") if x])
        m = re.search(r"replica_groups=\[(\d+),(\d+)\]", op.rest)
        if m:  # iota format [groups, size]
            return int(m.group(2))
        return 2

    def _coll_link_bytes(self, op: _Op) -> float:
        n = max(self._group_size(op), 2)
        size = _shape_bytes(op.result_sig)
        kind = op.opcode.replace("-start", "")
        if kind == "all-reduce":
            return 2.0 * size * (n - 1) / n
        if kind in ("all-gather", "all-to-all"):
            return size * (n - 1) / n
        if kind == "reduce-scatter":
            return size  # result is already the scattered shard; input n x
        if kind == "collective-permute":
            return size
        return 0.0

    def collectives(self, comp_name: Optional[str] = None) -> dict:
        name = comp_name or self.entry
        if name in self._memo_coll:
            return self._memo_coll[name]
        comp = self.comps.get(name)
        out = {k: {"count": 0.0, "link_bytes": 0.0} for k in COLLECTIVES}
        if comp is None:
            return out
        for op in comp.ops:
            kind = op.opcode.replace("-start", "")
            if kind in COLLECTIVES and not op.opcode.endswith("-done"):
                out[kind]["count"] += 1
                out[kind]["link_bytes"] += self._coll_link_bytes(op)
            for callee, mult in self._called(op):
                sub = self.collectives(callee)
                for k in COLLECTIVES:
                    out[k]["count"] += mult * sub[k]["count"]
                    out[k]["link_bytes"] += mult * sub[k]["link_bytes"]
        self._memo_coll[name] = out
        return out

    # -- tagged subtrees --------------------------------------------------------
    def _comp_matches(self, name: str, pattern: str, _seen=None) -> bool:
        if _seen is None:
            _seen = set()
        if name in _seen:
            return False
        _seen.add(name)
        comp = self.comps.get(name)
        if comp is None:
            return False
        rx = re.compile(pattern)
        for op in comp.ops:
            if rx.search(op.rest):
                return True
            for callee, _ in self._called(op):
                if self._comp_matches(callee, pattern, _seen):
                    return True
        return False

    def _has_matching_inner_while(self, name: str, pattern: str) -> bool:
        """Does this computation (transitively) contain a while whose body
        matches the pattern?"""
        comp = self.comps.get(name)
        if comp is None:
            return False
        for op in comp.ops:
            for callee, _ in self._called(op):
                if op.opcode == "while":
                    body = re.search(r"body=%?([\w.\-]+)", op.rest)
                    if body and callee == body.group(1) \
                            and self._comp_matches(callee, pattern):
                        return True
                if self._has_matching_inner_while(callee, pattern):
                    return True
        return False

    def tagged_while_bytes(self, pattern: str) -> float:
        """Total bytes (trip-multiplied) of every INNERMOST ``while``
        subtree whose body matches ``pattern`` (e.g. an einsum label in op
        metadata).  Outer scans that merely contain a matching inner scan
        are not tagged.  Used to attribute the jnp chunked-attention
        scan's HBM traffic so the Pallas-kernel projection can substitute
        it (``benchmarks/flash_projection``)."""
        total = 0.0

        def walk(name: str, mult: float, inside: bool) -> None:
            nonlocal total
            comp = self.comps.get(name)
            if comp is None:
                return
            for op in comp.ops:
                if inside and not comp.fused:
                    total += mult * self._op_bytes(comp, op)
                for callee, k in self._called(op):
                    if op.opcode == "fusion" and inside:
                        continue
                    sub_inside = inside
                    if op.opcode == "while" and not inside:
                        body = re.search(r"body=%?([\w.\-]+)", op.rest)
                        if body and callee == body.group(1) \
                                and self._comp_matches(callee, pattern) \
                                and not self._has_matching_inner_while(
                                    callee, pattern):
                            sub_inside = True
                    if op.opcode == "fusion" and not inside:
                        continue
                    walk(callee, mult * k, sub_inside)

        walk(self.entry, 1.0, False)
        return total

    def summary(self) -> dict:
        coll = self.collectives()
        return {
            "flops": self.flops(),
            "bytes": self.bytes_accessed(),
            "collectives": {k: {"count": v["count"],
                                "link_bytes": v["link_bytes"]}
                            for k, v in coll.items()},
            "collective_link_bytes": sum(v["link_bytes"]
                                         for v in coll.values()),
        }


# -- candidate cost ranking (the measured autotuner's pruning stage) -----------
#
# The joint tuner (repro/tuning/search.py) proposes a cross product of
# per-key layouts x per-kernel tiles plus per-segment layout flips — far
# more configurations than it can afford to time.  CostRanker turns the
# HEURISTIC plan's compiled region HLO into a traffic baseline (the true
# post-fusion bytes the program moves) and ranks each candidate by that
# baseline plus an analytic penalty the caller derives from the
# candidate's layout plan (relayout traffic, strided field access).
# Only the top-ranked candidates are ever measured; the rest are pruned.

# analytic per-access penalty factors on a record's storage bytes: a
# layout whose fields are interleaved (AoS) reads each field with stride
# num_components — on vector hardware that wastes a fraction of every
# cache line / VREG load; AoSoA amortizes the stride over its lane tile;
# SoA streams each field contiguously.  These are RANKING weights for
# pruning, not absolute costs — the survivors still get measured.
LAYOUT_PENALTY_FACTORS = {"AOS": 0.5, "AOSOA": 0.125, "SOA": 0.0}


def layout_access_penalty(layout_name: str, storage_bytes: float,
                          num_fields: int = 2) -> float:
    """Analytic strided-access penalty bytes for touching one record
    stored under ``layout_name`` (single-field records pay nothing —
    every layout stores them contiguously)."""
    if num_fields <= 1:
        return 0.0
    return LAYOUT_PENALTY_FACTORS.get(layout_name, 0.0) * storage_bytes


@dataclass(frozen=True)
class CandidateCost:
    """One ranked tuning candidate: the shared HLO base traffic plus the
    candidate's analytic penalty."""

    label: str
    penalty_bytes: float
    predicted_bytes: float

    def describe(self) -> str:
        return (f"{self.label}: predicted {self.predicted_bytes:.3e} B "
                f"(penalty {self.penalty_bytes:.3e} B)")


class CostRanker:
    """Rank joint (layout x tile) tuning candidates.

    Built from the heuristic plan's compiled region HLO texts
    (``Executor.region_hlo`` per device region); :meth:`rank` orders
    candidates by ``base_bytes + penalty_bytes`` ascending, with a
    STABLE sort so the caller controls tie-breaking by pre-ordering its
    entries (the tuner orders ties nearest-to-default-tile first).
    """

    def __init__(self, hlo_texts):
        self.models = [HloCostModel(t) for t in hlo_texts]
        self.base_bytes = float(sum(m.bytes_accessed()
                                    for m in self.models))
        self.base_flops = float(sum(m.flops() for m in self.models))

    def predict(self, penalty_bytes: float) -> float:
        """Predicted traffic of one candidate: the heuristic plan's HLO
        bytes plus the candidate's analytic penalty."""
        return self.base_bytes + float(penalty_bytes)

    def rank(self, entries) -> list[CandidateCost]:
        """``entries`` is an iterable of ``(label, penalty_bytes)``;
        returns :class:`CandidateCost` rows sorted cheapest-first
        (stable: equal predictions keep the caller's order)."""
        costs = [CandidateCost(label, float(p), self.predict(p))
                 for label, p in entries]
        return sorted(costs, key=lambda c: c.predicted_bytes)

    def describe(self) -> str:
        return (f"HLO cost base: {self.base_flops:.3e} flops, "
                f"{self.base_bytes:.3e} bytes over "
                f"{len(self.models)} device region(s)")


def normalize_cost_analysis(cost) -> dict:
    """``Compiled.cost_analysis()`` as a plain dict (``{}`` for None)."""
    return dict(cost or {})


def analyze_hlo(hlo_text: str) -> dict:
    return HloCostModel(hlo_text).summary()
