"""HLO-text analysis: loop-aware flops / HBM bytes / collective bytes.

Why this exists: XLA:CPU's ``compiled.cost_analysis()`` counts each
``while``-loop *body once* — verified with a minimal scan reproducer
(scan of 10 matmuls reports the flops of 1).  Every interesting program
here is a ``lax.scan`` over layers with further scans inside (flash
attention kv blocks, rwkv/mamba chunks), so flops, bytes AND collective
traffic would be undercounted by 1-3 orders of magnitude.

This module parses the *optimized* HLO text instead:

1. split the module into named computations,
2. recover each while loop's trip count from its condition computation
   (XLA canonicalizes counted loops to ``compare(iv, constant), LT``),
3. build the call-graph multiplier: entry=1, while-body ×= trip count,
   fusions/calls ×= 1,
4. per computation, accumulate
   - dot/conv flops (2 × numel(out) × contraction size),
   - HBM traffic ≈ Σ over top-level instructions of 2 × output bytes —
     post-fusion instruction boundaries approximate real traffic — plus
     each ENTRY parameter's bytes once (the weights and cache read in),
   - collective payload bytes by kind,
   each scaled by the computation's multiplier.

Validated in tests/test_hlo_analysis.py against known-flop programs.

Roofline (TPU v5e targets):  compute = flops / 197e12,
memory = bytes / 819e9, collective = coll_bytes / 50e9 — all per chip
(SPMD HLO is already the per-partition program).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "token": 0, "s2": 1, "u2": 1,
}

_COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                     "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _parse_shape(s: str):
    """'bf16[16,4096,3072]' -> (dtype, [dims]); tuples -> list of both."""
    out = []
    for m in _SHAPE_RE.finditer(s):
        dt = m.group(1)
        if dt not in _DTYPE_BYTES:
            continue
        dims = [int(d) for d in m.group(2).split(",") if d]
        out.append((dt, dims))
    return out


def _shape_bytes(s: str) -> int:
    total = 0
    for dt, dims in _parse_shape(s):
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


def _numel(dims) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


@dataclass
class Computation:
    name: str
    instrs: list = field(default_factory=list)  # (var, out_shape_str, op, rest)
    shapes: dict = field(default_factory=dict)  # var -> shape str


_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*->.*\{$")
_INSTR = re.compile(
    r"^(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[\d,]*\](?:{[^}]*})?))\s*"
    r"([\w\-]+)\((.*)$")


def parse_hlo(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for raw in text.splitlines():
        line = raw.strip()
        if not line:
            continue
        m = _COMP_HDR.match(line)
        if m and line.endswith("{"):
            cur = Computation(m.group(1))
            comps[cur.name] = cur
            # parameters appear in the header; shapes resolved per-instr below
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        mi = _INSTR.match(line)
        if mi:
            var, shape, op, rest = mi.groups()
            cur.instrs.append((var, shape, op, rest))
            cur.shapes[var] = shape
    return comps


_TPU_KERNEL = re.compile(
    r"%([\w\-]+?)(?:\.\d+)?\s*=.*custom_call_target=\"tpu_custom_call\"")


def tpu_kernels(text: str) -> set[str]:
    """Names of the Pallas kernels compiled into a TPU executable's HLO
    (each ``pallas_call`` becomes a ``tpu_custom_call`` named after it)."""
    return set(_TPU_KERNEL.findall(text))


def _while_trip_count(cond: Computation) -> int:
    """Counted loops canonicalize to compare(iv, K), direction=LT."""
    const_vals = {}
    for var, shape, op, rest in cond.instrs:
        if op == "constant":
            m = re.match(r"([\-\d]+)", rest)
            if m and shape.startswith(("s32[]", "s64[]", "u32[]", "u64[]")):
                const_vals[var] = int(m.group(1))
    for var, shape, op, rest in cond.instrs:
        if op == "compare":
            refs = re.findall(r"%?([\w\.\-]+)", rest)
            for r in refs:
                if r in const_vals:
                    return max(const_vals[r], 1)
    return 1


def _dot_flops(shape_str: str, rest: str, shapes: dict) -> float:
    """dot: 2 × numel(out) × contraction size (from lhs shape + dims)."""
    out = _parse_shape(shape_str)
    if not out:
        return 0.0
    out_numel = _numel(out[0][1])
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", rest)
    ops = re.findall(r"%?([\w\.\-]+)", rest)
    lhs_shape = None
    for o in ops:
        if o in shapes:
            lhs_shape = _parse_shape(shapes[o])
            break
    if not m or not lhs_shape:
        return 2.0 * out_numel  # degenerate
    dims = [int(d) for d in m.group(1).split(",") if d]
    k = 1
    for d in dims:
        if d < len(lhs_shape[0][1]):
            k *= lhs_shape[0][1][d]
    return 2.0 * out_numel * k


_SKIP_TRAFFIC = {"parameter", "constant", "tuple", "get-tuple-element",
                 "bitcast", "while", "conditional", "call",
                 "get-dimension-size", "after-all", "partition-id"}
# computations entered via these edges run *inside* an op — their
# instructions do not individually touch HBM (the call site's operands and
# output are the traffic)
_INLINE_EDGE = re.compile(
    r"(?:calls=|to_apply=|comparator=|update_computation=|select=|scatter=)"
    r"%?([\w\.\-]+)")
_BRANCH_EDGE = re.compile(
    r"(?:(?:true|false)_computation=|on_true=|on_false=|branch_computations=\{)"
    r"%?([\w\.\-]+)")


@dataclass
class HLOCosts:
    flops: float = 0.0
    traffic_bytes: float = 0.0
    coll_bytes_by_kind: dict = field(default_factory=dict)
    coll_count_by_kind: dict = field(default_factory=dict)
    trip_counts: dict = field(default_factory=dict)
    breakdown: list = field(default_factory=list)  # (bytes, comp, var, op)

    @property
    def coll_bytes(self) -> float:
        return float(sum(self.coll_bytes_by_kind.values()))

    def coll_summary(self) -> str:
        ks = sorted(self.coll_bytes_by_kind)
        return ", ".join(
            f"{k}:{self.coll_count_by_kind[k]}x/{self.coll_bytes_by_kind[k]/1e6:.0f}MB"
            for k in ks) or "none"


def analyze_hlo(text: str) -> HLOCosts:
    comps = parse_hlo(text)
    m = re.search(r"^ENTRY\s+%?([\w\.\-]+)", text, re.M)
    entry = m.group(1) if m else next(iter(comps), None)

    # comp -> (multiplier, counts_traffic)
    mult: dict[str, float] = {name: 0.0 for name in comps}
    inline: dict[str, bool] = {name: False for name in comps}
    if entry in mult:
        mult[entry] = 1.0

    def callees(comp: Computation):
        """yield (callee, trip_multiplier, is_inline)."""
        for var, shape, op, rest in comp.instrs:
            if op == "while":
                mb = re.search(r"body=%?([\w\.\-]+)", rest)
                mc = re.search(r"condition=%?([\w\.\-]+)", rest)
                if mb and mc and mb.group(1) in comps and mc.group(1) in comps:
                    mt = re.search(r'known_trip_count[^0-9]*(\d+)', rest)
                    tc = int(mt.group(1)) if mt else _while_trip_count(
                        comps[mc.group(1)])
                    yield mb.group(1), float(tc), False
                    yield mc.group(1), float(tc), True  # cond: negligible traffic
            else:
                for mm in _INLINE_EDGE.finditer(rest):
                    if mm.group(1) in comps:
                        yield mm.group(1), 1.0, True
                for mm in _BRANCH_EDGE.finditer(rest):
                    if mm.group(1) in comps:
                        yield mm.group(1), 1.0, False

    changed, rounds = True, 0
    while changed and rounds < 64:
        changed = False
        rounds += 1
        for name, comp in comps.items():
            base = mult.get(name, 0.0)
            if base <= 0:
                continue
            for callee, k, is_inline in callees(comp):
                new = base * k
                if new > mult.get(callee, 0.0):
                    mult[callee] = new
                    inline[callee] = is_inline
                    changed = True

    costs = HLOCosts()
    for name, comp in comps.items():
        m_ = mult.get(name, 0.0)
        if m_ <= 0:
            continue
        count_traffic = not inline.get(name, False)
        for var, shape, op, rest in comp.instrs:
            if op == "while":
                mt = re.search(r'known_trip_count[^0-9]*(\d+)', rest)
                mc = re.search(r"condition=%?([\w\.\-]+)", rest)
                costs.trip_counts[var] = (
                    int(mt.group(1)) if mt else _while_trip_count(
                        comps.get(mc.group(1), Computation(""))) if mc else 1)
            if op == "parameter" and name == entry:
                # the program's inputs (weights, cache) are read from HBM
                # once; a consumer that fuses their unpack in produces
                # nothing of their size, so the output rule never sees them
                costs.traffic_bytes += _shape_bytes(shape)
            if op in ("dot", "convolution"):
                costs.flops += m_ * _dot_flops(shape, rest, comp.shapes)
            for kind in _COLLECTIVE_KINDS:
                if op == kind or op == kind + "-start":
                    b = _shape_bytes(shape)
                    costs.coll_bytes_by_kind[kind] = (
                        costs.coll_bytes_by_kind.get(kind, 0) + m_ * b)
                    costs.coll_count_by_kind[kind] = (
                        costs.coll_count_by_kind.get(kind, 0) + 1)
            if (count_traffic and op not in _SKIP_TRAFFIC
                    and not op.endswith("-done")):
                # OUTPUT-based traffic: every byte produced is written once
                # and read ~once downstream (2×out).  Operand sums would
                # massively overcount fusions that embed a dynamic-slice of
                # a large stacked buffer (they read a slice, not the buffer).
                # dynamic-update-slice aliases its big operand: charge the
                # update window, not the full result.
                out_b = _shape_bytes(shape)
                if op in ("dynamic-update-slice", "scatter"):
                    oper_str = rest.split(")")[0]
                    opers = [_shape_bytes(comp.shapes[o])
                             for o in re.findall(r"%?([\w\.\-]+)", oper_str)
                             if o in comp.shapes]
                    small = [b for b in opers if b < out_b]
                    out_b = max(small) if small else out_b
                costs.traffic_bytes += m_ * 2 * out_b
                if m_ * 2 * out_b > 1e9:
                    costs.breakdown.append(
                        (m_ * 2 * out_b, name, var, op, shape[:70]))
    costs.breakdown.sort(reverse=True)
    return costs


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

V5E = {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9}


@dataclass
class Roofline:
    flops: float               # per-chip loop-corrected HLO flops
    hbm_bytes: float
    coll_bytes: float
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops: float = 0.0
    collectives: str = ""

    @property
    def t_total(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """useful model flops per chip vs what the bottleneck allows."""
        if self.t_total <= 0:
            return 0.0
        return (self.model_flops / V5E["peak_flops"]) / self.t_total

    def row(self) -> dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective, "bottleneck": self.bottleneck,
            "model_flops": self.model_flops, "useful_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
            "collectives": self.collectives,
        }


def roofline_from_costs(costs: HLOCosts, *, chips: int, model_flops: float,
                        hw=V5E) -> Roofline:
    t_c = costs.flops / hw["peak_flops"]
    t_m = costs.traffic_bytes / hw["hbm_bw"]
    t_x = costs.coll_bytes / hw["ici_bw"]
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    return Roofline(costs.flops, costs.traffic_bytes, costs.coll_bytes,
                    t_c, t_m, t_x, bottleneck=max(terms, key=terms.get),
                    model_flops=model_flops / chips,
                    collectives=costs.coll_summary())
