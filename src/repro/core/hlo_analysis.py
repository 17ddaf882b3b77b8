"""Static analysis of compiled HLO text: the framework's "instruction counter".

Why this exists: ``compiled.cost_analysis()`` counts a ``while`` body exactly
once (verified empirically), so any scan-based model (layer stacks, blockwise
attention, SSM scans) is undercounted by the trip count. XLA's optimized HLO
carries ``backend_config={"known_trip_count":{"n":...}}`` on while ops, so we
parse the module into its computation call graph and roll costs up with trip
multipliers — a *corrected* whole-program {FLOPs, bytes, collective-wire
bytes}. This mirrors how the paper's latency tables are meant to be consumed
(static instruction counts priced per-op; PPT-GPU-style), and it is what
``perfmodel.HloLatencyEstimator`` prices.

Accounting conventions (matches XLA's):
  * dot FLOPs = 2 x prod(result dims) x prod(contracting dims);
  * elementwise/reduce FLOPs = result elements (transcendentals weighted by
    the LatencyDB in perfmodel.HloLatencyEstimator, not here);
  * bytes are counted at computation-op granularity (fusion internals are
    VMEM-resident and free; the fusion's operands + result are HBM traffic);
  * collective wire bytes use ring-algorithm factors over the result bytes;
  * while body costs x known_trip_count; conditional branches take max.
"""
from __future__ import annotations

import dataclasses
import re
from collections import Counter
from typing import Iterable

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "f8e5m2fnuz": 1, "f8e4m3b11fnuz": 1, "token": 0, "opaque": 0,
}

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                    "collective-permute")

# opcodes that do no math worth counting
_ZERO_FLOP = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast", "copy",
    "reshape", "transpose", "broadcast", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "pad", "iota", "convert", "reverse",
    "gather", "scatter", "select", "compare", "and", "or", "not", "xor",
    "after-all", "custom-call", "rng", "rng-bit-generator", "copy-start",
    "copy-done", "partition-id", "replica-id", "reduce-precision", "domain",
    "get-dimension-size", "optimization-barrier", "send", "recv", "send-done",
    "recv-done", "infeed", "outfeed",
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
# Result types may be tuples containing /*index=N*/ comments; opcodes are the
# first lowercase word followed by '(' after the type (layout tiles are 'T(').
_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([a-z][a-z0-9\-]*)\((.*)$")
_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.+\s*\{\s*$")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"')
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def ring_factor(kind: str, group: int) -> float:
    """Ring-algorithm wire bytes per result byte for one collective kind.

    Shared convention between this parser, the measured collective ladder
    (``repro.parallel.ladders``) and the estimator's pricing ratio — all
    three must agree on what one "wire byte" means.
    """
    if group <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * (group - 1) / group
    if kind == "all-gather":
        return (group - 1) / group
    if kind == "reduce-scatter":
        return float(group - 1)
    if kind == "all-to-all":
        return (group - 1) / group
    if kind == "collective-permute":
        return 1.0
    raise ValueError(kind)


_ring_factor = ring_factor

# measured-ladder row kind (``coll.<kind>.*``) <-> HLO collective opcode kind;
# the jax primitives each ladder kind lowers to are what the names say
# (lax.psum -> all-reduce, lax.psum_scatter -> reduce-scatter, ...)
LADDER_TO_COLLECTIVE = {
    "psum": "all-reduce",
    "all_gather": "all-gather",
    "reduce_scatter": "reduce-scatter",
    "ppermute": "collective-permute",
}
COLLECTIVE_TO_LADDER = {v: k for k, v in LADDER_TO_COLLECTIVE.items()}


def _shape_info(type_str: str) -> tuple[int, int]:
    """(total elements, total bytes) of an HLO type string (tuples summed)."""
    elems = 0
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = int(np.prod([int(d) for d in dims.split(",") if d])) if dims else 1
        elems += n
        total += n * _DTYPE_BYTES[dt]
    return elems, total


def _dims_of(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    dims = m.group(2)
    return [int(d) for d in dims.split(",") if d] if dims else []


@dataclasses.dataclass
class OpLine:
    name: str
    result_type: str
    opcode: str
    rest: str                 # text after the opening paren of operands
    operands: list[str]
    is_root: bool = False     # the computation's ROOT-marked op


@dataclasses.dataclass
class Computation:
    name: str
    ops: list[OpLine] = dataclasses.field(default_factory=list)
    shapes: dict[str, str] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    kind: str
    result_bytes: int
    group_size: int
    wire_bytes: float
    executions: float = 1.0   # trip-count multiplier
    line: str = ""


@dataclasses.dataclass
class StaticCost:
    flops: float = 0.0
    bytes: float = 0.0
    wire_bytes: float = 0.0
    collectives: list[CollectiveOp] = dataclasses.field(default_factory=list)

    def __add__(self, o: "StaticCost") -> "StaticCost":
        return StaticCost(self.flops + o.flops, self.bytes + o.bytes,
                          self.wire_bytes + o.wire_bytes,
                          self.collectives + o.collectives)

    def scaled(self, k: float) -> "StaticCost":
        return StaticCost(self.flops * k, self.bytes * k, self.wire_bytes * k,
                          [dataclasses.replace(c, executions=c.executions * k)
                           for c in self.collectives])


def parse_module(hlo_text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for line in hlo_text.splitlines():
        hdr = _COMP_HDR_RE.match(line)
        if hdr and ("->" in line):
            cur = Computation(hdr.group(1))
            comps[cur.name] = cur
            if line.lstrip().startswith("ENTRY"):
                comps["__entry__"] = cur
            continue
        if cur is None:
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, rtype, opcode, rest = m.groups()
        close = rest.find(")")
        seg = rest if close < 0 else rest[:close]
        operands = re.findall(r"%([\w.\-]+)", seg)
        op = OpLine(name=name, result_type=rtype, opcode=opcode, rest=rest,
                    operands=operands,
                    is_root=line.lstrip().startswith("ROOT "))
        cur.ops.append(op)
        cur.shapes[name] = rtype
    return comps


def _group_size(rest: str, num_partitions: int) -> int:
    m = _GROUPS_IOTA_RE.search(rest)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(rest)
    if m:
        return len([t for t in m.group(1).split(",") if t.strip() != ""])
    if "source_target_pairs" in rest:
        return 2
    return num_partitions


class ModuleCost:
    """Roll program cost up the computation call graph with trip counts."""

    def __init__(self, hlo_text: str):
        self.comps = parse_module(hlo_text)
        mp = re.search(r"num_partitions=(\d+)", hlo_text)
        self.num_partitions = int(mp.group(1)) if mp else 1
        self._memo: dict[tuple[str, bool], StaticCost] = {}
        self._exec_memo: tuple[dict[str, float], set[str]] | None = None

    # ------------------------------------------------------------------ ops
    def _dot_flops(self, comp: Computation, op: OpLine) -> float:
        rdims = _dims_of(op.result_type)
        contracting = 1
        mc = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.rest)
        if mc and op.operands:
            lhs_shape = comp.shapes.get(op.operands[0])
            if lhs_shape:
                ldims = _dims_of(lhs_shape)
                for i in (int(x) for x in mc.group(1).split(",") if x):
                    if i < len(ldims):
                        contracting *= ldims[i]
        return 2.0 * float(np.prod(rdims, dtype=np.float64) if rdims else 1.0) * contracting

    def _conv_flops(self, comp: Computation, op: OpLine) -> float:
        rdims = _dims_of(op.result_type)
        out = float(np.prod(rdims, dtype=np.float64)) if rdims else 1.0
        if len(op.operands) > 1:
            kshape = comp.shapes.get(op.operands[1])
            if kshape:
                kd = _dims_of(kshape)
                # flops = 2 * out_elems * kernel_spatial*in_features (rough)
                if len(kd) >= 2:
                    return 2.0 * out * float(np.prod(kd[:-1], dtype=np.float64))
        return 2.0 * out

    def _op_cost(self, comp: Computation, op: OpLine, in_fusion: bool) -> StaticCost:
        c = StaticCost()
        elems, rbytes = _shape_info(op.result_type)
        kind = next((k for k in COLLECTIVE_KINDS
                     if op.opcode == k or op.opcode.startswith(k + "-start")), None)
        if kind and not op.opcode.endswith("-done"):
            group = _group_size(op.rest, self.num_partitions)
            wire = _ring_factor(kind, group) * rbytes
            c.wire_bytes += wire
            c.collectives.append(CollectiveOp(kind=kind, result_bytes=rbytes,
                                              group_size=group, wire_bytes=wire))
            if kind in ("all-reduce", "reduce-scatter"):
                c.flops += elems
            if not in_fusion:
                c.bytes += rbytes * 2
            return c

        if op.opcode == "dot":
            c.flops += self._dot_flops(comp, op)
        elif op.opcode == "convolution":
            c.flops += self._conv_flops(comp, op)
        elif op.opcode in ("reduce", "reduce-window"):
            if op.operands:
                oshape = comp.shapes.get(op.operands[0])
                c.flops += _shape_info(oshape)[0] if oshape else elems
        elif op.opcode in ("fusion", "while", "call", "conditional", "map",
                           "sort", "scatter", "gather"):
            pass  # handled via call graph / zero-flop
        elif op.opcode not in _ZERO_FLOP:
            c.flops += elems  # elementwise & transcendental: 1/elem

        if not in_fusion and op.opcode not in ("parameter", "constant", "tuple",
                                               "get-tuple-element", "bitcast"):
            if op.opcode == "fusion":
                c.bytes += self._fusion_bytes(comp, op, rbytes)
            elif op.opcode == "dynamic-update-slice":
                # in-place on TPU: read the update + write the slice, not the
                # whole buffer (XLA's own bytes-accessed overcounts this).
                ub = (_shape_info(comp.shapes.get(op.operands[1], ""))[1]
                      if len(op.operands) > 1 else rbytes)
                c.bytes += 2 * ub
            elif op.opcode == "dynamic-slice":
                c.bytes += 2 * rbytes
            else:
                # HBM traffic at top level: operands + result
                ob = sum(_shape_info(comp.shapes.get(o, ""))[1] for o in op.operands)
                c.bytes += ob + rbytes
        return c

    def _fusion_bytes(self, comp: Computation, op: OpLine, rbytes: int) -> float:
        """Fusion HBM traffic = result + operands, EXCEPT operands that are
        consumed inside the fusion only through a dynamic-slice (XLA fuses the
        slice; the hardware streams the slice, not the whole buffer — without
        this, a scanned layer stack counts its full stacked weights once per
        iteration: ~100x phantom traffic, observed on llama3-405b)."""
        total = float(rbytes)
        callee_name = None
        m = re.search(r"calls=%?([\w.\-]+)", op.rest)
        if m:
            callee_name = m.group(1)
        callee = self.comps.get(callee_name) if callee_name else None
        params: dict[int, str] = {}
        uses: dict[str, list[OpLine]] = {}
        if callee is not None:
            for cop in callee.ops:
                if cop.opcode == "parameter":
                    mi = re.match(r"\s*(\d+)", cop.rest)
                    if mi:
                        params[int(mi.group(1))] = cop.name
                for o in cop.operands:
                    uses.setdefault(o, []).append(cop)
        for i, oname in enumerate(op.operands):
            full = _shape_info(comp.shapes.get(oname, ""))[1]
            pname = params.get(i)
            consumers = uses.get(pname, []) if pname else []
            if consumers and all(c.opcode == "dynamic-slice" for c in consumers):
                sliced = sum(_shape_info(c.result_type)[1] for c in consumers)
                total += min(sliced, full)
            else:
                total += full
        return total

    # ------------------------------------------------------------- rollup
    def _called(self, op: OpLine) -> list[tuple[str, float, str]]:
        """(computation, multiplier, kind) called by this op."""
        out = []
        if op.opcode == "fusion":
            m = re.search(r"calls=%?([\w.\-]+)", op.rest)
            if m:
                out.append((m.group(1), 1.0, "fusion"))
        elif op.opcode == "while":
            m = re.search(r"body=%?([\w.\-]+)", op.rest)
            trip = 1.0
            mt = _TRIP_RE.search(op.rest)
            if mt:
                trip = float(mt.group(1))
            if m:
                out.append((m.group(1), trip, "while"))
        elif op.opcode in ("call", "async-start"):
            m = re.search(r"(?:to_apply|calls)=%?([\w.\-]+)", op.rest)
            if m:
                out.append((m.group(1), 1.0, "call"))
        elif op.opcode == "conditional":
            for m in re.finditer(r"(?:branch_computations=\{([^}]*)\}|"
                                 r"(?:true|false)_computation=%?([\w.\-]+))", op.rest):
                names = (m.group(1) or m.group(2) or "")
                for n in re.findall(r"%?([\w.\-]+)", names):
                    out.append((n, 1.0, "cond"))
        return out

    def comp_cost(self, name: str, in_fusion: bool = False) -> StaticCost:
        key = (name, in_fusion)
        if key in self._memo:
            return self._memo[key]
        comp = self.comps.get(name)
        total = StaticCost()
        if comp is None:
            return total
        self._memo[key] = total  # guard cycles
        cond_costs: list[StaticCost] = []
        for op in comp.ops:
            total += self._op_cost(comp, op, in_fusion)
            for callee, mult, kind in self._called(op):
                child_in_fusion = in_fusion or kind == "fusion"
                child = self.comp_cost(callee, child_in_fusion)
                if kind == "cond":
                    cond_costs.append(child.scaled(mult))
                else:
                    total += child.scaled(mult)
        if cond_costs:
            best = max(cond_costs, key=lambda c: c.flops + c.bytes)
            total += best
        self._memo[key] = total
        return total

    def total(self) -> StaticCost:
        return self.comp_cost("__entry__")

    # -------------------------------------------------------------- insight
    def _execution_counts(self) -> tuple[dict[str, float], set[str]]:
        """Dynamic executions per computation, rolled down the call graph.

        Returns ``(execs, fused)``: how many times each computation runs per
        entry invocation (while bodies x ``known_trip_count``), and which
        computations only ever run inside a fusion (their HBM bytes are
        free). Fixpoint passes; call graphs here are shallow. Memoized: an
        estimator call walks the graph for the histogram, the flops profile
        and the byte rollup, and the counts never change.
        """
        if self._exec_memo is not None:
            return self._exec_memo
        execs: dict[str, float] = {"__entry__": 1.0}
        fused: set[str] = set()
        for _ in range(8):
            changed = False
            for name, comp in self.comps.items():
                e = execs.get(name, 0.0)
                if not e:
                    continue
                for op in comp.ops:
                    for callee, mult, kind in self._called(op):
                        val = e * mult
                        if kind == "fusion" or name in fused:
                            if callee not in fused:
                                fused.add(callee)
                                changed = True
                        if execs.get(callee, 0.0) < val:
                            execs[callee] = val
                            changed = True
            if not changed:
                break
        self._exec_memo = (execs, fused)
        return self._exec_memo

    def _walk_dynamic(self):
        """Yield ``(comp, op, executions, in_fusion)`` for every op line,
        weighted by the call-graph execution counts (entry aliases skipped)."""
        execs, fused = self._execution_counts()
        entry = self.comps.get("__entry__")
        for name, comp in self.comps.items():
            if comp is entry and name != "__entry__":
                continue
            e = 1.0 if name == "__entry__" else execs.get(name, 0.0)
            if not e:
                continue
            in_fusion = name in fused
            for op in comp.ops:
                yield comp, op, e, in_fusion

    def dynamic_histogram(self) -> dict[tuple[str, int], float]:
        """Dynamic op counts: ``{(opcode, result elements): executions}``.

        The trip-count-aware analog of :func:`op_histogram` — an op inside a
        while body with ``known_trip_count n`` counts ``n`` times, nested
        loops multiply. This is what makes decode-step pricing see every
        layer of a scanned stack instead of one (the flat histogram's
        underpricing bug).
        """
        hist: dict[tuple[str, int], float] = {}
        for _, op, e, _ in self._walk_dynamic():
            key = (op.opcode, _shape_info(op.result_type)[0])
            hist[key] = hist.get(key, 0.0) + e
        return hist

    def dynamic_flops(self) -> dict[str, float]:
        """Executions-weighted FLOPs per opcode (dot FLOPs use contracting
        dims, matching :meth:`total`); feeds matmul pricing in perfmodel."""
        out: dict[str, float] = {}
        for comp, op, e, in_fusion in self._walk_dynamic():
            f = self._op_cost(comp, op, in_fusion=in_fusion).flops
            if f:
                out[op.opcode] = out.get(op.opcode, 0.0) + f * e
        return out

    def dynamic_custom_calls(self) -> list[tuple[str, float, float, str]]:
        """Every custom-call line, executions-weighted.

        Returns ``(target, hbm_bytes, executions, rest)`` per call site:
        the ``custom_call_target`` string, the call's operand+result bytes
        (its HBM footprint — the quantity the fused-row pricing scales by),
        the trip-count-weighted execution count, and the raw op tail so
        :func:`resolve_custom_call` can scan lowering payloads (Mosaic
        embeds the kernel name in the ``tpu_custom_call`` config, not the
        target)."""
        out = []
        for comp, op, e, _ in self._walk_dynamic():
            if op.opcode != "custom-call":
                continue
            m = _CC_TARGET_RE.search(op.rest)
            target = m.group(1) if m else ""
            _, rbytes = _shape_info(op.result_type)
            ob = sum(_shape_info(comp.shapes.get(o, ""))[1]
                     for o in op.operands)
            out.append((target, float(ob + rbytes), e, op.rest))
        return out


def static_cost(hlo_text: str) -> StaticCost:
    return ModuleCost(hlo_text).total()


# -------------------------------------------------------- simple interfaces
def parse_collectives(hlo_text: str) -> list[CollectiveOp]:
    return static_cost(hlo_text).collectives


def collective_wire_bytes(hlo_text: str) -> float:
    return static_cost(hlo_text).wire_bytes


def collective_summary(hlo_text: str) -> dict[str, dict[str, float]]:
    summ: dict[str, dict[str, float]] = {}
    for c in parse_collectives(hlo_text):
        d = summ.setdefault(c.kind, {"count": 0, "result_bytes": 0.0, "wire_bytes": 0.0})
        d["count"] += c.executions
        d["result_bytes"] += c.result_bytes * c.executions
        d["wire_bytes"] += c.wire_bytes * c.executions
    return summ


# ---------------------------------------------------------------- histogram
HLO_TO_TABLE = {
    "add": "add.float32", "subtract": "sub.float32", "multiply": "mul.float32",
    "divide": "div.runtime.float32", "maximum": "max.float32", "minimum": "min.float32",
    "exponential": "ex2", "exponential-minus-one": "ex2", "log": "lg2",
    "log-plus-one": "lg2", "tanh": "tanh", "rsqrt": "rsqrt",
    "sqrt": "sqrt", "sine": "sin", "cosine": "cos", "abs": "abs", "negate": "sub",
    "and": "and", "or": "or", "xor": "xor", "not": "not",
    "shift-left": "shl", "shift-right-logical": "shr", "shift-right-arithmetic": "shr",
    "popcnt": "popc", "count-leading-zeros": "clz", "remainder": "rem.s",
    "power": "ex2", "logistic": "tanh",
}

# Custom-call targets the characterization pipeline has measured rows for:
# target (or lowering-payload substring) -> fused-kernel name, i.e. the stem
# of an ``inkernel.fused.<name>`` LatencyDB row whose two-size slope priced
# one workload unit of that kernel (see repro.inkernel.fused.FUSED_KERNELS
# and repro.audit.dataflow.fused_registry — the dataflow certificates carry
# each row's unit-bytes denominator). The estimator prices a resolved call
# as ``executions * call_bytes / unit_bytes * row_ns``; unresolved targets
# stay unpriced and count against coverage (HLO_TO_TABLE's veil rule).
CUSTOM_CALL_TARGETS = {
    "flash_attention": "flash_attention",
    "flash_decode": "flash_decode",
    "mamba_scan": "mamba_scan",
    "rmsnorm": "rmsnorm",
}

_CC_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')


def resolve_custom_call(target: str, rest: str = "") -> str | None:
    """Map one custom-call site to a measured fused-kernel row stem.

    Exact target match first (GPU lowerings name the kernel directly), then
    a registered-name substring scan over ``target`` + ``rest`` — TPU Pallas
    kernels all share the ``tpu_custom_call`` target and carry the kernel
    name only inside the serialized Mosaic config. Returns ``None`` for
    unknown targets: those must surface as ``custom-call:<target>`` in
    ``PricedReport.unpriced_opcodes``, never silently default-priced as a
    generic opcode."""
    if target in CUSTOM_CALL_TARGETS:
        return CUSTOM_CALL_TARGETS[target]
    hay = target + " " + rest
    for key, name in CUSTOM_CALL_TARGETS.items():
        if key in hay:
            return name
    return None


# Opcodes that are bookkeeping/data-movement, not issued arithmetic: excluded
# from the estimator's coverage denominator (an unmapped `multiply` lowers
# coverage; an unmapped `get-tuple-element` must not). Memory traffic they
# cause is captured by the byte rollup, i.e. the estimator's memory term.
# `custom-call` is deliberately NOT here: it is an opaque library/Pallas
# kernel of unknown — often dominant — cost, so it must count against
# coverage and show up in unpriced_opcodes rather than vanish.
STRUCTURAL_OPS = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast", "copy",
    "copy-start", "copy-done", "reshape", "transpose", "broadcast", "slice",
    "dynamic-slice", "dynamic-update-slice", "concatenate", "pad", "iota",
    "reverse", "after-all", "domain", "get-dimension-size",
    "optimization-barrier", "send", "recv", "send-done", "recv-done",
    "infeed", "outfeed", "partition-id", "replica-id", "fusion", "while",
    "call", "conditional", "map", "async-start", "async-done",
    "async-update", "rng-get-and-update-state",
})


def dynamic_op_histogram(hlo_text: str) -> Counter:
    """Trip-count-aware counts of (opcode, result elements).

    Unlike :func:`op_histogram` (flat: every op line counts once), ops inside
    ``while`` bodies are weighted by ``known_trip_count`` — the dynamic
    instruction counts a PPT-GPU-style consumer needs. Counts are floats
    (conditional branches and unrooted computations may contribute 0).
    """
    hist: Counter = Counter()
    for key, e in ModuleCost(hlo_text).dynamic_histogram().items():
        hist[key] += e
    return hist


def op_histogram(hlo_text: str) -> Counter:
    """Counts of (opcode, result elements) over every computation (no rollup)."""
    hist: Counter = Counter()
    comps = parse_module(hlo_text)
    seen: set[int] = set()
    for comp in comps.values():
        if id(comp) in seen:
            continue
        seen.add(id(comp))
        for op in comp.ops:
            elems, _ = _shape_info(op.result_type)
            hist[(op.opcode, elems)] += 1
    return hist


def flop_ops(hlo_text: str) -> dict[str, int]:
    out: dict[str, int] = {}
    for (opcode, n), count in op_histogram(hlo_text).items():
        out[opcode] = out.get(opcode, 0) + n * count
    return out
