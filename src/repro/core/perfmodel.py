"""Analytical performance model fed by the characterization results.

This is the paper's *raison d'être* (Section I: accurate per-instruction
latencies make performance models like PPT-GPU accurate).
:class:`HloLatencyEstimator` prices a lowered HLO module with *measured*
per-op latencies from the LatencyDB: the simulator-feeding use case.
Dynamic (trip-count-rolled) instruction counts, a two-term
``max(compute, memory)`` estimate whose memory term comes from the measured
pointer-chase ladder, and a :class:`PricedReport` diagnosis with an
explicit coverage fraction. :class:`ServingPoint` parses the
``serving.<phase>.<cell>`` rows the ``repro.api.ServingCostProbe`` writes
(predicted-vs-measured, docs/serving.md). :class:`HardwareSpec` holds the
datasheet peaks of the suite's targets (``configs/paper_suite.py``).
"""
from __future__ import annotations

import dataclasses
import functools
import re

from repro.core import hlo_analysis
from repro.core.latency_db import LatencyDB, LatencyRecord
from repro.utils import parse_kv_notes


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops: float          # per chip, bf16
    hbm_bw: float              # bytes/s per chip
    ici_bw: float              # bytes/s per link
    hbm_bytes: float           # capacity per chip
    clock_hz: float = 0.0

    @property
    def arithmetic_intensity_knee(self) -> float:
        return self.peak_flops / self.hbm_bw


# Datasheet peaks of one chip.
TPU_V5E = HardwareSpec(name="tpu-v5e", peak_flops=197e12, hbm_bw=819e9,
                       ici_bw=50e9, hbm_bytes=16 * 2**30, clock_hz=1.7e9)
# For completeness / cross-checks when running measured benches on this host.
CPU_HOST = HardwareSpec(name="cpu-host", peak_flops=1e11, hbm_bw=2e10,
                        ici_bw=1e10, hbm_bytes=64 * 2**30, clock_hz=3e9)


@dataclasses.dataclass(frozen=True)
class ClassCost:
    """One op-class row of a :class:`PricedReport` breakdown."""

    ns: float = 0.0
    instances: float = 0.0       # dynamic op instances (trip-count weighted)
    elements: float = 0.0        # dynamic result elements across instances

    def _plus(self, ns: float, instances: float, elements: float) -> "ClassCost":
        return ClassCost(self.ns + ns, self.instances + instances,
                         self.elements + elements)


@dataclasses.dataclass(frozen=True)
class PricedReport:
    """Full diagnosis of one :meth:`HloLatencyEstimator.estimate` call.

    ``total_ns = launch_ns + max(compute_ns, memory_ns) + collective_ns``:
    the serial-issue instruction estimate and the measured memory estimate
    overlap on hardware, so the slower term bounds the on-chip module
    (two-term roofline over measured rows); the interconnect term — priced
    from the measured collective ladder — is serial with both (a dependent
    collective stalls the shard) and adds on top, and so does the measured
    host round trip of one call (``clock_overhead``), which every timed
    execution of the module pays.
    ``coverage`` is the fraction of countable dynamic op instances priced
    from an actual DB row — instances priced at ``default_ns`` (no mapping,
    or mapping with no measured row) count against it, structural
    data-movement ops (:data:`hlo_analysis.STRUCTURAL_OPS`) count in neither
    direction.
    """

    total_ns: float
    compute_ns: float
    memory_ns: float
    coverage: float
    priced_instances: float
    unpriced_instances: float
    by_class: dict[str, ClassCost]
    unpriced_opcodes: tuple[tuple[str, float], ...]   # (opcode, dyn count)
    bytes_accessed: float
    opt_level: str
    # additive interconnect term from the measured collective ladder
    # (``coll.<kind>.d<N>.<bytes>`` rows); 0.0 for unsharded modules, so the
    # pre-collective report shape is unchanged
    collective_ns: float = 0.0
    # one call's host round trip (the ``clock_overhead`` row); 0.0 without it
    launch_ns: float = 0.0

    @property
    def bound(self) -> str:
        if self.collective_ns > max(self.compute_ns, self.memory_ns):
            return "collective"
        return "compute" if self.compute_ns >= self.memory_ns else "memory"

    def summary(self) -> str:
        miss = ", ".join(f"{op}x{c:g}" for op, c in self.unpriced_opcodes[:4])
        coll = (f" coll={self.collective_ns:.1f}"
                if self.collective_ns else "")
        launch = (f" launch={self.launch_ns:.1f}" if self.launch_ns else "")
        return (f"{self.total_ns:.1f}ns ({self.bound}-bound: "
                f"comp={self.compute_ns:.1f} mem={self.memory_ns:.1f}"
                f"{coll}{launch}), coverage={self.coverage:.1%}"
                + (f", unpriced: {miss}" if miss else ""))


@dataclasses.dataclass(frozen=True)
class MemoryRung:
    """One measured rung of the DB's pointer-chase ladder."""

    working_set_bytes: int
    ns_per_line: float
    line_bytes: int
    source: str                  # "inkernel" | "host"


@dataclasses.dataclass(frozen=True)
class CollectiveRung:
    """One measured rung of the DB's collective ladder, keyed by HLO kind."""

    kind: str                    # HLO opcode kind ("all-reduce", ...)
    devices: int                 # ladder mesh size (== HLO group size target)
    wire_bytes: float            # ring-convention wire bytes one step moved
    ns: float                    # measured slope: ns per chained collective


# The streaming-bandwidth row (repro.core.membench.prepare_stream).
STREAM_OP = "mem.stream"
# Elements one device issue covers when no row says otherwise: an (8, 128)
# float32 vreg, the in-kernel chains' default tile.
DEVICE_ISSUE_ELEMS = 8 * 128


class _EstimatedNs(float):
    """A float that carries its :class:`PricedReport` (see ``estimate_ns``)."""

    report: PricedReport


_MEM_ROW_RE = re.compile(r"^(?:mem\.chase\.ws|inkernel\.mem\.)(\d+)$")
_TILE_RE = re.compile(r"tile=\((\d+), *(\d+)\)")
_COLL_ROW_RE = re.compile(
    r"^coll\.(psum|all_gather|reduce_scatter|ppermute)\.d(\d+)\.(\d+)$")


class HloLatencyEstimator:
    """Price a lowered HLO module from measured per-op latencies.

    The simulator-feeding use case (PPT-GPU-style): dynamic instruction
    counts x measured table latencies. Counts are **trip-count aware**
    (:meth:`hlo_analysis.ModuleCost.dynamic_histogram`): an op inside a
    scanned layer stack counts once per iteration, so decode-step modules are
    no longer underpriced by the layer count. The estimate has two terms:

    * **compute**: Σ over dynamic op instances of ``issue latency +
      (elements-1)/lanes x THROUGHPUT_FACTOR x latency`` — one issue plus
      lane-amortized per-element throughput. ``dot``/``convolution`` price
      their FLOPs/2 as fma-equivalents through the same formula. Opcodes with
      no mapped or measured row are priced at ``default_ns`` and reported in
      ``unpriced_opcodes`` instead of being silently skipped. Custom-calls
      resolving through :data:`hlo_analysis.CUSTOM_CALL_TARGETS` to a
      measured ``inkernel.fused.<name>`` row are priced by HBM footprint
      against the row's certified unit bytes; unresolved targets are
      reported per target as ``custom-call:<target>``.
    * **memory**: the module's rolled-up HBM bytes priced from the measured
      pointer-chase ladder (``inkernel.mem.<N>`` preferred over the host twin
      ``mem.chase.ws<N>``): the rung covering the module's footprint gives
      ns/line, amortized over ``mem_streams`` concurrent streams (a dependent
      chase measures pure latency; streamed traffic overlaps).
    * **collective**: each HLO-parsed
      :class:`~repro.core.hlo_analysis.CollectiveOp` priced from the covering
      measured ladder rung (``coll.<kind>.d<N>.<bytes>`` rows,
      :meth:`collective_ladder`): ``wire_bytes / rung_wire x rung_ns``, per
      kind, env-filtered. A kind with no measured rung is *never*
      default-priced — it counts against coverage as ``collective:<kind>``.
    * **launch**: the measured host round trip of one call
      (``clock_overhead`` at the estimator's opt level), which a timed
      execution pays once whatever the module holds.

    ``total = launch + max(compute, memory) + collective`` — the on-chip
    terms overlap in hardware; a dependent collective stalls the shard and
    the call's round trip adds on top.

    Which rows price compute depends on where they are the device's own
    time. On the CPU the dispatch-level chains are, and the
    in-kernel rows come from the Pallas interpreter. On an accelerator the
    reverse holds: a dispatch-level chain of a few thousand ops runs inside
    the host round trip of one call (about 0.5 ms on a v5e) and its slope is
    noise, while an in-kernel row is a millisecond-long chain on the device.
    So when the ``backend`` filter names anything but the CPU
    (``device_rows``), the compute term reads ``inkernel.<row>`` only,
    takes one issue to cover the row's tile (``tile=`` in its notes, e.g.
    1024 elements for an (8, 128) vreg) instead of ``lanes`` — and so does a
    ``default_ns`` issue (:data:`DEVICE_ISSUE_ELEMS`) — and skips rows
    that are not :attr:`~repro.core.latency_db.LatencyRecord.resolved`. The
    in-kernel rows are VPU chains, not matrix-unit work: there a ``dot`` is
    left unpriced (reported in ``unpriced_opcodes``) rather than priced as
    vector FMAs.

    Where the DB holds a streaming-bandwidth row (``mem.stream``, ns per
    line moved by a one-pass elementwise program), the memory term prices
    the module's bytes with it directly; a chase rung measures the latency
    of one dependent load, not the rate of streamed traffic, and remains the
    fallback.
    """

    THROUGHPUT_FACTOR = 0.25     # per-element cost fraction once issued

    def __init__(self, db: LatencyDB, opt_level: str = "O3",
                 lanes: int = 8, default_ns: float = 5.0,
                 mem_streams: int = 8, filters: dict[str, str] | None = None):
        self.db = db
        self.opt_level = opt_level
        self.lanes = lanes
        self.default_ns = default_ns
        self.mem_streams = mem_streams
        # env filters (device_kind/backend/jax_version): a DB accumulates
        # runs across devices, and pricing one device's module with another
        # device's rows would be meaningless (compare_markdown's rule)
        self.filters = dict(filters) if filters else {}
        # the rows of any backend but the CPU are the device's own
        self.device_rows = self.filters.get("backend", "cpu") != "cpu"
        # elements one default-priced issue covers: a vreg on the device
        self.issue_lanes = DEVICE_ISSUE_ELEMS if self.device_rows else lanes

    # ------------------------------------------------------------- lookups
    def _latest(self, op: str, opt_level: str) -> LatencyRecord | None:
        recs = self.db.query(op=op, opt_level=opt_level, **self.filters)
        return max(recs, key=lambda r: r.measured_at) if recs else None

    def _table_latency(self, table_op: str) -> tuple[float, bool, int]:
        """(latency ns, was a measured row found, elements one issue
        covers). Falls back from the exact table row to its base row
        (``sub.float32`` -> ``sub``) before resorting to ``default_ns``.
        With ``device_rows`` the rows are ``inkernel.<row>`` (always O3) and
        must be resolved."""
        for op in dict.fromkeys((table_op, table_op.split(".")[0])):
            if not self.device_rows:
                lat = self.db.lookup_ns(op, self.opt_level, **self.filters)
                if lat is not None:
                    return lat, True, self.lanes
                continue
            rec = self._latest(f"inkernel.{op}", "O3")
            if rec is not None and rec.resolved:
                tile = _TILE_RE.search(rec.notes)
                lanes = (int(tile[1]) * int(tile[2]) if tile
                         else self.issue_lanes)
                return rec.latency_ns, True, lanes
        return self.default_ns, False, self.issue_lanes

    def _fused_row(self, name: str) -> tuple[float, float] | None:
        """``(ns_per_unit, unit_bytes)`` of a measured fused-kernel row.

        ``unit_bytes`` — the HBM footprint of one workload unit, certified
        by the dataflow audit — is the scaling denominator: a zoo-model
        custom-call moving ``B`` bytes costs ``B / unit_bytes`` row units.
        Preferred source is the row's own notes (FusedKernelProbe persists
        ``unit_bytes=N``); older rows fall back to re-deriving the
        certificate, and rows with neither are unusable for pricing."""
        recs = self.db.query(op=f"inkernel.fused.{name}",
                             opt_level=self.opt_level, **self.filters)
        if not recs:
            return None
        rec = sorted(recs, key=lambda r: r.measured_at)[-1]
        unit_bytes = float(parse_kv_notes(rec.notes).get("unit_bytes", 0.0)
                           or 0.0)
        if unit_bytes <= 0:
            try:
                from repro.audit.dataflow import fused_unit
                from repro.inkernel.fused import FUSED_LENS

                unit_bytes = float(
                    fused_unit(name, FUSED_LENS.interpret)["bytes"])
            except Exception:  # noqa: BLE001 - uncertifiable row: no pricing
                return None
        if unit_bytes <= 0:
            return None
        return rec.latency_ns, unit_bytes

    def memory_ladder(self) -> list[MemoryRung]:
        """Measured chase rungs in the DB, ascending by working set.

        Only unsuffixed rows participate (``inkernel.mem.8192.vmem`` is a
        forced-residency experiment, not the hierarchy); where both the
        in-kernel row and its host twin exist at one working set, the
        in-kernel (device-side) number wins.
        """
        rungs: dict[int, MemoryRung] = {}
        for r in self.db.query(category="memory", **self.filters):
            m = _MEM_ROW_RE.match(r.op)
            if not m or r.opt_level != self.opt_level:
                continue
            ws = int(m.group(1))
            source = "inkernel" if r.op.startswith("inkernel.") else "host"
            if ws in rungs and rungs[ws].source == "inkernel" and source == "host":
                continue
            lm = re.search(r"(?:line|stride)=(\d+)", r.notes)
            line = int(lm.group(1)) if lm else 64
            rungs[ws] = MemoryRung(working_set_bytes=ws,
                                   ns_per_line=r.latency_ns,
                                   line_bytes=line, source=source)
        return sorted(rungs.values(), key=lambda g: g.working_set_bytes)

    def collective_ladder(self) -> dict[str, list[CollectiveRung]]:
        """Measured collective rungs in the DB, grouped by HLO kind and
        ascending by wire bytes.

        Only unsuffixed ``coll.<kind>.d<N>.<bytes>`` rows participate (a
        lens-suffixed row is a different fidelity experiment, exactly like
        the memory ladder's rule). The rung's wire bytes come from the
        probe's own notes (ring-convention, ``repro.parallel.ladders``);
        older rows without the note fall back to re-deriving them from the
        recorded payload, and rows with neither are unusable for pricing.
        """
        rungs: dict[str, list[CollectiveRung]] = {}
        for r in self.db.query(category="collective", **self.filters):
            m = _COLL_ROW_RE.match(r.op)
            if not m or r.opt_level != self.opt_level:
                continue
            kind = hlo_analysis.LADDER_TO_COLLECTIVE[m.group(1)]
            devices = int(m.group(2))
            kv = parse_kv_notes(r.notes)
            wire = float(kv.get("wire_bytes", 0.0) or 0.0)
            if wire <= 0:
                payload = float(kv.get("payload_bytes", m.group(3)) or 0.0)
                if m.group(1) == "all_gather":
                    result = payload * devices
                elif m.group(1) == "reduce_scatter":
                    result = payload / max(devices, 1)
                else:
                    result = payload
                wire = hlo_analysis.ring_factor(kind, devices) * result
            if wire <= 0:
                continue
            rungs.setdefault(kind, []).append(
                CollectiveRung(kind=kind, devices=devices, wire_bytes=wire,
                               ns=r.latency_ns))
        for kind in rungs:
            rungs[kind].sort(key=lambda g: g.wire_bytes)
        return rungs

    def _memory_ns(self, bytes_accessed: float) -> float:
        """Price HBM traffic: at the measured streaming rate (``mem.stream``)
        when the DB has it, else off the chase ladder — the rung whose
        working set covers the module's footprint (else the deepest rung)
        gives ns/byte, and ``mem_streams`` concurrent streams amortize the
        serial-chase latency."""
        if bytes_accessed <= 0:
            return 0.0
        stream = self._latest(STREAM_OP, self.opt_level)
        if stream is not None and stream.latency_ns > 0:
            line = re.search(r"line=(\d+)", stream.notes)
            return bytes_accessed * stream.latency_ns / (
                int(line[1]) if line else 64)
        ladder = self.memory_ladder()
        if not ladder:
            return 0.0
        rung = next((g for g in ladder if g.working_set_bytes >= bytes_accessed),
                    ladder[-1])
        ns_per_byte = rung.ns_per_line / rung.line_bytes
        return bytes_accessed * ns_per_byte / max(self.mem_streams, 1)

    # ------------------------------------------------------------- pricing
    def _instance_ns(self, latency: float, elements: float,
                     instances: float = 1.0, lanes: int | None = None) -> float:
        """Issue latency per instance + lane-amortized per-element throughput."""
        extra = max(elements - instances, 0.0)
        lanes = lanes or self.lanes
        return instances * latency + (extra / lanes) * self.THROUGHPUT_FACTOR * latency

    def estimate(self, hlo_text: str) -> PricedReport:
        """Price a module; returns the full :class:`PricedReport` diagnosis."""
        mc = hlo_analysis.ModuleCost(hlo_text)
        hist = mc.dynamic_histogram()
        by_class: dict[str, ClassCost] = {}
        unpriced_ops: dict[str, float] = {}
        compute = priced = unpriced = 0.0
        matmul_instances = 0.0

        def account(cls: str, ns: float, count: float, elems: float) -> None:
            by_class[cls] = by_class.get(cls, ClassCost())._plus(ns, count, elems)

        for (opcode, elems), count in sorted(hist.items()):
            if count <= 0 or opcode in hlo_analysis.STRUCTURAL_OPS:
                continue
            if opcode == "custom-call":
                continue            # priced per call site below (fused rows)
            if opcode in ("dot", "convolution"):
                matmul_instances += count
                continue            # priced below from dynamic FLOPs
            table_op = hlo_analysis.HLO_TO_TABLE.get(opcode)
            if table_op is None:
                ns = count * self._instance_ns(self.default_ns, elems,
                                               lanes=self.issue_lanes)
                compute += ns
                unpriced += count
                unpriced_ops[opcode] = unpriced_ops.get(opcode, 0.0) + count
                account("unpriced", ns, count, count * elems)
                continue
            lat, covered, lanes = self._table_latency(table_op)
            ns = count * self._instance_ns(lat, elems, lanes=lanes)
            compute += ns
            if covered:
                priced += count
                account(_table_category(table_op), ns, count, count * elems)
            else:
                unpriced += count
                unpriced_ops[opcode] = unpriced_ops.get(opcode, 0.0) + count
                account("unpriced", ns, count, count * elems)

        # Custom-calls: per call site, not per opcode. A site whose target
        # resolves through CUSTOM_CALL_TARGETS to a measured
        # ``inkernel.fused.<name>`` row is priced by HBM footprint —
        # ``executions x call_bytes / unit_bytes x row_ns`` — the two-size
        # slope already netted launch + DMA out of row_ns, so scaling by the
        # certified unit bytes is the same per-unit algebra the probe used.
        # Everything else stays default-priced and is reported per *target*
        # (``custom-call:<target>``), not lumped under one opaque opcode.
        for target, cbytes, execs, rest in mc.dynamic_custom_calls():
            if execs <= 0:
                continue
            name = hlo_analysis.resolve_custom_call(target, rest)
            row = self._fused_row(name) if name else None
            if row is not None:
                row_ns, unit_bytes = row
                ns = execs * (cbytes / unit_bytes) * row_ns
                compute += ns
                priced += execs
                account(f"fused:{name}", ns, execs, 0.0)
            else:
                ns = execs * self.default_ns
                compute += ns
                unpriced += execs
                label = f"custom-call:{target or '?'}"
                unpriced_ops[label] = unpriced_ops.get(label, 0.0) + execs
                account("unpriced", ns, execs, 0.0)

        if matmul_instances:
            dyn_flops = mc.dynamic_flops()
            fmas = (dyn_flops.get("dot", 0.0)
                    + dyn_flops.get("convolution", 0.0)) / 2.0
            if self.device_rows:        # no matrix-unit row: see the class doc
                lat, covered, lanes = self.default_ns, False, self.issue_lanes
                fmas = matmul_instances
            else:
                lat, covered, lanes = self._table_latency("fma.float32")
            ns = self._instance_ns(lat, fmas, instances=matmul_instances,
                                   lanes=lanes)
            compute += ns
            account("matmul", ns, matmul_instances, fmas)
            if covered:
                priced += matmul_instances
            else:
                unpriced += matmul_instances
                unpriced_ops["dot"] = unpriced_ops.get("dot", 0.0) + matmul_instances

        # Collectives: each parsed (trip-weighted) CollectiveOp is priced
        # from the *covering* measured ladder rung of its kind — the first
        # rung whose wire bytes reach the op's, else the largest — scaled
        # linearly: ``executions x wire_bytes / rung_wire x rung_ns``. Rungs
        # measured at the op's group size are preferred; a kind with no
        # measured rung at all is NEVER default-priced — it counts against
        # coverage and is reported as ``collective:<kind>`` so a sharded
        # prediction can't look measurement-backed when its interconnect
        # term is fiction. Zero-wire ops (group size 1) are free and count
        # in neither direction.
        collective_ns = 0.0
        coll_ladder: dict[str, list[CollectiveRung]] | None = None
        for c in mc.total().collectives:
            if c.executions <= 0:
                continue
            if c.group_size <= 1 or c.wire_bytes <= 0:
                continue
            if coll_ladder is None:
                coll_ladder = self.collective_ladder()
            rungs = coll_ladder.get(c.kind, [])
            sized = [g for g in rungs if g.devices == c.group_size] or rungs
            rung = next((g for g in sized if g.wire_bytes >= c.wire_bytes),
                        sized[-1] if sized else None)
            if rung is not None:
                ns = c.executions * (c.wire_bytes / rung.wire_bytes) * rung.ns
                collective_ns += ns
                priced += c.executions
                account("collective", ns, c.executions, 0.0)
            else:
                unpriced += c.executions
                label = f"collective:{c.kind}"
                unpriced_ops[label] = unpriced_ops.get(label, 0.0) + c.executions
                account("unpriced", 0.0, c.executions, 0.0)

        bytes_accessed = mc.total().bytes
        memory_ns = self._memory_ns(bytes_accessed)
        if memory_ns:
            account("memory", memory_ns, 0.0, 0.0)
        launch = self._latest("clock_overhead", self.opt_level)
        launch_ns = launch.latency_ns if launch is not None else 0.0
        if launch_ns:
            account("launch", launch_ns, 1.0, 0.0)
        countable = priced + unpriced
        return PricedReport(
            total_ns=launch_ns + max(compute, memory_ns) + collective_ns,
            compute_ns=compute, memory_ns=memory_ns,
            collective_ns=collective_ns, launch_ns=launch_ns,
            coverage=priced / countable if countable else 1.0,
            priced_instances=priced, unpriced_instances=unpriced,
            by_class=by_class,
            unpriced_opcodes=tuple(sorted(unpriced_ops.items(),
                                          key=lambda kv: (-kv[1], kv[0]))),
            bytes_accessed=bytes_accessed, opt_level=self.opt_level)

    def estimate_ns(self, hlo_text: str) -> float:
        """Total estimate as a float, with the :class:`PricedReport` attached
        as ``.report`` — callers that only compare magnitudes keep working,
        callers that need the diagnosis (what fraction was actually priced?)
        no longer have to re-run the analysis."""
        report = self.estimate(hlo_text)
        out = _EstimatedNs(report.total_ns)
        out.report = report
        return out


# ------------------------------------------------------------------ serving
@dataclasses.dataclass(frozen=True)
class ServingPoint:
    """One ``serving.<phase>.<cell>`` row, parsed back from its record.

    The record's ``latency_ns`` is the *measured* wall clock of the lowered
    prefill / decode-step executable; the estimator's prediction and its
    diagnosis ride along in the notes (``predicted_ns=... coverage=...``),
    so predicted-vs-measured never needs a second lookup.
    """

    phase: str                   # "prefill" | "decode"
    batch: int
    prompt_len: int
    measured_ns: float
    predicted_ns: float
    compute_ns: float
    memory_ns: float
    coverage: float
    model: str = ""
    # sharded (tp>1) cells: tensor-parallel degree, interconnect term and
    # the count of collective instances the estimator could NOT price from
    # a measured rung (0 = fully measurement-backed interconnect)
    tp: int = 1
    collective_ns: float = 0.0
    coll_unpriced: float = 0.0

    @property
    def ratio(self) -> float:
        """predicted / measured (1.0 = perfect model)."""
        return self.predicted_ns / self.measured_ns if self.measured_ns else 0.0

    @property
    def abs_log10_error(self) -> float:
        """|log10(predicted/measured)| — the CI tolerance metric: symmetric
        in over/under-prediction and stable across cell magnitudes."""
        import math

        if self.measured_ns <= 0 or self.predicted_ns <= 0:
            return float("inf")
        return abs(math.log10(self.predicted_ns / self.measured_ns))


def servingpoint_from_record(rec: LatencyRecord) -> ServingPoint:
    """Parse a ``serving.*`` :class:`LatencyRecord` back into its point."""
    kv = parse_kv_notes(rec.notes)
    parts = rec.op.split(".")
    assert parts[0] == "serving" and len(parts) >= 3, rec.op
    return ServingPoint(
        phase=kv.get("phase", parts[1]),
        batch=int(kv["batch"]), prompt_len=int(kv["prompt"]),
        measured_ns=rec.latency_ns,
        predicted_ns=float(kv["predicted_ns"]),
        compute_ns=float(kv.get("compute_ns", 0.0)),
        memory_ns=float(kv.get("memory_ns", 0.0)),
        coverage=float(kv.get("coverage", 0.0)),
        model=kv.get("model", ""),
        tp=int(kv.get("tp", 1)),
        collective_ns=float(kv.get("collective_ns", 0.0)),
        coll_unpriced=float(kv.get("coll_unpriced", 0.0)))


@dataclasses.dataclass(frozen=True)
class SloPoint:
    """One ``slo.r<rate>`` row: predicted-vs-measured serving SLOs at one
    arrival rate, parsed back from the record an :class:`~repro.api.SloProbe`
    persisted. The record's ``latency_ns`` is the measured p50 TTFT; the
    notes carry the full percentile set for both sides (ns), goodput (tok/s)
    and the estimator coverage of the priced prefill/decode modules.
    """

    rate_rps: float
    n_requests: int
    n_slots: int
    predicted: dict               # metric name -> value (pred_* keys, no prefix)
    measured: dict                # same metric names, measured side
    coverage: float
    model: str = ""

    METRICS = ("ttft_p50_ns", "ttft_p99_ns", "tpot_p50_ns", "tpot_p99_ns",
               "e2e_p50_ns", "goodput_tok_s")

    def abs_log10_error(self, metric: str) -> float:
        """|log10(pred/meas)| for one metric — same CI tolerance semantics
        as :attr:`ServingPoint.abs_log10_error`."""
        import math

        p, m = self.predicted.get(metric, 0.0), self.measured.get(metric, 0.0)
        if p is None or m is None or p <= 0 or m <= 0 \
                or math.isnan(p) or math.isnan(m):
            return float("inf")
        return abs(math.log10(p / m))


def slopoint_from_record(rec: LatencyRecord) -> SloPoint:
    """Parse an ``slo.*`` :class:`LatencyRecord` back into its point."""
    kv = parse_kv_notes(rec.notes)
    assert rec.op.split(".")[0] == "slo", rec.op

    def side(prefix: str) -> dict:
        return {m: float(kv[f"{prefix}_{m}"]) for m in SloPoint.METRICS
                if f"{prefix}_{m}" in kv}

    return SloPoint(
        rate_rps=float(kv["rate"]), n_requests=int(kv.get("n", 0)),
        n_slots=int(kv.get("slots", 0)),
        predicted=side("pred"), measured=side("meas"),
        coverage=float(kv.get("coverage", 0.0)),
        model=kv.get("model", ""))


def slo_markdown(points: "list[SloPoint]") -> str:
    """Markdown throughput-vs-latency table over :class:`SloPoint` rows —
    the ``serve-slo`` CLI's output. Latencies in ms, goodput in tok/s."""
    def ms(d: dict, key: str) -> str:
        v = d.get(key)
        return f"{v / 1e6:.3f}" if v is not None else "-"

    lines = ["| rate (req/s) | side | TTFT p50 | TTFT p99 | TPOT p50 "
             "| TPOT p99 | goodput (tok/s) | coverage |",
             "|---" * 8 + "|"]
    for pt in points:
        for side_name, d in (("predicted", pt.predicted),
                             ("measured", pt.measured)):
            good = d.get("goodput_tok_s")
            lines.append(
                f"| {pt.rate_rps:g} | {side_name} "
                f"| {ms(d, 'ttft_p50_ns')} | {ms(d, 'ttft_p99_ns')} "
                f"| {ms(d, 'tpot_p50_ns')} | {ms(d, 'tpot_p99_ns')} "
                f"| {good:.1f} | {pt.coverage:.1%} |"
                if good is not None else
                f"| {pt.rate_rps:g} | {side_name} | - | - | - | - | - "
                f"| {pt.coverage:.1%} |")
    return "\n".join(lines)


@functools.cache
def _table_category(table_op: str) -> str:
    """Registry category of a table row (``sub.float32`` -> ``fp32``);
    memory rows and unknown names fall back to sensible classes."""
    from repro.core import chains

    names = {o.name: o.category for o in chains.default_registry()}
    if table_op in names:
        return names[table_op]
    base = table_op.split(".")[0]
    return names.get(base, "uncategorized")
