"""Probe types: the unit of work a :class:`repro.api.Session` schedules.

A probe is one measurement with a stable identity. The identity — the
``(device_kind, backend, jax_version, opt_level, op, dtype)`` tuple — is
exactly a :class:`LatencyRecord` key, which is what makes the session's result
cache work: a probe whose key already exists in the DB is a cache hit and is
never re-run (unless forced).

Concrete probes wrap the existing measurement machinery:

* :class:`InstructionProbe` — one :class:`OpSpec` at one opt level via the
  dependent-chain slope method (paper Table II).
* :class:`MemoryProbe` — the pointer-chase hierarchy probe at one working-set
  size (paper Fig. 6).
* :class:`ClockOverheadProbe` — the cost of the timed region itself at one
  opt level (paper Fig. 5).
* :class:`StreamProbe` — streaming bandwidth, the rate the estimator prices
  a module's memory traffic at.
* :class:`KernelProbe` — an in-kernel (Pallas) dependent ALU chain, the
  device-side analog of the paper's timed PTX block.
* :class:`KernelChainProbe` — any registry :class:`OpSpec` lowered into a
  Pallas ``fori_loop`` chain (``repro.inkernel``): the paper's in-pipeline
  measurement, one probe per table row.
* :class:`MemoryChaseProbe` — the pointer chase *inside* a Pallas kernel at
  one working-set size, VMEM-resident below the footprint budget and
  HBM-streaming (``memory_space=ANY``) above — the in-kernel Table IV /
  Fig. 6 analog, one probe per ladder rung.
* :class:`ServingCostProbe` — the consumer side: one serving-engine
  prefill/decode cell, priced with the estimator against the session DB and
  wall-clock measured, predicted-vs-measured in one record (docs/serving.md).
* :class:`SloProbe` — the end-to-end consumer: one arrival rate's serving
  SLOs, a seeded trace replayed through both the LatencyDB-priced simulator
  and the engine's continuous-batching slot pool (``repro.traffic``),
  predicted-vs-measured percentiles in one record (docs/traffic.md).

New probe types (energy counters, occupancy sweeps, ...) subclass
:class:`Probe` and immediately gain caching, resumability and structured
failure handling from the session scheduler.
"""
from __future__ import annotations

import dataclasses
import os
import weakref
from typing import Any, Callable, Mapping

from repro import tracing
from repro.core import measure, membench
from repro.core.chains import OpSpec
from repro.core.latency_db import LatencyRecord
from repro.core.timing import Measurement, Timer
from repro.utils import timestamp


@dataclasses.dataclass(frozen=True)
class ProbeContext:
    """Session-owned machinery handed to every probe run."""

    timer: Timer
    env: Mapping[str, str]              # device_kind / backend / jax_version
    clock_hz: float
    baseline_ns: Callable[[str], float]  # per-level 1-cycle-class baseline
    device: Any = None                   # session's pinned jax device (None = default)
    db: Any = None                       # session's LatencyDB — lets consumer
                                         # probes (ServingCostProbe) price
                                         # against already-measured rows
    compile_cache: Any = None            # CompileCache — persisted executables
    adaptive: bool = False               # adaptive fidelity on: effective rep
                                         # counts ride in record notes


class Probe:
    """One schedulable measurement. Subclasses set identity + implement run.

    Attributes
    ----------
    op: table row name (e.g. ``"fma.float32"``, ``"mem.chase.ws8192"``).
    opt_level: compilation level the probe measures under.
    dtype: dtype axis of the record key.
    category: table grouping (reuses the paper's categories; new probe kinds
        add their own, e.g. ``"memory"``, ``"overhead"``, ``"kernel"``).

    Pipelining (docs/performance.md): probes may split their work into
    :meth:`prepare` — everything XLA-bound (lowering, compiling, cache
    loads), safe to run on the session's compile-ahead thread — and
    :meth:`run_prepared` — everything device-bound, always on the main
    thread so timing stays strictly serial on the device. The base-class
    defaults keep third-party probes working unchanged: ``prepare`` returns
    None and ``run_prepared(ctx, None)`` falls back to :meth:`run`.
    """

    op: str = ""
    opt_level: str = "O3"
    dtype: str = "float32"
    category: str = "uncategorized"

    def logical_key(self) -> tuple[str, str, str]:
        """Environment-independent identity, used for plan dedupe."""
        return (self.op, self.opt_level, self.dtype)

    def match_names(self) -> frozenset[str]:
        """Every name an op filter may address this probe by.

        Always contains the full derived ``op``; subclasses whose op names are
        derived from a base row (``inkernel.add`` from ``add``, fidelity
        suffixes like ``mem.chase.ws8192.s512-1536``) also answer to the base
        forms, so ``Plan.filter(ops=["add"])`` keeps a plan's ``inkernel.add``
        instead of silently dropping it. Exact-by-construction: ``add`` never
        matches the distinct registry row ``add.bfloat16``.
        """
        return frozenset((self.op,))

    def key(self, env: Mapping[str, str]) -> tuple:
        """Full cache key; identical layout to ``LatencyRecord.key()``."""
        return (env["device_kind"], env["backend"], env["jax_version"],
                self.opt_level, self.op, self.dtype)

    def run(self, ctx: ProbeContext) -> LatencyRecord:
        raise NotImplementedError

    def prepare(self, ctx: ProbeContext) -> Any:
        """XLA-bound half: compile this probe's callables, no device timing.

        Runs on the session's compile-ahead thread in pipelined mode (and
        inline in serial mode). The default returns None, which makes
        :meth:`run_prepared` fall back to :meth:`run` — third-party probes
        that only implement ``run`` keep working.
        """
        return None

    def run_prepared(self, ctx: ProbeContext, prepared: Any) -> LatencyRecord:
        """Device-bound half: time the callables ``prepare`` built."""
        return self.run(ctx)

    # ------------------------------------------------------------------ util
    def _record(self, ctx: ProbeContext, m: Measurement, *, guard: int = 0,
                notes: str = "", baseline: float | None = None) -> LatencyRecord:
        """Build the result record from a Measurement, netting out guards.

        ``baseline`` overrides the session's dispatch-level add baseline for
        probes whose guard ops run under a different methodology (in-kernel).
        """
        if ctx.adaptive:
            # the convergence rule may have stopped early (or banked reps may
            # have extended the run): persist the effective sample count
            notes = (notes + " " if notes else "") + f"reps_eff={m.n}"
        ns = max(m.median_ns, 0.0)
        if guard:
            base = baseline if baseline is not None else ctx.baseline_ns(self.opt_level)
        else:
            base = 0.0
        net = ns - guard * base
        if net < 0.0:
            # The guard subtraction went negative: the clamp below would
            # otherwise persist indistinguishably from a genuinely ~0 latency,
            # so flag the row for the auditor (repro.audit surfaces clamped=1
            # rows — a negative net usually means the declared guard count is
            # wrong or the baseline came from a different methodology).
            notes = (notes + " " if notes else "") + "clamped=1"
        return LatencyRecord(
            op=self.op, category=self.category, dtype=self.dtype,
            opt_level=self.opt_level, latency_ns=ns, mad_ns=m.mad_ns,
            cycles=ns * ctx.clock_hz / 1e9, guard=guard,
            net_latency_ns=max(net, 0.0), n_samples=m.n,
            measured_at=timestamp(), notes=notes, **ctx.env)

    def __repr__(self) -> str:
        return f"{type(self).__name__}({self.op}@{self.opt_level})"


class InstructionProbe(Probe):
    """One registry OpSpec at one opt level (paper Table II row x column)."""

    def __init__(self, spec: OpSpec, opt_level: str = "O3"):
        self.spec = spec
        self.op = spec.name
        self.opt_level = opt_level
        self.dtype = spec.dtype
        self.category = spec.category

    def run(self, ctx: ProbeContext) -> LatencyRecord:
        m = measure.measure_op_full(self.spec, self.opt_level, ctx.timer)
        return self._record(ctx, m, guard=self.spec.guard, notes=self.spec.notes)

    def prepare(self, ctx: ProbeContext):
        return measure.prepare_op(self.spec, self.opt_level,
                                  cache=ctx.compile_cache, env=ctx.env)

    def run_prepared(self, ctx: ProbeContext, prepared) -> LatencyRecord:
        if prepared is None:
            return self.run(ctx)
        m = measure.run_prepared_op(prepared, ctx.timer)
        return self._record(ctx, m, guard=self.spec.guard, notes=self.spec.notes)


class ClockOverheadProbe(Probe):
    """Cost of the timed region itself at one opt level (paper Fig. 5)."""

    category = "overhead"

    def __init__(self, opt_level: str = "O3"):
        self.op = "clock_overhead"
        self.opt_level = opt_level

    def run(self, ctx: ProbeContext) -> LatencyRecord:
        return self.run_prepared(ctx, self.prepare(ctx))

    def prepare(self, ctx: ProbeContext):
        import jax
        import jax.numpy as jnp

        from repro.core.optlevels import compile_at_level

        x = jnp.asarray(1.0, jnp.float32)
        if self.opt_level != "O0" and ctx.compile_cache is not None:
            from repro.core.compile_cache import fidelity_key

            key = fidelity_key(ctx.env, self.op, self.opt_level,
                               self.dtype, "null")
            fn, _, _ = ctx.compile_cache.load_or_compile(
                key, lambda: measure._aot_compile(lambda v: v,
                                                  self.opt_level, x))
        else:
            fn = compile_at_level(lambda v: v, self.opt_level, x)
        return (fn, x)

    def run_prepared(self, ctx: ProbeContext, prepared) -> LatencyRecord:
        if prepared is None:
            return self.run(ctx)
        fn, x = prepared
        m = ctx.timer.time_callable(fn, x, reps=measure._REPS[self.opt_level])
        return self._record(ctx, m, notes="null timed region (Fig. 5 analog)")


class MemoryProbe(Probe):
    """Dependent pointer chase at one working-set size (paper Fig. 6 point).

    Non-default chase parameters are part of the op name (and therefore the
    cache key): a low-fidelity short-chase point must never satisfy a cache
    lookup for the standard-fidelity sweep.
    """

    category = "memory"
    dtype = "int32"
    DEFAULT_STEPS = (2048, 6144)
    DEFAULT_LINE_BYTES = 64

    def __init__(self, working_set_bytes: int,
                 line_bytes: int = DEFAULT_LINE_BYTES,
                 steps: tuple[int, int] = DEFAULT_STEPS):
        self.working_set_bytes = int(working_set_bytes)
        self.line_bytes = line_bytes
        self.steps = tuple(steps)
        self.base_op = f"mem.chase.ws{self.working_set_bytes}"
        self.op = self.base_op
        if self.steps != self.DEFAULT_STEPS:
            self.op += f".s{self.steps[0]}-{self.steps[1]}"
        if self.line_bytes != self.DEFAULT_LINE_BYTES:
            self.op += f".line{self.line_bytes}"

    def match_names(self) -> frozenset[str]:
        # "mem" is the whole-family base row: ``--ops mem`` keeps every
        # memory-hierarchy rung, host-level and in-kernel alike
        return frozenset((self.op, self.base_op, "mem"))

    def run(self, ctx: ProbeContext) -> LatencyRecord:
        return self.run_prepared(ctx, self.prepare(ctx))

    def prepare(self, ctx: ProbeContext):
        return membench.prepare_chase(self.working_set_bytes,
                                      line_bytes=self.line_bytes,
                                      steps=self.steps,
                                      cache=ctx.compile_cache, env=ctx.env)

    def run_prepared(self, ctx: ProbeContext, prepared) -> LatencyRecord:
        if prepared is None:
            return self.run(ctx)
        pt = membench.run_prepared_chase(prepared, ctx.timer)
        m = Measurement(median_ns=pt.latency_ns, mad_ns=0.0,
                        min_ns=pt.latency_ns, n=ctx.timer.reps)
        return self._record(
            ctx, m, notes=f"cold_ns={pt.cold_latency_ns:.3f} "
                          f"stride={pt.stride_bytes}")


class StreamProbe(Probe):
    """Streaming bandwidth (``mem.stream``): ns per 64-byte line moved by a
    one-pass elementwise program, the slope between two sizes.

    A chase rung is the latency of one dependent load; the traffic of a
    compiled model streams, many loads in flight. This row is what
    :class:`~repro.core.perfmodel.HloLatencyEstimator` prices a module's
    bytes with.
    """

    category = "memory"

    def __init__(self):
        from repro.core.perfmodel import STREAM_OP

        self.op = STREAM_OP

    def match_names(self) -> frozenset[str]:
        return frozenset((self.op, "mem"))

    def run(self, ctx: ProbeContext) -> LatencyRecord:
        return self.run_prepared(ctx, self.prepare(ctx))

    def prepare(self, ctx: ProbeContext):
        return membench.prepare_stream()

    def run_prepared(self, ctx: ProbeContext, prepared) -> LatencyRecord:
        if prepared is None:
            return self.run(ctx)
        m = membench.run_prepared_stream(prepared, ctx.timer)
        n1, n2 = prepared.lens
        return self._record(
            ctx, m, notes=f"line={membench.STREAM_LINE_BYTES} lens={n1}-{n2}")


class KernelProbe(Probe):
    """In-kernel (Pallas) dependent ALU chain, slope-timed.

    The device-side analog of the paper's timed PTX block: the whole kernel is
    the timed region and the two-length slope cancels DMA/launch overhead.
    Runs in interpret mode on CPU; lowers to a real kernel on TPU.
    """

    category = "kernel"
    DEFAULT_LENS = (8, 64)
    DEFAULT_SHAPE = (8, 128)

    def __init__(self, kernel_op: str = "fma",
                 lens: tuple[int, int] = DEFAULT_LENS,
                 shape: tuple[int, int] = DEFAULT_SHAPE, reps: int = 5):
        self.kernel_op = kernel_op
        self.lens = tuple(lens)
        self.shape = tuple(shape)
        self.reps = reps
        # non-default chain lengths / tile are a different experiment: make
        # them part of the cache identity, like MemoryProbe.steps
        self.base_op = f"kernel.alu_chain.{kernel_op}"
        self.op = self.base_op
        if self.lens != self.DEFAULT_LENS:
            self.op += f".l{self.lens[0]}-{self.lens[1]}"
        if self.shape != self.DEFAULT_SHAPE:
            self.op += f".t{self.shape[0]}x{self.shape[1]}"

    def match_names(self) -> frozenset[str]:
        return frozenset((self.op, self.base_op, self.kernel_op))

    def run(self, ctx: ProbeContext) -> LatencyRecord:
        return self.run_prepared(ctx, self.prepare(ctx))

    def prepare(self, ctx: ProbeContext):
        import jax.numpy as jnp

        from repro.inkernel.measure import _cached_aot
        from repro.kernels.ops import alu_chain

        x = jnp.full(self.shape, 1.0, jnp.float32)
        a = jnp.full(self.shape, 0.5, jnp.float32)
        fns = {}

        def fn_by_len(n: int):
            if n not in fns:
                raw = lambda x, a, n=n: alu_chain(x, a, n=n,  # noqa: E731
                                                  op=self.kernel_op)
                fns[n] = _cached_aot(raw, (x, a), self.base_op,
                                     f"chain{n}.{self.kernel_op}."
                                     f"t{self.shape[0]}x{self.shape[1]}",
                                     ctx.compile_cache, ctx.env,
                                     dtype="float32")
            return fns[n]

        fn_by_len(self.lens[0])
        fn_by_len(self.lens[1])
        return (fn_by_len, x, a)

    def run_prepared(self, ctx: ProbeContext, prepared) -> LatencyRecord:
        if prepared is None:
            return self.run(ctx)
        fn_by_len, x, a = prepared
        m = ctx.timer.slope(fn_by_len, *self.lens, x, a, reps=self.reps)
        return self._record(
            ctx, m, notes=f"pallas alu_chain tile={self.shape} lens={self.lens}")


class KernelChainProbe(Probe):
    """One registry :class:`OpSpec` as an in-kernel Pallas chain (the paper's
    in-pipeline measurement, ``repro.inkernel``).

    Shares the record schema and category with the spec's dispatch-level
    :class:`InstructionProbe`, but under the op name ``inkernel.<name>`` —
    both rows coexist in one LatencyDB, which is what
    ``LatencyDB.compare_markdown`` pairs up. ``opt_level`` is pinned to
    ``"O3"``: a Pallas kernel is always fully compiled, there is no eager
    analog. Non-default chain lengths / tiles are a different fidelity and
    therefore part of the cache identity, like ``MemoryProbe.steps``
    (``lens=None`` means the library default, ``inkernel.INKERNEL_LENS`` for
    this backend — the single source of truth for what "unsuffixed
    fidelity" means).

    Guard netting stays in-method: the ``guard x add`` subtraction uses an
    *in-kernel* add baseline (measured once per session timer and chain
    lengths), never the dispatch-level baseline — mixing the two
    methodologies would clamp cheap guarded ops to a net of 0 on hardware
    where in-kernel latencies are far below dispatch ones.
    """

    # per-(timer, lens) in-kernel add-pair baseline; WeakKey so session
    # timers don't leak
    _baselines: "weakref.WeakKeyDictionary" = None  # set below the class

    def __init__(self, spec: OpSpec, lens: tuple[int, int] | None = None,
                 shape: tuple[int, int] | None = None, reps: int = 5):
        from repro import inkernel

        if not inkernel.supported(spec):
            raise ValueError(f"spec {spec.name!r} cannot lower in-kernel")
        self.spec = spec
        self.lens = tuple(lens or inkernel.INKERNEL_LENS.here())
        self.shape = tuple(shape) if shape is not None else None
        self.reps = reps
        self.opt_level = "O3"
        self.dtype = spec.dtype
        self.category = spec.category
        self.base_op = f"inkernel.{spec.name}"
        self.op = self.base_op
        if self.lens != inkernel.INKERNEL_LENS.here():
            self.op += f".l{self.lens[0]}-{self.lens[1]}"
        if self.shape is not None:
            self.op += f".t{self.shape[0]}x{self.shape[1]}"

    def match_names(self) -> frozenset[str]:
        # addressable by the full derived name, the unsuffixed in-kernel name,
        # and the dispatch-side base row (``--ops add`` keeps ``inkernel.add``)
        return frozenset((self.op, self.base_op, self.spec.name))

    def _inkernel_baseline_ns(self, ctx: ProbeContext) -> float:
        """In-kernel 1-cycle-class baseline: the ``add`` spec's (add ^ xor)
        pair measured in-kernel at the same lens, / (1 + its guard)."""
        from repro import inkernel
        from repro.core import chains

        per_timer = KernelChainProbe._baselines.setdefault(ctx.timer, {})
        if self.lens not in per_timer:
            base = next(o for o in chains.default_registry() if o.name == "add")
            with tracing.span("repro.session.setup"):
                m = inkernel.measure_inkernel_full(base, lens=self.lens,
                                                   timer=ctx.timer,
                                                   reps=self.reps)
            per_timer[self.lens] = max(m.median_ns, 0.0) / (1 + base.guard)
        return per_timer[self.lens]

    def run(self, ctx: ProbeContext) -> LatencyRecord:
        from repro import inkernel

        m = inkernel.measure_inkernel_full(self.spec, lens=self.lens,
                                           shape=self.shape, timer=ctx.timer,
                                           reps=self.reps)
        return self._finish(ctx, m)

    def prepare(self, ctx: ProbeContext):
        from repro import inkernel

        return inkernel.prepare_inkernel(self.spec, lens=self.lens,
                                         shape=self.shape, reps=self.reps,
                                         cache=ctx.compile_cache, env=ctx.env)

    def run_prepared(self, ctx: ProbeContext, prepared) -> LatencyRecord:
        from repro import inkernel

        if prepared is None:
            return self.run(ctx)
        m = inkernel.run_prepared_inkernel(prepared, ctx.timer)
        return self._finish(ctx, m)

    def _finish(self, ctx: ProbeContext, m: Measurement) -> LatencyRecord:
        from repro import inkernel

        baseline = self._inkernel_baseline_ns(ctx) if self.spec.guard else None
        return self._record(
            ctx, m, guard=self.spec.guard, baseline=baseline,
            notes=f"pallas fori_loop chain lens={self.lens} "
                  f"tile={self.shape or inkernel.default_tile(self.spec.dtype)}")


KernelChainProbe._baselines = weakref.WeakKeyDictionary()


class FusedKernelProbe(Probe):
    """One in-repo fused Pallas kernel as a two-size workload slope
    (``inkernel.fused.<name>`` rows; plan name ``fused``).

    The same netting algebra as :class:`KernelChainProbe`, with the chain
    length replaced by a workload-unit count (KV blocks for the attention
    kernels, sequence chunks for the SSM scan, row blocks for rmsnorm): two
    sizes share the launch path and block shapes, so the slope is the pure
    per-unit kernel cost. The builder (``repro.inkernel.fused.build_fused``)
    is shared with the dataflow auditor, whose signature-linearity
    certificate guarantees the slope's denominator; the certified per-unit
    HBM byte count rides in the record notes (``unit_bytes=``) so
    ``HloLatencyEstimator`` can scale the row to a zoo model's custom-call
    of a different shape.
    """

    def __init__(self, name: str, lens: tuple[int, int] | None = None,
                 reps: int = 5):
        from repro import inkernel

        if name not in inkernel.FUSED_KERNELS:
            raise ValueError(f"unknown fused kernel {name!r}; known: "
                             f"{', '.join(inkernel.FUSED_KERNELS)}")
        self.name = name
        self.lens = tuple(lens or inkernel.FUSED_LENS.here())
        self.reps = reps
        self.opt_level = "O3"
        self.dtype = "float32"
        self.category = "kernel"
        self.base_op = f"inkernel.fused.{name}"
        self.op = self.base_op
        if self.lens != inkernel.FUSED_LENS.here():
            self.op += f".l{self.lens[0]}-{self.lens[1]}"

    def match_names(self) -> frozenset[str]:
        return frozenset((self.op, self.base_op, self.name))

    def run(self, ctx: ProbeContext) -> LatencyRecord:
        from repro import inkernel

        m = inkernel.measure_fused_full(self.name, lens=self.lens,
                                        timer=ctx.timer, reps=self.reps)
        return self._finish(ctx, m)

    def prepare(self, ctx: ProbeContext):
        from repro import inkernel

        return inkernel.prepare_fused(self.name, lens=self.lens,
                                      reps=self.reps,
                                      cache=ctx.compile_cache, env=ctx.env)

    def run_prepared(self, ctx: ProbeContext, prepared) -> LatencyRecord:
        from repro import inkernel

        if prepared is None:
            return self.run(ctx)
        m = inkernel.run_prepared_fused(prepared, ctx.timer)
        return self._finish(ctx, m)

    def _finish(self, ctx: ProbeContext, m: Measurement) -> LatencyRecord:
        notes = f"pallas fused kernel lens={self.lens[0]}-{self.lens[1]}"
        try:
            from repro.audit.dataflow import fused_unit

            unit = fused_unit(self.name, self.lens)
            notes += (f" unit_bytes={unit['bytes']} "
                      f"unit_ops={sum(unit['ops'].values())}")
        except Exception:
            # the certificate is attached by the audit pass; a failure to
            # derive it here must not lose the measurement
            pass
        return self._record(ctx, m, notes=notes)


class MemoryChaseProbe(Probe):
    """In-kernel pointer chase at one working-set size: the memory-hierarchy
    rows of the in-pipeline method (paper Table IV / Fig. 6 analogs).

    The dependent chase runs *inside* a Pallas kernel
    (``repro.kernels.chase``) under the same two-length ``Timer.slope``
    extraction as :class:`KernelChainProbe`; the ring's residency is selected
    by footprint — BlockSpec-pinned in VMEM below the budget (Table IV, the
    shared-memory analog), ``memory_space=ANY`` streaming from HBM above
    (Fig. 6, the global-memory analog) — and the residency actually used is
    persisted in the record notes (``space=vmem|any``) together with the
    working-set / line metadata (:func:`membench.chasepoint_from_record`).

    Op name ``inkernel.mem.<bytes>``; ``opt_level`` pinned to ``"O3"`` like
    every Pallas probe (a kernel is always fully compiled). Non-default step
    counts, a non-default line padding or a *forced* memory space are a
    different experiment and become fidelity suffixes in the cache identity,
    like ``MemoryProbe.steps``.
    """

    category = "memory"
    dtype = "int32"
    DEFAULT_LINE_BYTES = 64

    def __init__(self, working_set_bytes: int,
                 line_bytes: int = DEFAULT_LINE_BYTES,
                 lens: tuple[int, int] | None = None,
                 memory_space: str | None = None, reps: int = 5):
        from repro import inkernel

        self.working_set_bytes = int(working_set_bytes)
        self.line_bytes = line_bytes
        self.lens = tuple(lens or inkernel.CHASE_LENS.here())
        self.memory_space = memory_space  # None = select by footprint
        self.reps = reps
        self.opt_level = "O3"
        self.base_op = f"inkernel.mem.{self.working_set_bytes}"
        self.host_op = f"mem.chase.ws{self.working_set_bytes}"
        self.op = self.base_op
        if self.lens != inkernel.CHASE_LENS.here():
            self.op += f".l{self.lens[0]}-{self.lens[1]}"
        if self.line_bytes != self.DEFAULT_LINE_BYTES:
            self.op += f".line{self.line_bytes}"
        if memory_space is not None:
            self.op += f".{memory_space}"

    def match_names(self) -> frozenset[str]:
        # addressable by the full derived name, the unsuffixed in-kernel row,
        # the host-level twin (``--ops mem.chase.ws8192`` keeps both sides of
        # the pairing) and the whole-family base row ``mem``
        return frozenset((self.op, self.base_op, self.host_op, "mem"))

    def run(self, ctx: ProbeContext) -> LatencyRecord:
        from repro import inkernel

        m, space = inkernel.measure_chase_full(
            self.working_set_bytes, line_bytes=self.line_bytes,
            lens=self.lens, timer=ctx.timer, memory_space=self.memory_space,
            reps=self.reps)
        return self._finish(ctx, m, space)

    def prepare(self, ctx: ProbeContext):
        from repro import inkernel

        return inkernel.prepare_chase(
            self.working_set_bytes, line_bytes=self.line_bytes,
            lens=self.lens, memory_space=self.memory_space, reps=self.reps,
            cache=ctx.compile_cache, env=ctx.env)

    def run_prepared(self, ctx: ProbeContext, prepared) -> LatencyRecord:
        from repro import inkernel

        if prepared is None:
            return self.run(ctx)
        m, space = inkernel.run_prepared_chase(prepared, ctx.timer)
        return self._finish(ctx, m, space)

    def _finish(self, ctx: ProbeContext, m: Measurement,
                space: str) -> LatencyRecord:
        return self._record(
            ctx, m, notes=f"pallas chase ws={self.working_set_bytes} "
                          f"line={self.line_bytes} space={space} "
                          f"lens={self.lens[0]}-{self.lens[1]}")


class CollectiveProbe(Probe):
    """One collective-ladder rung: ``n`` dependent collective ops chained
    inside ``shard_map``, slope-timed (``repro.parallel.ladders``).

    The paper's dependent-chain method pointed at the interconnect: two chain
    lengths share the dispatch, shard_map wrapping and first-transfer warm-up,
    so ``Timer.slope`` isolates the pure per-collective cost. One probe per
    ``(kind, device count, payload)``; op name
    ``coll.<kind>.d<devices>.<bytes>`` with the payload being the *nominal*
    per-device rung (the actual local bytes after divisibility rounding, and
    the ring-convention wire bytes per step, ride in the record notes —
    ``HloLatencyEstimator.collective_ladder`` prices from those).

    ``opt_level`` is pinned to ``"O3"``: a shard_map chain is always fully
    compiled. Non-default chain lengths are a different fidelity and suffix
    the cache identity, like ``MemoryProbe.steps``. Off-TPU the mesh is built
    from simulated XLA host devices
    (``--xla_force_host_platform_device_count``); a backend with fewer
    devices than the row names fails structurally instead of silently
    measuring a smaller group.
    """

    category = "collective"
    dtype = "float32"
    DEFAULT_LENS = (2, 6)

    def __init__(self, kind: str, payload_bytes: int,
                 devices: int | None = None,
                 lens: tuple[int, int] | None = None, reps: int = 5):
        from repro.parallel import ladders

        if kind not in ladders.LADDER_KINDS:
            raise ValueError(f"unknown collective kind {kind!r}; known: "
                             f"{', '.join(ladders.LADDER_KINDS)}")
        if payload_bytes <= 0:
            raise ValueError(f"payload_bytes must be positive, "
                             f"got {payload_bytes}")
        if devices is None:
            import jax

            devices = jax.device_count()
        self.kind = kind
        self.payload_bytes = int(payload_bytes)
        self.devices = int(devices)
        self.lens = tuple(lens) if lens is not None else self.DEFAULT_LENS
        self.reps = reps
        self.opt_level = "O3"
        self.base_op = f"coll.{kind}.d{self.devices}.{self.payload_bytes}"
        self.op = self.base_op
        if self.lens != self.DEFAULT_LENS:
            self.op += f".l{self.lens[0]}-{self.lens[1]}"

    def match_names(self) -> frozenset[str]:
        # addressable by the full rung name, the unsuffixed rung, the kind
        # family (``--ops coll.psum``) and the whole-family row ``coll``
        return frozenset((self.op, self.base_op,
                          f"coll.{self.kind}", "coll"))

    def run(self, ctx: ProbeContext) -> LatencyRecord:
        return self.run_prepared(ctx, self.prepare(ctx))

    def prepare(self, ctx: ProbeContext):
        from repro.parallel import ladders

        return ladders.prepare_collective(
            self.kind, self.payload_bytes, self.devices, self.lens,
            op=self.op, cache=ctx.compile_cache, env=ctx.env)

    def run_prepared(self, ctx: ProbeContext, prepared) -> LatencyRecord:
        from repro.parallel import ladders

        if prepared is None:
            return self.run(ctx)
        fn_by_len, x, local_bytes = prepared
        m = ctx.timer.slope(fn_by_len, *self.lens, x, reps=self.reps)
        wire = ladders.step_wire_bytes(self.kind, local_bytes, self.devices)
        return self._record(
            ctx, m,
            notes=f"kind={self.kind} devices={self.devices} "
                  f"payload_bytes={local_bytes} wire_bytes={wire:.0f} "
                  f"lens={self.lens[0]}-{self.lens[1]}")


def serving_tiny_config():
    """The default model the serving cells characterize: small enough for CI
    wall clocks, deep enough (2 scanned periods) that the decode-step HLO
    carries a real ``known_trip_count`` for the estimator's rollup."""
    from repro.models.config import ModelConfig, Runtime

    cfg = ModelConfig(name="serving-tiny", family="dense", n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                      vocab_size=128, param_dtype="float32",
                      compute_dtype="float32")
    rt = Runtime(remat=False, xent_chunk=16, moe_groups=1)
    return cfg, rt


class ServingCostProbe(Probe):
    """Price + measure one serving cell: the Engine's prefill or decode-step
    HLO at ``(batch, prompt_len)`` — where the measurement side of the repo
    (LatencyDB) meets the model side (perfmodel), the paper's stated purpose.

    The probe lowers :meth:`repro.serving.Engine.lower_prefill` /
    :meth:`~repro.serving.Engine.lower_decode` at the cell, prices the
    optimized HLO with :class:`~repro.core.perfmodel.HloLatencyEstimator`
    against the session's DB (environment-filtered: rows from other
    devices/jax versions never price this cell), then times the compiled
    executable. The record's ``latency_ns`` is the **measured** wall clock;
    the prediction and its :class:`~repro.core.perfmodel.PricedReport`
    digest (coverage, compute/memory split) ride in the notes and are parsed
    back by :func:`~repro.core.perfmodel.servingpoint_from_record`.

    Op names ``serving.prefill.b<B>p<L>`` / ``serving.decode.b<B>p<L>``;
    ``opt_level`` pinned to ``"O3"`` (a lowered executable is always fully
    compiled). A non-default model config is a different experiment and
    suffixes the cache identity with its name, like ``MemoryProbe.steps``.
    """

    category = "serving"

    def __init__(self, phase: str, batch: int, prompt_len: int,
                 cfg=None, rt=None, max_len: int | None = None, reps: int = 5):
        if phase not in ("prefill", "decode"):
            raise ValueError(f"phase must be prefill|decode, got {phase!r}")
        default_cfg, default_rt = serving_tiny_config()
        self.phase = phase
        self.batch = int(batch)
        self.prompt_len = int(prompt_len)
        self.cfg = cfg if cfg is not None else default_cfg
        self.rt = rt if rt is not None else default_rt
        self.max_len = max_len
        self.reps = reps
        self.opt_level = "O3"
        self.dtype = self.cfg.compute_dtype
        self.base_op = f"serving.{phase}.b{self.batch}p{self.prompt_len}"
        self.op = self.base_op
        if max_len is not None:
            # a non-default decode cache size is a different experiment
            # (different HLO), so it suffixes the cache identity like
            # MemoryProbe.steps
            self.op += f".c{int(max_len)}"
        if self.cfg.name != default_cfg.name:
            self.op += f".{self.cfg.name}"

    def match_names(self) -> frozenset[str]:
        # addressable by the full cell name, the phase family
        # (``--ops serving.decode``) and the whole-family row ``serving``
        return frozenset((self.op, self.base_op,
                          f"serving.{self.phase}", "serving"))

    def run(self, ctx: ProbeContext) -> LatencyRecord:
        return self.run_prepared(ctx, self.prepare(ctx))

    def prepare(self, ctx: ProbeContext):
        """Init params, lower the cell and compile it (via the compile cache).

        The lowering itself always runs (it is what produces the call args);
        only the XLA backend compile — the expensive part — is skipped on a
        cache hit. The optimized HLO text rides in the cache entry's
        ``extra`` payload because a deserialized executable cannot be asked
        for ``as_text()`` on every backend.
        """
        import jax

        from repro.models import transformer
        from repro.serving.engine import Engine

        params = transformer.init_lm(jax.random.PRNGKey(0), self.cfg)
        eng = Engine(params, self.cfg, self.rt)
        if self.phase == "prefill":
            lowered, args = eng.lower_prefill(self.batch, self.prompt_len)
            cache_len = 0                     # prefill builds, never scans, KV
        else:
            cache_len = self.max_len if self.max_len is not None else eng.max_len
            lowered, args = eng.lower_decode(self.batch, self.prompt_len,
                                             cache_len)
        if ctx.compile_cache is not None:
            from repro.core.compile_cache import fidelity_key

            key = fidelity_key(ctx.env, self.op, self.opt_level, self.dtype,
                               f"cache{cache_len}")
            compiled, hlo, _ = ctx.compile_cache.load_or_compile(
                key, lowered.compile, extra=lambda c: c.as_text())
        else:
            compiled = lowered.compile()
            hlo = None
        if hlo is None:
            try:
                hlo = compiled.as_text()
            except Exception:  # noqa: BLE001 - deserialized executable
                hlo = ""
        return (compiled, args, hlo, cache_len)

    def run_prepared(self, ctx: ProbeContext, prepared) -> LatencyRecord:
        import jax

        from repro.core.perfmodel import HloLatencyEstimator

        if prepared is None:
            return self.run(ctx)
        compiled, args, hlo, cache_len = prepared
        if ctx.db is not None and getattr(ctx.db, "path", None):
            # sharded runs (Session.fan_out) give each device its own DB
            # copy; sibling shards flush their dep rows to the shared path
            # after every probe, so pick those up before pricing instead of
            # falling back to default_ns for rows another shard measured
            from repro.core.latency_db import LatencyDB

            if os.path.exists(ctx.db.path):
                ctx.db.merge(LatencyDB(ctx.db.path))
        est = HloLatencyEstimator(ctx.db, opt_level=self.opt_level,
                                  filters=dict(ctx.env))
        report = est.estimate(hlo)
        m = ctx.timer.time_callable(compiled, *args, reps=self.reps)
        # cache= records the KV length this cell actually priced: a decode
        # row is meaningless without it (the scan length dominates), and
        # lower_decode's default changed once already (prompt+32 -> max_len)
        notes = (f"phase={self.phase} batch={self.batch} "
                 f"prompt={self.prompt_len} cache={cache_len} "
                 f"model={self.cfg.name} "
                 f"predicted_ns={report.total_ns:.3f} "
                 f"compute_ns={report.compute_ns:.3f} "
                 f"memory_ns={report.memory_ns:.3f} "
                 f"coverage={report.coverage:.4f} "
                 f"bound={report.bound}")
        return self._record(ctx, m, notes=notes)


class ShardedServingCostProbe(Probe):
    """Price + measure one *tensor-parallel* serving cell: the Engine's
    prefill or decode-step HLO lowered under a ``(1, tp)`` mesh
    (``launch/mesh.make_mesh_for``), params sharded over the ``model`` axis.

    The sharded lowering makes GSPMD insert real collectives; the estimator
    prices the per-shard compute/memory from the existing measured rows
    *plus* the new collective term from the measured ladder rungs
    (``coll.<kind>.d<N>.<bytes>``), and the compiled SPMD executable is
    wall-clock timed on the same simulated mesh — predicted-vs-measured for
    distributed serving in one record. Collective pricing is explicit: the
    notes carry the collective-ns split, the number of priced collective
    instances and the count left unpriced (``coll_unpriced=0`` is the CI
    acceptance gate — zero default-priced collectives).

    Op names ``serving.tp<N>.<phase>.b<B>p<L>`` — rendered by the same
    ``compare_markdown(prefix="serving.")`` table and parsed by the same
    :func:`~repro.core.perfmodel.servingpoint_from_record` (phase rides in
    the notes) as the single-device cells.
    """

    category = "serving"

    def __init__(self, phase: str, batch: int, prompt_len: int, tp: int = 2,
                 cfg=None, rt=None, max_len: int | None = None, reps: int = 5):
        if phase not in ("prefill", "decode"):
            raise ValueError(f"phase must be prefill|decode, got {phase!r}")
        if int(tp) < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        default_cfg, default_rt = serving_tiny_config()
        self.phase = phase
        self.batch = int(batch)
        self.prompt_len = int(prompt_len)
        self.tp = int(tp)
        self.cfg = cfg if cfg is not None else default_cfg
        self.rt = rt if rt is not None else default_rt
        self.max_len = max_len
        self.reps = reps
        self.opt_level = "O3"
        self.dtype = self.cfg.compute_dtype
        self.base_op = (f"serving.tp{self.tp}.{phase}"
                        f".b{self.batch}p{self.prompt_len}")
        self.op = self.base_op
        if max_len is not None:
            self.op += f".c{int(max_len)}"
        if self.cfg.name != default_cfg.name:
            self.op += f".{self.cfg.name}"

    def match_names(self) -> frozenset[str]:
        return frozenset((self.op, self.base_op, f"serving.tp{self.tp}",
                          f"serving.{self.phase}", "serving"))

    def run(self, ctx: ProbeContext) -> LatencyRecord:
        return self.run_prepared(ctx, self.prepare(ctx))

    def prepare(self, ctx: ProbeContext):
        """Shard params over the TP mesh, lower the cell, compile (cached).

        Params are ``device_put`` onto their resolved ``NamedSharding``\\ s
        before lowering, so jit infers sharded in_shardings and GSPMD
        partitions the module (``num_partitions=tp``, collectives in the
        optimized HLO). The lowering runs inside
        :func:`repro.parallel.sharding.use_sharding` so the model's
        activation ``annotate`` constraints resolve against the same mesh.
        """
        import jax

        from repro.launch.mesh import make_mesh_for
        from repro.models import transformer
        from repro.parallel import sharding as shd
        from repro.serving.engine import Engine

        if self.tp > jax.device_count():
            raise RuntimeError(
                f"{self.op} needs {self.tp} devices, backend has "
                f"{jax.device_count()} (set XLA_FLAGS=--xla_force_host_"
                f"platform_device_count={self.tp})")
        mesh = make_mesh_for(self.tp, model_parallel=self.tp)
        rules = shd.lm_rules(fsdp=False)
        params = transformer.init_lm(jax.random.PRNGKey(0), self.cfg)
        params = jax.device_put(params,
                                shd.param_shardings(params, mesh, rules))
        with shd.use_sharding(mesh, rules):
            eng = Engine(params, self.cfg, self.rt)
            if self.phase == "prefill":
                lowered, args = eng.lower_prefill(self.batch, self.prompt_len)
                cache_len = 0
            else:
                cache_len = (self.max_len if self.max_len is not None
                             else eng.max_len)
                lowered, args = eng.lower_decode(self.batch, self.prompt_len,
                                                 cache_len)
            if ctx.compile_cache is not None:
                from repro.core.compile_cache import fidelity_key

                key = fidelity_key(ctx.env, self.op, self.opt_level,
                                   self.dtype, f"cache{cache_len}")
                compiled, hlo, _ = ctx.compile_cache.load_or_compile(
                    key, lowered.compile, extra=lambda c: c.as_text())
            else:
                compiled = lowered.compile()
                hlo = None
        if hlo is None:
            try:
                hlo = compiled.as_text()
            except Exception:  # noqa: BLE001 - deserialized executable
                hlo = ""
        return (compiled, args, hlo, cache_len)

    def run_prepared(self, ctx: ProbeContext, prepared) -> LatencyRecord:
        from repro.core.perfmodel import ClassCost, HloLatencyEstimator

        if prepared is None:
            return self.run(ctx)
        compiled, args, hlo, cache_len = prepared
        if ctx.db is not None and getattr(ctx.db, "path", None):
            from repro.core.latency_db import LatencyDB

            if os.path.exists(ctx.db.path):
                ctx.db.merge(LatencyDB(ctx.db.path))
        est = HloLatencyEstimator(ctx.db, opt_level=self.opt_level,
                                  filters=dict(ctx.env))
        report = est.estimate(hlo)
        m = ctx.timer.time_callable(compiled, *args, reps=self.reps)
        coll = report.by_class.get("collective", ClassCost())
        coll_unpriced = sum(
            c for label, c in report.unpriced_opcodes
            if label.startswith("collective:"))
        notes = (f"phase={self.phase} batch={self.batch} "
                 f"prompt={self.prompt_len} cache={cache_len} "
                 f"tp={self.tp} model={self.cfg.name} "
                 f"predicted_ns={report.total_ns:.3f} "
                 f"compute_ns={report.compute_ns:.3f} "
                 f"memory_ns={report.memory_ns:.3f} "
                 f"collective_ns={report.collective_ns:.3f} "
                 f"coll_ops={coll.instances:g} "
                 f"coll_unpriced={coll_unpriced:g} "
                 f"coverage={report.coverage:.4f} "
                 f"bound={report.bound}")
        return self._record(ctx, m, notes=notes)


class SloProbe(Probe):
    """One serving-SLO point: a seeded arrival trace at one rate, replayed
    through *both* sides of ``repro.traffic`` — the LatencyDB-priced
    simulator (predicted) and the engine's continuous-batching slot pool
    (measured) — and aggregated into exact-rank TTFT/TPOT/e2e percentiles.

    The record's ``latency_ns`` is the **measured p50 TTFT** (the headline
    SLO number); every other percentile, both predicted and measured, plus
    goodput and the estimator's coverage, ride in the notes and are parsed
    back by :func:`~repro.core.perfmodel.slopoint_from_record`. Like
    :class:`ServingCostProbe` this is a consumer probe: it prices against
    ``ctx.db``, so schedule it *after* the instruction/memory rows
    (``Plan.slo`` does).

    Op name ``slo.r<rate>``; a non-default trace shape (request count, slot
    count, seed, arrival process) or model is a different experiment and
    suffixes the cache identity, like ``MemoryProbe.steps``.

    This probe intentionally has no ``prepare``/``run_prepared`` split: its
    wall clock is dominated by the slot-pool trace replay, not by XLA
    compiles, and it consumes rows sibling probes may still be flushing —
    the base-class fallback (``run_prepared(ctx, None) -> run``) schedules
    it correctly in pipelined sessions.
    """

    category = "slo"
    DEFAULT_N = 12
    DEFAULT_SLOTS = 4

    def __init__(self, rate_rps: float, n_requests: int = DEFAULT_N,
                 n_slots: int = DEFAULT_SLOTS, seed: int = 0,
                 cfg=None, rt=None, max_len: int | None = None,
                 process: str = "poisson", burstiness_cv: float = 1.0,
                 prompt_len: tuple[int, int] = (4, 8),
                 max_new: tuple[int, int] = (4, 8)):
        default_cfg, default_rt = serving_tiny_config()
        self.rate_rps = float(rate_rps)
        self.n_requests = int(n_requests)
        self.n_slots = int(n_slots)
        self.seed = int(seed)
        self.cfg = cfg if cfg is not None else default_cfg
        self.rt = rt if rt is not None else default_rt
        self.max_len = max_len
        self.process = process
        self.burstiness_cv = float(burstiness_cv)
        self.prompt_len = tuple(prompt_len)
        self.max_new = tuple(max_new)
        self.opt_level = "O3"
        self.dtype = self.cfg.compute_dtype
        self.base_op = f"slo.r{self.rate_rps:g}"
        self.op = self.base_op
        if (self.n_requests, self.n_slots) != (self.DEFAULT_N,
                                               self.DEFAULT_SLOTS):
            self.op += f".n{self.n_requests}s{self.n_slots}"
        if self.seed != 0:
            self.op += f".seed{self.seed}"
        if self.process != "poisson":
            self.op += f".{self.process}{self.burstiness_cv:g}"
        if max_len is not None:
            self.op += f".c{int(max_len)}"
        if self.cfg.name != default_cfg.name:
            self.op += f".{self.cfg.name}"

    def match_names(self) -> frozenset[str]:
        # addressable by the full point name, the rate family and the
        # whole-family row ``slo``
        return frozenset((self.op, self.base_op, "slo"))

    def trace_config(self):
        """The (deterministic) trace recipe this point replays."""
        from repro.traffic.traces import TraceConfig

        return TraceConfig(n_requests=self.n_requests, rate_rps=self.rate_rps,
                           seed=self.seed, process=self.process,
                           burstiness_cv=self.burstiness_cv,
                           prompt_len=self.prompt_len, max_new=self.max_new,
                           vocab_size=self.cfg.vocab_size)

    def run(self, ctx: ProbeContext) -> LatencyRecord:
        import jax

        from repro.models import transformer
        from repro.serving.engine import Engine
        from repro.traffic.simulate import run_slo_point
        from repro.traffic.traces import generate_trace

        params = transformer.init_lm(jax.random.PRNGKey(0), self.cfg)
        eng = Engine(params, self.cfg, self.rt)
        trace = generate_trace(self.trace_config())
        db = ctx.db
        if db is None:
            from repro.core.latency_db import LatencyDB

            db = LatencyDB()
        elif getattr(db, "path", None) and os.path.exists(db.path):
            # pick up sibling shards' dep rows, like ServingCostProbe
            from repro.core.latency_db import LatencyDB

            db.merge(LatencyDB(db.path))
        pred, meas, coverage = run_slo_point(
            eng, db, trace, n_slots=self.n_slots, max_len=self.max_len,
            opt_level=self.opt_level, filters=dict(ctx.env))
        m = Measurement(median_ns=meas.ttft_ns[50.0], mad_ns=0.0,
                        min_ns=meas.ttft_ns[50.0], n=self.n_requests)
        notes = (f"rate={self.rate_rps:g} n={self.n_requests} "
                 f"slots={self.n_slots} seed={self.seed} "
                 f"model={self.cfg.name} "
                 f"pred_ttft_p50_ns={pred.ttft_ns[50.0]:.1f} "
                 f"pred_ttft_p99_ns={pred.ttft_ns[99.0]:.1f} "
                 f"pred_tpot_p50_ns={pred.tpot_ns[50.0]:.1f} "
                 f"pred_tpot_p99_ns={pred.tpot_ns[99.0]:.1f} "
                 f"pred_e2e_p50_ns={pred.e2e_ns[50.0]:.1f} "
                 f"pred_goodput_tok_s={pred.goodput_tok_s:.3f} "
                 f"meas_ttft_p50_ns={meas.ttft_ns[50.0]:.1f} "
                 f"meas_ttft_p99_ns={meas.ttft_ns[99.0]:.1f} "
                 f"meas_tpot_p50_ns={meas.tpot_ns[50.0]:.1f} "
                 f"meas_tpot_p99_ns={meas.tpot_ns[99.0]:.1f} "
                 f"meas_e2e_p50_ns={meas.e2e_ns[50.0]:.1f} "
                 f"meas_goodput_tok_s={meas.goodput_tok_s:.3f} "
                 f"coverage={coverage:.4f}")
        return self._record(ctx, m, notes=notes)
