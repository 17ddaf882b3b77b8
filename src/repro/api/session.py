"""The session: single front door for all characterization runs.

A :class:`Session` owns the pieces every sweep needs exactly once — the
:class:`Timer`, the environment fingerprint, the calibrated clock, the
per-level guard baseline, and a :class:`LatencyDB`-backed result cache — and
executes :class:`Plan`\\ s **incrementally**:

* probes whose cache key already exists in the DB are skipped (``force=True``
  re-measures);
* after every measured/failed probe the new rows are appended to the DB's
  journal (:meth:`LatencyDB.flush` — a delta write, not a whole-file
  rewrite), so an interrupted sweep resumes for free: re-run the same plan
  and completed probes are cache hits; the run's final ``save`` compacts the
  journal into one atomic whole-file write;
* a probe that raises is recorded as a structured :class:`ProbeFailure` in
  the DB (and superseded when a later run of the same probe succeeds) instead
  of vanishing into a log line. ``KeyboardInterrupt`` is *not* swallowed —
  partial results are already on disk.

Runs are **pipelined** by default (``pipeline=False`` for strictly serial
execution): a single background compile thread runs probe N+1's
:meth:`Probe.prepare` (lowering, XLA compiles, compile-cache loads) while
probe N's :meth:`Probe.run_prepared` times on the main thread — timing stays
strictly serial on the device, only compilation overlaps it. With a
persistent :class:`~repro.core.compile_cache.CompileCache` attached
(``compile_cache=...``), re-runs skip XLA entirely; with
``adaptive=True``, quiet rows stop repeating once their MAD/median
converges and the saved reps are spent on noisy ones
(:class:`~repro.core.timing.AdaptiveFidelity`). See docs/performance.md.

A session may be **pinned to one device** (``Session(device=...)``): the
environment fingerprint, the timer, the guard baseline and every probe
execution then derive from that device instead of the process default.
:meth:`Session.fan_out` builds on this to shard a plan across all local
devices — one pinned session per device, probes sequential within each
(timing must not contend), per-shard DBs merged on completion.

Typical use::

    from repro.api import Plan, Session

    session = Session(db="/tmp/latency_db.json")
    result = session.run(Plan.instructions(opt_levels=("O0", "O3"))
                         + Plan.memory())
    print(result.summary())
    print(result.table_markdown())

    # multi-device: same records, wall-clock / n_devices
    result = session.fan_out(Plan.instructions())
"""
from __future__ import annotations

import concurrent.futures
import contextlib
import dataclasses
import time
from typing import Any

import jax

from repro import tracing
from repro.core import chains, measure
from repro.core.compile_cache import CompileCache
from repro.core.latency_db import (LatencyDB, LatencyRecord, ProbeFailure,
                                   current_environment)
from repro.core.timing import AdaptiveFidelity, Timer
from repro.utils import logger, timestamp

from repro.api.plan import Plan
from repro.api.probes import Probe, ProbeContext


def _prepare_probe(probe: Probe, ctx: ProbeContext) -> Any:
    """Probe's XLA-bound half. Probes are duck-typed: one that predates the
    prepare/run_prepared split (only implements ``run``) prepares nothing."""
    prep = getattr(probe, "prepare", None)
    return prep(ctx) if prep is not None else None


def _execute_probe(probe: Probe, ctx: ProbeContext, prepared: Any):
    run_prepared = getattr(probe, "run_prepared", None)
    if run_prepared is not None:
        return run_prepared(ctx, prepared)
    return probe.run(ctx)


@dataclasses.dataclass(frozen=True)
class ProbeResult:
    """Outcome of one scheduled probe."""

    probe: Probe
    status: str                        # "measured" | "cached" | "failed"
    record: LatencyRecord | None = None
    failure: ProbeFailure | None = None


@dataclasses.dataclass
class ResultSet:
    """Per-probe outcomes of one ``Session.run``, in plan order."""

    results: list[ProbeResult]
    db: LatencyDB
    # wall-clock attribution for this run: {"compile", "time", "flush"} in ns
    stage_ns: dict = dataclasses.field(default_factory=dict)
    # CompileCache hit/compile counters for THIS run (a delta, not the
    # cache's lifetime totals); None when no cache was configured
    cache_stats: Any = None

    @property
    def measured(self) -> list[ProbeResult]:
        return [r for r in self.results if r.status == "measured"]

    @property
    def cached(self) -> list[ProbeResult]:
        return [r for r in self.results if r.status == "cached"]

    @property
    def failed(self) -> list[ProbeResult]:
        return [r for r in self.results if r.status == "failed"]

    def records(self) -> list[LatencyRecord]:
        return [r.record for r in self.results if r.record is not None]

    def summary(self) -> str:
        s = (f"{len(self.measured)} measured, {len(self.cached)} cached, "
             f"{len(self.failed)} failed ({len(self.results)} probes)")
        if self.cache_stats is not None:
            st = self.cache_stats
            s += f", compile cache: {st.hits} hits, {st.misses} compiled"
        return s

    def table_markdown(self, opt_levels: tuple[str, ...] = ("O3", "O0")) -> str:
        return self.db.table_markdown(opt_levels=opt_levels)

    def __len__(self) -> int:
        return len(self.results)


class Session:
    """Cache-aware scheduler over a LatencyDB (see module docstring).

    Parameters
    ----------
    db: a :class:`LatencyDB`, a path to one (loaded if present, created on
        first flush), or None for an in-memory DB.
    timer: shared :class:`Timer`; defaults to the standard calibration.
    force: re-measure cache hits by default (per-run ``force`` overrides).
    device: pin the session to one jax device (a ``jax.Device`` or an index
        into ``jax.devices()``). The environment fingerprint, every probe
        execution, the timer and the guard baseline all derive from *this*
        device; ``None`` keeps the process default (single-device behavior).
    compile_cache: a :class:`CompileCache`, a directory path for one, or
        None (no executable persistence). Shared across fan-out shards.
    adaptive: True for default :class:`AdaptiveFidelity`, an instance for
        custom thresholds, or None/False to keep fixed rep counts.
    pipeline: overlap probe N+1's compile with probe N's timing (default).
        ``False`` restores strictly serial prepare-then-run execution; the
        measured values are identical either way (only compilation is
        overlapped, never timing).
    audit: statically verify each probe's compiled artifact as it is
        prepared (``repro.audit``: chain count, guard accounting, dependent
        path) and attach the verdict to the record's notes
        (``audit=ok`` / ``audit=transformed:<cause>`` / ...). Runs on the
        compile thread, never the timing thread. Off by default; a failed
        verdict only flags the record — ``python -m repro audit --strict``
        turns flags into a failing exit.
    """

    def __init__(self, db: LatencyDB | str | None = None,
                 timer: Timer | None = None, force: bool = False,
                 device=None, compile_cache: CompileCache | str | None = None,
                 adaptive: AdaptiveFidelity | bool | None = None,
                 pipeline: bool = True, audit: bool = False):
        if isinstance(device, int):
            device = jax.devices()[device]
        self.device = device
        self.db = db if isinstance(db, LatencyDB) else LatencyDB(path=db)
        self.timer = timer or Timer()
        if self.device is not None:
            if self.timer.device is None:
                self.timer.device = self.device
            elif self.timer.device != self.device:
                # a timer calibrated/pinned on another device would silently
                # override this session's pin inside time_callable
                raise ValueError(
                    f"timer is pinned to {self.timer.device}, session to "
                    f"{self.device}; give each pinned session its own timer")
        if isinstance(compile_cache, str):
            compile_cache = CompileCache(compile_cache)
        self.compile_cache = compile_cache
        if adaptive is True:
            adaptive = AdaptiveFidelity()
        elif adaptive is False:
            adaptive = None
        self.adaptive = adaptive
        if adaptive is not None:
            self.timer.adaptive = adaptive
        self.pipeline = pipeline
        self.audit = audit
        self.force = force
        self.env = current_environment(device)
        self._baseline: dict[tuple, float] = {}

    def _device_ctx(self):
        """Scope in which all of this session's jax work runs."""
        if self.device is None:
            return contextlib.nullcontext()
        return jax.default_device(self.device)

    def _device_token(self):
        """Hashable identity of the pinned device for in-session caches."""
        return None if self.device is None else (self.env["backend"],
                                                 self.device.id)

    # ------------------------------------------------------------- baseline
    def baseline_ns(self, opt_level: str, use_db: bool = True) -> float:
        """Per-level 1-cycle-class baseline used to net out guard ops.

        The ``add`` spec is an (add ^ xor) pair in the same latency class, so
        baseline = measured_pair / (1 + guard). Derived from the DB when the
        pair is already cached (and ``use_db``); measured (and cached
        in-session) otherwise. Forced runs pass ``use_db=False`` so a stale
        cached baseline is never mixed into fresh measurements. The cache is
        partitioned by the pinned device: fan-out shards must never share a
        baseline measured on a different device.
        """
        cache_key = (self._device_token(), opt_level, use_db)
        if cache_key not in self._baseline:
            base = next((o for o in chains.default_registry()
                         if o.name == "add"), None)
            if base is None:
                self._baseline[cache_key] = 0.0
            else:
                rec = self.db.get((self.env["device_kind"], self.env["backend"],
                                   self.env["jax_version"], opt_level,
                                   base.name, base.dtype)) if use_db else None
                if rec is not None:
                    ns = rec.latency_ns
                else:
                    with tracing.span("repro.session.setup"), \
                            self._device_ctx():
                        ns = measure.measure_op(base, opt_level, self.timer)
                self._baseline[cache_key] = ns / (1 + base.guard)
        return self._baseline[cache_key]

    def _context(self, force: bool = False) -> ProbeContext:
        return ProbeContext(timer=self.timer, env=self.env,
                            clock_hz=self.timer.calibrate_clock_hz(),
                            baseline_ns=lambda lv: self.baseline_ns(
                                lv, use_db=not force),
                            device=self.device, db=self.db,
                            compile_cache=self.compile_cache,
                            adaptive=self.adaptive is not None)

    # ------------------------------------------------------------ execution
    def run(self, plan: Plan, force: bool | None = None,
            pipeline: bool | None = None) -> ResultSet:
        """Execute a plan incrementally; returns per-probe outcomes.

        Timing runs strictly sequentially on the main thread (timing probes
        must not contend with each other). In pipelined mode a single
        background thread runs the *next* probe's ``prepare`` (compiles)
        while the current probe times. After every measured/failed probe the
        new rows are journal-appended to the DB path (cheap delta flush), so
        interrupting a sweep loses at most the in-flight probe; a completed
        run compacts the journal into the main DB file.
        """
        force = self.force if force is None else force
        pipeline = self.pipeline if pipeline is None else pipeline
        plan = plan.dedupe()
        probes = list(plan)
        with tracing.span("repro.session.run", probes=len(probes)):
            with tracing.span("repro.session.setup"):
                ctx = self._context(force=force)
            results: dict[int, ProbeResult] = {}
            pending: list[tuple[int, Probe]] = []
            for i, probe in enumerate(probes):
                key = probe.key(self.env)
                if not force and key in self.db:
                    results[i] = ProbeResult(probe, "cached",
                                             record=self.db.get(key))
                    logger.debug("cached   %-28s",
                                 probe.op + "@" + probe.opt_level)
                else:
                    pending.append((i, probe))
            stage_ns = {"compile": 0, "time": 0, "flush": 0}
            stats0 = (dataclasses.replace(self.compile_cache.stats)
                      if self.compile_cache is not None else None)
            if pending:
                if pipeline and len(pending) > 1:
                    self._run_pipelined(pending, ctx, results, stage_ns)
                else:
                    self._run_serial(pending, ctx, results, stage_ns)
            if self.db.path:
                t0 = time.perf_counter_ns()
                with tracing.span("repro.session.flush", start_ns=t0):
                    self.db.save()  # compact the journal into one atomic write
                stage_ns["flush"] += time.perf_counter_ns() - t0
        cache_stats = None
        if stats0 is not None:
            now = self.compile_cache.stats
            cache_stats = dataclasses.replace(
                now, hits=now.hits - stats0.hits,
                misses=now.misses - stats0.misses,
                stores=now.stores - stats0.stores,
                evictions=now.evictions - stats0.evictions,
                errors=now.errors - stats0.errors)
        return ResultSet(results=[results[i] for i in range(len(probes))],
                         db=self.db, stage_ns=stage_ns,
                         cache_stats=cache_stats)

    def _audit_for(self, probe: Probe):
        """Static integrity verdict for one probe's artifact (compile-side).

        Runs right after ``prepare`` so the compile cache's optimized-HLO
        sidecars are warm and the audit never re-invokes XLA for a cached
        chain. Any auditor error degrades to no verdict — auditing must
        never turn a measurable probe into a failure.
        """
        if not self.audit:
            return None
        try:
            from repro.audit import audit_target

            return audit_target(probe.op, probe.opt_level,
                                cache=self.compile_cache, env=self.env)
        except Exception as e:  # noqa: BLE001 - advisory only
            logger.warning("audit of %s@%s errored: %s", probe.op,
                           probe.opt_level, e)
            return None

    def _run_serial(self, pending, ctx, results, stage_ns) -> None:
        """prepare + run_prepared inline, one probe at a time."""
        for i, probe in pending:
            t0 = time.perf_counter_ns()
            prepared, exc, verdict = None, None, None
            try:
                with tracing.span("repro.session.prepare", start_ns=t0,
                                  op=probe.op), self._device_ctx():
                    prepared = _prepare_probe(probe, ctx)
                    verdict = self._audit_for(probe)
            except Exception as e:  # noqa: BLE001 - structured failure below
                exc = e
            stage_ns["compile"] += time.perf_counter_ns() - t0
            self._finish_probe(i, probe, ctx, prepared, exc, results, stage_ns,
                               verdict=verdict)

    def _run_pipelined(self, pending, ctx, results, stage_ns) -> None:
        """Compile-ahead: the worker prepares probe N+1 while N times.

        One worker thread, and ``prepare`` only compiles — all timing stays
        on the main thread, so probes never contend for the device while
        being measured. The ``jax.default_device`` scope is thread-local and
        therefore re-entered inside the worker task.
        """
        def _prepare(probe: Probe):
            t0 = time.perf_counter_ns()
            try:
                with tracing.span("repro.session.prepare", start_ns=t0,
                                  op=probe.op), self._device_ctx():
                    prepared = _prepare_probe(probe, ctx)
                    verdict = self._audit_for(probe)
                return prepared, None, verdict, time.perf_counter_ns() - t0
            except Exception as e:  # noqa: BLE001 - structured failure later
                return None, e, None, time.perf_counter_ns() - t0

        pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="repro-compile")
        try:
            fut = pool.submit(_prepare, pending[0][1])
            for j, (i, probe) in enumerate(pending):
                cur = fut
                if j + 1 < len(pending):
                    # enqueue the next compile BEFORE waiting on this one:
                    # the worker moves straight on to probe N+1 while the
                    # main thread times probe N below
                    fut = pool.submit(_prepare, pending[j + 1][1])
                with tracing.span("repro.session.compile_wait"):
                    prepared, exc, verdict, compile_ns = cur.result()
                stage_ns["compile"] += compile_ns
                self._finish_probe(i, probe, ctx, prepared, exc, results,
                                   stage_ns, verdict=verdict)
        finally:
            pool.shutdown(wait=False, cancel_futures=True)

    def _finish_probe(self, i, probe, ctx, prepared, exc, results,
                      stage_ns, verdict=None) -> None:
        """Time one prepared probe on the main thread and record the outcome."""
        if exc is None:
            t0 = time.perf_counter_ns()
            try:
                with tracing.span("repro.session.time", start_ns=t0,
                                  op=probe.op), self._device_ctx():
                    rec = _execute_probe(probe, ctx, prepared)
            except Exception as e:  # noqa: BLE001 - recorded as failure
                exc = e
            else:
                if verdict is not None:
                    note = verdict.note()
                    rec = dataclasses.replace(
                        rec, notes=f"{rec.notes} {note}".strip())
                    if verdict.failed:
                        logger.warning("audit: %s@%s %s (%s)", probe.op,
                                       probe.opt_level, note, verdict.detail)
                self.db.add(rec)
                results[i] = ProbeResult(probe, "measured", record=rec)
                logger.info("measured %-28s %8.1fns (±%.1f)",
                            f"{probe.op}@{probe.opt_level}", rec.latency_ns,
                            rec.mad_ns)
            stage_ns["time"] += time.perf_counter_ns() - t0
        if exc is not None:
            failure = ProbeFailure(
                op=probe.op, dtype=probe.dtype, opt_level=probe.opt_level,
                error_type=type(exc).__name__, message=str(exc),
                failed_at=timestamp(), **self.env)
            self.db.add_failure(failure)
            results[i] = ProbeResult(probe, "failed", failure=failure)
            logger.warning("probe %s@%s failed: %s: %s", probe.op,
                           probe.opt_level, type(exc).__name__, exc)
        t0 = time.perf_counter_ns()
        with tracing.span("repro.session.flush", start_ns=t0):
            self._flush()
        stage_ns["flush"] += time.perf_counter_ns() - t0

    def _flush(self) -> None:
        """Per-probe durability point: journal-append the new rows only."""
        if self.db.path:
            self.db.flush()

    # -------------------------------------------------------------- fan-out
    def fan_out(self, plan: Plan, devices=None, force: bool | None = None
                ) -> ResultSet:
        """Shard ``plan`` across devices; one pinned Session per device.

        The plan is dealt round-robin over ``devices`` (default: all of
        ``jax.local_devices()``) via :meth:`Plan.shard`; each shard runs in
        its own thread through a device-pinned Session. Probes stay
        sequential *within* a device — timing probes must not contend for
        the hardware they are measuring — so wall-clock scales with the
        device count while each measurement still sees an idle device.

        Every shard flushes to this session's DB path (safe: ``save`` is an
        atomic read-merge-write), and on completion the shard DBs are merged
        into ``self.db`` under :meth:`LatencyDB.merge` rules. Returns one
        :class:`ResultSet` with all shard outcomes in shard order.
        """
        devices = list(devices) if devices is not None else jax.local_devices()
        if not devices:
            raise ValueError("fan_out needs at least one device")
        force = self.force if force is None else force
        plan = plan.dedupe()
        shards = plan.shard(len(devices))
        # calibrate once, serially: the spin-loop calibration under N
        # concurrent shard threads would be GIL-inflated ~N-fold, skewing
        # every record's cycles field versus a serial run
        clock_hz = self.timer.calibrate_clock_hz()
        sessions = [
            Session(db=LatencyDB(path=self.db.path),
                    timer=Timer(warmup=self.timer.warmup, reps=self.timer.reps,
                                clock_hz=clock_hz, device=dev,
                                adaptive=self.adaptive),
                    force=force, device=dev,
                    compile_cache=self.compile_cache,  # thread-safe, shared
                    adaptive=self.adaptive, pipeline=self.pipeline)
            for dev in devices]
        logger.info("fan-out: plan '%s' (%d probes) over %d device(s)",
                    plan.name, len(plan), len(devices))
        with concurrent.futures.ThreadPoolExecutor(
                max_workers=len(devices),
                thread_name_prefix="repro-shard") as pool:
            futures = [pool.submit(sess.run, shard, force)
                       for sess, shard in zip(sessions, shards) if len(shard)]
            shard_results = [f.result() for f in futures]
        self.db.merge(*(sess.db for sess in sessions))
        if self.db.path:
            self.db.save()  # compaction: one atomic whole-file write
        return ResultSet(
            results=[r for rs in shard_results for r in rs.results],
            db=self.db)
