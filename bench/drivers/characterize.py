"""Characterization cells: the program's plan, pass after pass, on one chip.

The window runs ``repro.api.Session.run`` over the cell's plan
(``bench/traffic/<mix>.json`` composes it from ``repro.api.Plan`` builders
and probes), pass after pass, each a fresh ``Session`` and ``Timer`` over a
fresh in-memory ``LatencyDB`` with ``force=True``: what a user pays to get
a DB. A row counts when its probe's record reaches the DB inside the
window; the window closes at the first record after it (the probe in
flight is not counted, and the pass stops there).

Set-up, counted in ``setup_s``: one whole pass that is not counted (its
compiles or compile-cache reads, and whatever else a pass does first in a
process), and the held-out programs' compiles.

A run's ``attempted`` and ``failed`` count the rows measured and the probes
failed in the window. After the window: the held-out programs
(``bench/heldout.py``) are timed on the chip (:func:`time_heldout`) and
priced by ``HloLatencyEstimator`` from the window's rows, the newest of each
probe; standard error gets each program's two times before the checks.

Correctness compares three numbers with their limits: ``row_noise``, the
largest MAD over latency of those rows (infinite where a probe failed in the
window or had no row in it; the limit, one third, is the 3-MAD rule of
``LatencyRecord.resolved``); ``chase_mismatch``, how many of two chases (one
VMEM-resident and one HBM-streaming ring, built here from the seed) end
elsewhere than a plain host chase says (limit 0); and ``heldout_err``, the
largest ``max |program - reference| / std(reference)`` of the held-out
programs against their float32 references.
"""
from __future__ import annotations

import math
import sys
import threading
import time

import numpy as np

from bench import harness, heldout

BLOCKS, BLOCK_S = 9, 0.2


def build_plan(mix: dict, lens: str | None = None):
    """The pass's plan: ``mix['plan']`` entries, each ``{"plan": builder}``
    or ``{"probe": class}`` with ``args``. ``lens="interpret"`` gives every
    in-kernel entry the Pallas interpreter's chain lengths instead of the
    chip's (the control)."""
    from repro import api, inkernel

    short = {"inkernel": inkernel.INKERNEL_LENS.interpret,
             "memory_inkernel": inkernel.CHASE_LENS.interpret,
             "fused": inkernel.FUSED_LENS.interpret}
    plan = api.Plan()
    for entry in mix["plan"]:
        args = dict(entry.get("args", {}))
        if "plan" in entry:
            if lens == "interpret" and entry["plan"] in short:
                args["lens"] = short[entry["plan"]]
            plan = plan + getattr(api.Plan, entry["plan"])(**args)
        else:
            plan = plan + api.Plan((getattr(api, entry["probe"])(**args),))
    return plan


def _ring(working_set_bytes: int, seed: int, line_bytes: int = 64):
    """A one-cycle pointer-chase ring, one live slot per line, from the seed
    (Sattolo's shuffle): ``(flat int32 table, start)``."""
    from bench import arrivals

    n = max(working_set_bytes // line_bytes, 8)
    pad = line_bytes // 4
    perm = np.arange(n)
    rng = arrivals.philox(seed, 2 << 20, working_set_bytes)
    picks = (rng.random(n - 1) * np.arange(n - 1, 0, -1)).astype(np.int64)
    for i, j in zip(range(n - 1, 0, -1), picks.tolist()):
        perm[i], perm[j] = perm[j], perm[i]
    table = np.zeros(n * pad, np.int32)
    table[np.arange(n) * pad] = perm * pad
    return table, 0


def host_chase(table: np.ndarray, start: int, steps: int) -> int:
    p = start
    for _ in range(steps):
        p = int(table[p])
    return p


def chase_mismatches(seed: int) -> int:
    """Chases of one VMEM-resident and one HBM-streaming ring through the
    program's Pallas kernel, against :func:`host_chase`."""
    import jax.numpy as jnp

    from repro.inkernel.measure import CHASE_LENS
    from repro.kernels.chase import VMEM_BUDGET_BYTES, chase

    steps = CHASE_LENS.here()[1]
    bad = 0
    for ws in (VMEM_BUDGET_BYTES >> 4, VMEM_BUDGET_BYTES << 1):
        table, start = _ring(ws, seed)
        got = int(np.asarray(chase(jnp.asarray(table),
                                   jnp.asarray([start], jnp.int32),
                                   steps=steps))[0])
        bad += got != host_chase(table, start, steps)
    return bad


def row_noise(records, failures: int) -> float:
    if failures:
        return math.inf
    worst = 0.0
    for r in records:
        ratio = r.mad_ns / r.latency_ns if r.latency_ns > 0 else math.inf
        worst = max(worst, ratio)
    return worst


class _WindowDB:
    """Makes fresh ``LatencyDB``s whose ``add`` stamps each row on the host
    clock, keeps it, and closes the window: the first row after it raises."""

    def __init__(self, close: float):
        from repro.core.latency_db import LatencyDB

        outer = self
        self.close = close
        self.stamps: list[float] = []
        self.rows = LatencyDB()          # the newest row of each probe

        class DB(LatencyDB):
            def add(self, rec):
                t = harness.now()
                if t >= outer.close:
                    raise harness.WindowClosed
                outer.stamps.append(t)
                outer.rows.add(rec)
                super().add(rec)

        self.cls = DB


def _wait_for_compile_threads() -> None:
    """A closed window leaves the session's compile-ahead thread finishing
    its prepare; wait for it before timing anything else."""
    for t in threading.enumerate():
        if t.name.startswith("repro-compile"):
            t.join()


def time_heldout(programs: dict) -> dict:
    """Wall time of one blocked call of each compiled program: the median,
    over ``BLOCKS`` blocks of at least ``BLOCK_S`` seconds, of the block's
    mean, so that a host stall spoils one block and not the reading."""
    import jax

    out = {}
    for name, (fn, args) in programs.items():
        jax.block_until_ready(fn(*args))
        means = []
        for _ in range(BLOCKS):
            n, t0 = 0, time.perf_counter()
            while True:
                jax.block_until_ready(fn(*args))
                n += 1
                if time.perf_counter() - t0 >= BLOCK_S and n >= 3:
                    break
            means.append((time.perf_counter() - t0) / n)
        out[name] = float(np.median(means))
    return out


def heldout_error(name: str, got, key) -> float:
    import jax
    import jax.numpy as jnp

    want = heldout.reference(name)(*heldout.inputs(name, key))
    err = jnp.max(jnp.abs(got.astype(jnp.float32) - want)) / jnp.std(want)
    return float(jax.device_get(err))


def run(run: harness.Run, lens: str | None = None) -> None:
    from bench.models.dense_decoder import seed_key
    from repro.api import Session
    from repro.core.latency_db import LatencyDB, current_environment
    from repro.core.perfmodel import HloLatencyEstimator
    from repro.core.timing import Timer

    plan = build_plan(run.mix, lens)
    # set-up: one whole pass, uncounted, so that everything a pass compiles,
    # reads from the compile cache or touches for the first time in the
    # process is done before the window; and the held-out programs' compiles
    Session(db=LatencyDB(), timer=Timer(), force=True).run(plan)
    key = seed_key(run.seed)
    compiled = {}
    for name in heldout.NAMES:
        args = heldout.inputs(name, key)
        compiled[name] = (heldout.program(name).lower(*args).compile(), args)

    profile = harness.Profile(run)
    profile.start()
    t0 = harness.now()
    run.window = (t0, t0 + run.seconds)
    run.setup_s = t0 - run.t_start
    run.mark("bench.window_start")
    hook = _WindowDB(run.window[1])
    passes, stage_ns, failed = [], {}, 0
    try:
        while True:
            t = harness.now()
            with run.span("bench.pass"):
                session = Session(db=hook.cls(), timer=Timer(), force=True)
                res = session.run(plan)
            passes.append(harness.now() - t)
            failed += len(res.failed)
            for k, v in res.stage_ns.items():
                stage_ns[k] = stage_ns.get(k, 0) + v
    except harness.WindowClosed:
        failed += len(session.db.failures())
    run.mark("bench.window_end")
    _wait_for_compile_threads()
    profile.stop()
    run.memory_peak_bytes = harness.memory_peak_bytes(run.devices)
    rows = len(hook.stamps)
    run.attempted = rows + failed
    run.failed = failed
    db = hook.rows

    env = current_environment()
    est = HloLatencyEstimator(db, filters={k: env[k] for k in
                                           ("device_kind", "backend",
                                            "jax_version")})
    measured = time_heldout(compiled)
    priced = {}
    for name, (fn, args) in compiled.items():
        priced[name] = est.estimate(fn.as_text())
        print(f"heldout {name}: measured {measured[name]!r} s, predicted "
              f"{priced[name].total_ns * 1e-9!r} s", file=sys.stderr)
    run.data.update(rows=rows, pass_s=passes,
                    rows_per_pass=len(plan), stage_ns=stage_ns,
                    heldout_measured_s=measured,
                    heldout_predicted_s={k: r.total_ns * 1e-9
                                         for k, r in priced.items()},
                    heldout_coverage={k: r.coverage for k, r in priced.items()})

    missing = len(plan) - len(db)
    run.check("row_noise", row_noise(db.query(), failed + missing),
              run.cell["limits"]["row_noise"])
    run.check("chase_mismatch", chase_mismatches(run.seed),
              run.cell["limits"]["chase_mismatch"])
    run.check("heldout_err", max(
        heldout_error(name, fn(*args), key)
        for name, (fn, args) in compiled.items()),
        run.cell["limits"]["heldout_err"])
