"""Serving cells: the program's continuous batching under open-loop traffic.

The window drives the program's own path: ``repro.traffic.scheduler``'s
``ContinuousBatchingScheduler`` (its FIFO admission policy) over
``repro.serving.SlotPool`` (``admit`` / ``step`` / ``evict``), through
:class:`WallClockExecutor`, which holds each admission until its request is
due on the wall clock, stamps every admission, step and token on the host
clock, and ends the run at the first call after the window closes, without
draining.

Set-up, counted in ``setup_s``: weights from the seed on the device in one
call, the traffic from the seed, one prefill and slot write of every prompt
length the traffic holds and one decode step (compiled, or read from the
compile cache), then a ramp of the cell's own traffic (``ramp_s``) that
brings the pool to steady occupancy. The window follows the ramp without a
pause.

Correctness, after the window closes and the program's state is freed: a
sample of the requests finished in the window, drawn from the seed and
always holding the one with the most served tokens, runs through the plain
float32 reference (``bench/models/<family>.py``) over its prompt and served
tokens. The number compared is the widest gap by which a served token's
reference logit lies below the reference's best at its position, in
reference standard deviations (``worst_gap_std``).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import arrivals, harness


class WallClockExecutor:
    """The scheduler's executor on the wall clock.

    The scheduler advances a clock by the cost each call returns, and when
    nothing is active it jumps that clock to the next arrival. This
    executor mirrors that clock and returns as cost the wall time since the
    clock's last value, so that after every call the scheduler's clock is
    the wall clock since the traffic began; an admission due later than now
    (only after such a jump) waits until it is due."""

    def __init__(self, run: harness.Run, pool, t0: float):
        self.run, self.pool, self.t0 = run, pool, t0
        self.close = run.window[1]
        self.clock_ns = 0.0
        self.slot_uid: dict[int, int] = {}
        self.requests: dict[int, dict] = {}
        self.admits: list[tuple[float, float, int]] = []
        self.steps: list[tuple[float, float, int, int]] = []
        self.n_slots = pool.n_slots
        self.marks = _WindowMarks(run)

    def _cost(self, end: float) -> float:
        cost = (end - self.t0) * 1e9 - self.clock_ns
        self.clock_ns += cost
        return cost

    def admit(self, slot: int, req):
        from repro.utils import block

        self.marks.tick()
        if req.arrival_ns > self.clock_ns:        # the scheduler's idle jump
            self.clock_ns = req.arrival_ns
        due = self.t0 + req.arrival_ns * 1e-9
        if max(due, harness.now()) >= self.close:
            raise harness.WindowClosed
        if harness.now() < due:
            with self.run.span("bench.wait"):
                time.sleep(max(due - harness.now(), 0.0))
        start = harness.now()
        with self.run.span("bench.admit"):
            tok = self.pool.admit(slot, list(req.prompt), uid=req.uid,
                                  max_new=req.max_new)
            block(self.pool.cache)
        end = harness.now()
        self.admits.append((start, end, len(req.prompt)))
        self.requests[req.uid] = {
            "uid": req.uid, "due": due, "admit": start, "prompt": req.prompt,
            "max_new": req.max_new, "tokens": [tok], "times": [end],
            "finish": None}
        self.slot_uid[slot] = req.uid
        return tok, self._cost(end)

    def step(self):
        from repro.utils import block

        self.marks.tick()
        if harness.now() >= self.close:
            raise harness.WindowClosed
        keys = sum(self.pool.position(s) + 1 for s in self.slot_uid)
        start = harness.now()
        with self.run.span("bench.step"):
            toks = self.pool.step()
            block(self.pool.cache)
        end = harness.now()
        self.steps.append((start, end, len(self.slot_uid), keys))
        for slot, uid in self.slot_uid.items():
            r = self.requests[uid]
            r["tokens"].append(int(toks[slot]))
            r["times"].append(end)
        return toks, self._cost(end)

    def evict(self, slot: int) -> None:
        uid = self.slot_uid.pop(slot)
        self.requests[uid]["finish"] = self.requests[uid]["times"][-1]
        self.pool.evict(slot)


def _sample(requests: list[dict], window, seed: int, want: dict) -> list[dict]:
    """Requests finished in the window: the one with the most served tokens,
    then others drawn from the seed until ``want['tokens']`` served tokens
    or ``want['requests']`` requests."""
    done = [r for r in requests
            if r["finish"] is not None and window[0] <= r["finish"] <= window[1]]
    if not done:
        return []
    done.sort(key=lambda r: (len(r["tokens"]), len(r["prompt"])))
    picked = [done.pop()]
    rng = arrivals.philox(seed, 1 << 20)
    for i in rng.permutation(len(done)):
        if (sum(len(r["tokens"]) for r in picked) >= want["tokens"]
                or len(picked) >= want["requests"]):
            break
        picked.append(done[i])
    return picked


def setup(run: harness.Run) -> dict:
    """Weights from the seed, the engine and its slot pool, every shape the
    cell's traffic uses compiled (or read from the compile cache)."""
    import jax

    from repro.serving import Engine

    cell, cfg = run.cell, run.cfg
    weights = harness.reference(run.root, cfg).init_weights(run.seed, cfg)
    jax.block_until_ready(weights)
    params, mcfg, rt = harness.adapter(run.root, cfg).program(cfg, weights)
    engine = Engine(params, mcfg, rt, max_len=cell["max_len"])
    pool = engine.slots(cell["slots"])
    # prefill and slot write per prompt length, and the decode step
    for i, n in enumerate(run.mix["prompt_len"]["values"]):
        pool.admit(0, [1] * n, uid=-1, max_new=2)
        if i == 0:
            pool.step()
        pool.evict(0)
    jax.block_until_ready(pool.cache)
    return {"weights": weights, "engine": engine, "pool": pool}


def traffic(run: harness.Run, rate_rps: float):
    """The cell's requests from the seed, due over the ramp and the window."""
    from repro.traffic.traces import Request

    arr = arrivals.generate(run.mix, run.seed, rate_rps,
                            float(run.cell["ramp_s"]) + run.seconds + 5.0,
                            run.cfg["vocab_size"])
    return [Request(uid=a.uid, arrival_ns=a.at_s * 1e9, prompt=a.prompt,
                    max_new=a.max_new) for a in arr]


def window(run: harness.Run, pool, trace) -> None:
    """The ramp, then the window: the program's scheduler over the pool
    until the first call after the window closes."""
    from repro.traffic.scheduler import ContinuousBatchingScheduler

    profile = harness.Profile(run)
    profile.start()
    t0 = harness.now()
    run.window = (t0 + float(run.cell["ramp_s"]),
                  t0 + float(run.cell["ramp_s"]) + run.seconds)
    run.setup_s = run.window[0] - run.t_start
    ex = WallClockExecutor(run, pool, t0)
    try:
        ContinuousBatchingScheduler(ex).run(trace)
    except harness.WindowClosed:
        pass
    ex.marks.end()
    profile.stop()
    run.memory_peak_bytes = harness.memory_peak_bytes(run.devices)
    due = [(r.uid, t0 + r.arrival_ns * 1e-9) for r in trace]
    due = [(uid, t) for uid, t in due if run.window[0] <= t < run.window[1]]
    run.attempted = len(due)
    run.data.update(requests=sorted(ex.requests.values(),
                                    key=lambda r: r["due"]),
                    admits=ex.admits, steps=ex.steps, due=due)


def run(run: harness.Run) -> None:
    state = setup(run)
    window(run, state["pool"], traffic(run, run.cell["rate_rps"]))
    sample = _sample(run.data["requests"], run.window, run.seed,
                     run.cell["check"])
    state.clear()
    gc.collect()
    run.check("worst_gap_std", compare(run, sample),
              run.cell["limits"]["worst_gap_std"])


def compare(run: harness.Run, sample: list[dict], quants: tuple = ()):
    """Widest gap of the sample's served tokens below the reference's best,
    and for each precision in ``quants`` the widest of that control's."""
    cfg, cell = run.cfg, run.cell
    ref = harness.reference(run.root, cfg)
    weights = ref.init_weights(run.seed, cfg)
    worst = np.inf if not sample else -np.inf
    controls = {q: -np.inf for q in quants}
    for r in sample:
        gaps, ctl = ref.served_gaps(weights, cfg, r["prompt"], r["tokens"],
                                    cell["max_len"], run.mix["max_new"]["hi"],
                                    quants=quants)
        worst = max(worst, float(gaps.max()))
        for q, g in ctl.items():
            controls[q] = max(controls[q], float(g.max()))
    del weights
    gc.collect()
    return (worst, controls) if quants else worst


class _WindowMarks:
    """Marks the window's two ends in the trace, at the first call into the
    executor on or after each."""

    def __init__(self, run: harness.Run):
        self.run = run
        self.started = self.ended = False

    def tick(self) -> None:
        t = harness.now()
        if not self.started and t >= self.run.window[0]:
            self.run.mark("bench.window_start")
            self.started = True

    def end(self) -> None:
        if not self.started:
            self.run.mark("bench.window_start")
            self.started = True
        if not self.ended:
            self.run.mark("bench.window_end")
            self.ended = True
