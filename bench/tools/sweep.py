"""Find a serving cell's knee: its window at several fixed rates, one process.

    python3 bench/tools/sweep.py --workload internlm2-20b.chat --rates 2,3,4,5 \\
        --seed 11 --seconds 30 [--out chiprun_out/sweep.jsonl]

Sets the cell up once (weights, engine, every shape), then for each rate
runs the ramp and the window of the cell's own traffic at that rate and
prints one JSON line: tokens/s, TTFT p50 and p95, the queue wait's median
in each half of the window, and the backlog at the close (requests due and
not yet admitted). A rate is sustained where the second half's wait is no
longer than the first's and the backlog stays small; the knee is the
highest such rate. Needs the chip; times only mean something there.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def summarize(run) -> dict:
    from bench import stats

    w0, w1 = run.window
    reqs = {r["uid"]: r for r in run.data["requests"]}
    mid = (w0 + w1) / 2
    waits = {True: [], False: []}
    ttft = []
    backlog = 0
    for uid, t in run.data["due"]:
        r = reqs.get(uid)
        admit = r["admit"] if r is not None and r["admit"] < w1 else w1
        first = r["times"][0] if r is not None and r["times"][0] < w1 else w1
        backlog += r is None or r["admit"] >= w1
        waits[t < mid].append(admit - t)
        ttft.append(first - t)
    tokens = sum(1 for r in reqs.values() for t in r["times"] if w0 <= t <= w1)
    occupancy = [rows for s, _, rows, _ in run.data["steps"] if w0 <= s < w1]
    return {"tokens_per_s": tokens / run.window_s,
            "ttft_p50_ms": stats.percentile(ttft, 50) * 1e3,
            "ttft_p95_ms": stats.percentile(ttft, 95) * 1e3,
            "wait_p50_first_half_ms": stats.percentile(waits[True], 50) * 1e3,
            "wait_p50_second_half_ms": stats.percentile(waits[False], 50) * 1e3,
            "backlog_at_close": backlog, "due": len(run.data["due"]),
            "mean_rows": sum(occupancy) / max(len(occupancy), 1),
            "steps": len(occupancy)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from bench import harness

    cell = harness.load_cell(ROOT, args.workload)
    devices = harness.require_chips(cell["chips"])
    harness.use_compile_cache(harness.compile_cache_dir(ROOT))
    drv = harness.driver(ROOT, cell)
    base = harness.Run(root=ROOT, cell=cell, seed=args.seed,
                       seconds=args.seconds, trace=False,
                       t_start=harness.now(), devices=devices)
    state = drv.setup(base)
    pool = state["pool"]
    for rate in [float(r) for r in args.rates.split(",")]:
        for s in pool.active_slots():
            pool.evict(s)
        run = harness.Run(root=ROOT, cell=cell, seed=args.seed,
                          seconds=args.seconds, trace=False,
                          t_start=harness.now(), devices=devices)
        drv.window(run, pool, drv.traffic(run, rate))
        line = {"workload": args.workload, "rate_rps": rate,
                **summarize(run)}
        print(json.dumps(line), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
