"""Benchmark orchestrator. One function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. ``--quick`` trims sweeps (used by CI);
the default run measures the full registry. All characterization benches route
through the ``repro.api`` Session/Plan pipeline (with ``force=True`` so perf
tracking re-measures), i.e. the exact code path
``python -m repro characterize`` users run.
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.core.timing import Timer
from repro.utils import logger


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names (e.g. clock,alu)")
    args = ap.parse_args()

    from benchmarks import (bench_characterize_speed, bench_collectives,
                            bench_serving_slo, paper_tables as pt)
    timer = Timer(warmup=2, reps=10 if args.quick else 20)
    benches = {
        "clock": lambda t: pt.bench_clock_overhead(t),
        "alu": lambda t: pt.bench_alu_latency(t, quick=args.quick),
        "optlevels": lambda t: pt.bench_optlevels(t),
        "memory": lambda t: pt.bench_memory_hierarchy(t, quick=args.quick),
        "onchip": lambda t: pt.bench_onchip_memory(t),
        "inkernel": lambda t: pt.bench_inkernel_vs_dispatch(t, quick=args.quick),
        "inkernel_memory": lambda t: pt.bench_inkernel_memory(t, quick=args.quick),
        "serving_cost": lambda t: pt.bench_serving_cost(t, quick=args.quick),
        "serving_slo": lambda t: bench_serving_slo.run_bench(t, quick=args.quick),
        "collectives": lambda t: bench_collectives.run_bench(t, quick=args.quick),
        "characterize_speed": lambda t: bench_characterize_speed.run_bench(
            t, quick=args.quick),
        "fanout": lambda t: pt.bench_fanout_scaling(t, quick=args.quick),
        "attention": lambda t: pt.bench_attention_impls(t),
    }
    only = set(args.only.split(",")) if args.only else set(benches)
    print("name,us_per_call,derived")
    for name, fn in benches.items():
        if name not in only:
            continue
        t0 = time.time()
        try:
            rows = fn(timer)
            pt._emit(rows)
        except Exception as e:  # noqa: BLE001
            print(f"{name}.ERROR,0.0,{type(e).__name__}: {e}")
            logger.exception("bench %s failed", name)
        logger.info("bench %s done in %.1fs", name, time.time() - t0)


if __name__ == "__main__":
    main()
