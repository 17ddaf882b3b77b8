"""One benchmark per paper table/figure (run via ``python -m benchmarks.run``).

Paper artifact -> bench:
  Fig. 5  clock overhead per opt level          -> bench_clock_overhead
  Table II ALU instruction latencies O3 vs O0   -> bench_alu_latency
  Table III version/level optimization deltas   -> bench_optlevels
  Fig. 6  global/L1/L2 + texture analog         -> bench_memory_hierarchy
  Table IV shared/constant memory analog        -> bench_onchip_memory
  Fig. 3  in-pipeline vs dispatch sampling      -> bench_inkernel_vs_dispatch
  Table IV + Fig. 6 in-kernel memory ladder     -> bench_inkernel_memory
  (Section I purpose) serving predicted-vs-meas -> bench_serving_cost
  (framework) attention/kernel-path comparison  -> bench_attention_impls
  (framework) sharded vs serial fan-out scaling -> bench_fanout_scaling
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import Plan, Session
from repro.core import chains, membench, optlevels, perfmodel
from repro.core.optlevels import OPT_LEVELS
from repro.core.timing import Timer
from repro.utils import dump_json

RESULTS = os.path.join(os.path.dirname(__file__), "results")


def _emit(rows: list[tuple[str, float, str]]) -> None:
    for name, us, derived in rows:
        print(f"{name},{us:.4f},{derived}")


# ------------------------------------------------------------------- Fig. 5
def bench_clock_overhead(timer: Timer) -> list[tuple[str, float, str]]:
    # force=True: benches must re-measure, not report cached numbers.
    result = Session(timer=timer).run(Plan.clock_overhead(OPT_LEVELS), force=True)
    ov = {r.opt_level: r.latency_ns for r in result.records()}
    dump_json(ov, f"{RESULTS}/clock_overhead.json")
    return [(f"clock_overhead.{lv}", ns / 1e3,
             f"timing-region overhead at {lv} (paper Fig.5)")
            for lv, ns in sorted(ov.items())]


# ----------------------------------------------------------------- Table II
def bench_alu_latency(timer: Timer, quick: bool = False) -> list[tuple[str, float, str]]:
    keep = {"add", "mul", "div.s.runtime", "div.s.regular", "fma.float32",
            "div.runtime.float32", "sqrt", "sin", "popc", "add.bfloat16"
            } if quick else None
    session = Session(db=f"{RESULTS}/latency_db.json", timer=timer)
    session.run(Plan.instructions(ops=keep, opt_levels=("O0", "O3")), force=True)
    db = session.db
    with open(f"{RESULTS}/table2_alu_latency.md", "w") as f:
        f.write(db.table_markdown())
    rows = []
    for cat in chains.CATEGORIES:
        recs = [r for r in db.query(opt_level="O3") if r.category == cat]
        if recs:
            med = float(np.median([r.latency_ns for r in recs]))
            rows.append((f"alu.{cat}.O3_median", med / 1e3,
                         f"{len(recs)} ops measured (paper Table II)"))
    return rows


# ---------------------------------------------------------------- Table III
def bench_optlevels(timer: Timer) -> list[tuple[str, float, str]]:
    """O1-vs-O3 deltas + the jax-version key for cross-version diffs."""
    keep = {"div.s.runtime", "div.s.irregular", "div.runtime.float32",
            "mul64hi", "popc", "sqrt"}
    session = Session(db=f"{RESULTS}/latency_db.json", timer=timer)
    session.run(Plan.instructions(ops=keep, opt_levels=("O1", "O3")), force=True)
    db = session.db
    rows = []
    for name in sorted(keep):
        o1 = db.lookup_ns(name, "O1")
        o3 = db.lookup_ns(name, "O3")
        if o1 and o3:
            delta = 100 * (o3 - o1) / max(o1, 1e-9)
            rows.append((f"optlevel.{name}", o3 / 1e3,
                         f"O1={o1:.1f}ns O3={o3:.1f}ns delta={delta:+.0f}%"
                         f" [{optlevels.o1_option_string()}]"))
    with open(f"{RESULTS}/table3_optlevels.md", "w") as f:
        f.write(db.table_markdown(opt_levels=("O3", "O1", "O0")))
    return rows


# ------------------------------------------------------------------- Fig. 6
def bench_memory_hierarchy(timer: Timer, quick: bool = False
                           ) -> list[tuple[str, float, str]]:
    sizes = [1 << k for k in (range(13, 24, 2) if quick else range(12, 26))]
    result = Session(timer=timer).run(Plan.memory(sizes), force=True)
    pts = [membench.mempoint_from_record(r) for r in result.records()]
    levels = membench.detect_levels(pts)
    bw = membench.bandwidth_probe(timer=timer)
    dump_json({"points": [vars(p) for p in pts], "levels": levels,
               "stream_bw_GBs": bw}, f"{RESULTS}/fig6_memory.json")
    rows = [(f"mem.ws_{p.working_set_bytes}", p.latency_ns / 1e3,
             f"hit={p.latency_ns:.2f}ns cold={p.cold_latency_ns:.2f}ns")
            for p in pts]
    for lv in levels:
        rows.append((f"mem.level{lv['level']}", lv["hit_latency_ns"] / 1e3,
                     f"capacity>={lv['capacity_bytes_lower_bound']}B "
                     f"(paper Fig.6 hierarchy cliff)"))
    rows.append(("mem.stream_bandwidth", 0.0, f"{bw:.2f} GB/s"))
    return rows


# ------------------------------------------------------- multi-device fan-out
def bench_fanout_scaling(timer: Timer, quick: bool = False
                         ) -> list[tuple[str, float, str]]:
    """Sharded vs serial wall-clock for one plan (docs/fanout.md).

    On a single-device host the two are identical (1 shard); on an N-device
    host (or under --xla_force_host_platform_device_count) the sharded run
    should approach serial/N while producing the same record set.
    """
    ops = ("add", "mul", "sqrt", "popc") if quick else tuple(
        o.name for o in chains.default_registry()[:12])
    plan = Plan.instructions(ops=ops, opt_levels=("O3",))
    n_dev = len(jax.local_devices())

    t0 = time.perf_counter()
    serial = Session(timer=timer).run(plan, force=True)
    t_serial = time.perf_counter() - t0

    fan_session = Session(timer=Timer(warmup=timer.warmup, reps=timer.reps))
    t0 = time.perf_counter()
    fanned = fan_session.fan_out(plan, force=True)
    t_fan = time.perf_counter() - t0

    same = ({r.key() for r in serial.db.records()}
            == {r.key() for r in fanned.db.records()})
    dump_json({"devices": n_dev, "probes": len(plan), "serial_s": t_serial,
               "fanout_s": t_fan, "record_sets_equal": same},
              f"{RESULTS}/fanout_scaling.json")
    return [("fanout.serial", t_serial * 1e6, f"{len(plan)} probes, 1 device"),
            ("fanout.sharded", t_fan * 1e6,
             f"{len(plan)} probes over {n_dev} device shard(s), "
             f"speedup={t_serial / max(t_fan, 1e-9):.2f}x, "
             f"records_equal={same}")]


# ---------------------------------------------------------------- Table IV
def bench_onchip_memory(timer: Timer) -> list[tuple[str, float, str]]:
    """Shared/constant-memory analog: Pallas in-kernel chase (VMEM-resident)
    vs host-level chase, in interpret mode for correctness and with slope
    timing for the numbers (on TPU this is the real VMEM latency probe)."""
    from repro.kernels.ops import chase
    n = 512
    ring = membench._ring_permutation(n)
    ring_j = jnp.asarray(ring)
    start = jnp.asarray([0], jnp.int32)

    def fn_by_len(steps):
        return jax.jit(lambda r, s: chase(r, s, steps=steps, interpret=True))

    est = timer.slope(fn_by_len, 64, 192, ring_j, start, reps=5)
    host = membench.measure_latency(n * 64, timer=timer, steps=(512, 1536))
    dump_json({"vmem_analog_ns": est.median_ns, "host_ns": host.latency_ns},
              f"{RESULTS}/table4_onchip.json")
    return [("onchip.pallas_chase", max(est.median_ns, 0) / 1e3,
             "in-kernel dependent load (paper Table IV shared-mem analog; "
             "interpret mode on CPU)"),
            ("onchip.host_chase", host.latency_ns / 1e3,
             "host-level chase, same working set")]


# --------------------------------- Table IV + Fig. 6: in-kernel memory rows
def bench_inkernel_memory(timer: Timer, quick: bool = False
                          ) -> list[tuple[str, float, str]]:
    """In-kernel chase ladder + host twins (docs/memory.md): per-load latency
    vs working-set size with the residency (VMEM-pinned vs HBM-streaming)
    recorded per rung. On TPU the in-kernel column is the paper's Table IV /
    Fig. 6 number; in interpret mode it validates the machinery."""
    from repro.kernels.chase import VMEM_BUDGET_BYTES

    sizes = ([VMEM_BUDGET_BYTES >> 6, VMEM_BUDGET_BYTES, VMEM_BUDGET_BYTES << 1]
             if quick else None)
    session = Session(db=f"{RESULTS}/latency_db.json", timer=timer)
    result = session.run(Plan.memory_inkernel(sizes), force=True)
    db = session.db
    # the shared bench DB also holds op-chain pairings; the ladder artifact
    # renders only the memory family
    from repro.core.latency_db import LatencyDB

    mem_db = LatencyDB()
    mem_db.extend(r for r in db.records() if r.category == "memory")
    with open(f"{RESULTS}/inkernel_memory.md", "w") as f:
        f.write(mem_db.compare_markdown())
    points = []
    for r in result.records():
        if not r.op.startswith("inkernel.mem."):
            continue
        pt = membench.chasepoint_from_record(r)
        # env-filtered like compare_markdown: the shared bench DB accumulates
        # runs across devices/jax versions, and a cross-env pairing is
        # meaningless
        host = db.lookup_ns(f"mem.chase.ws{pt.working_set_bytes}",
                            **session.env)
        points.append({"working_set_bytes": pt.working_set_bytes,
                       "inkernel_ns": pt.latency_ns,
                       "host_ns": host,
                       "memory_space": pt.memory_space,
                       "line_bytes": pt.line_bytes})
    points.sort(key=lambda p: p["working_set_bytes"])
    dump_json({"vmem_budget_bytes": VMEM_BUDGET_BYTES, "points": points},
              f"{RESULTS}/inkernel_memory.json")
    rows = []
    for p in points:
        host = (f"{p['host_ns']:.2f}ns" if p["host_ns"] is not None else "—")
        rows.append((f"inkernel.mem.ws_{p['working_set_bytes']}",
                     p["inkernel_ns"] / 1e3,
                     f"space={p['memory_space']} host={host} "
                     "(paper Table IV/Fig. 6 in-kernel)"))
    crossed = sorted({p["memory_space"] for p in points})
    rows.append(("inkernel.mem.boundary", 0.0,
                 f"ladder spans residencies {crossed} around the "
                 f"{VMEM_BUDGET_BYTES >> 20}MiB VMEM budget"))
    return rows


# ------------------------------------------ Fig. 3: in-pipeline vs dispatch
def bench_inkernel_vs_dispatch(timer: Timer, quick: bool = False
                               ) -> list[tuple[str, float, str]]:
    """Paired dispatch-vs-in-kernel table (repro.inkernel): every eligible op
    measured both at dispatch granularity and as a Pallas fori_loop chain,
    side by side. On TPU the in-kernel column is the paper's in-pipeline
    number; in interpret mode (this container) it validates the machinery."""
    cats = ("int_arith", "fp32") if quick else None
    keep = {"add", "mul", "mad", "div.s.runtime", "fma.float32",
            "div.runtime.float32", "add.float32"} if quick else None
    session = Session(db=f"{RESULTS}/latency_db.json", timer=timer)
    session.run(Plan.inkernel(ops=keep, categories=cats), force=True)
    db = session.db
    md = db.compare_markdown()
    with open(f"{RESULTS}/inkernel_vs_dispatch.md", "w") as f:
        f.write(md)
    rows = []
    for cat in chains.CATEGORIES:
        recs = [r for r in db.query(opt_level="O3")
                if r.category == cat and r.op.startswith("inkernel.")]
        if recs:
            med = float(np.median([r.latency_ns for r in recs]))
            rows.append((f"inkernel.{cat}.median", med / 1e3,
                         f"{len(recs)} ops in-kernel (paper Fig. 3 method)"))
    return rows


# ------------------------------------------- serving predicted vs measured
def bench_serving_cost(timer: Timer, quick: bool = False
                       ) -> list[tuple[str, float, str]]:
    """Serving-path characterization (docs/serving.md): the Engine's prefill
    and decode-step HLO priced from the measured LatencyDB vs its wall
    clock, per (batch, prompt_len) cell. The paper's stated purpose made a
    bench: measured tables feeding a performance model of a real program."""
    from repro.api.plan import SERVING_CELLS

    cells = SERVING_CELLS[:1] if quick else SERVING_CELLS
    session = Session(db=f"{RESULTS}/latency_db.json", timer=timer)
    result = session.run(Plan.serving(cells=cells), force=True)
    db = session.db
    with open(f"{RESULTS}/serving_cost.md", "w") as f:
        f.write(db.compare_markdown(prefix="serving."))
    points = sorted(
        (perfmodel.servingpoint_from_record(r) for r in result.records()
         if r.op.startswith("serving.")),
        key=lambda p: (p.phase, p.batch, p.prompt_len))
    dump_json({"cells": [vars(p) for p in points]},
              f"{RESULTS}/serving_cost.json")
    rows = []
    for p in points:
        rows.append((f"serving.{p.phase}.b{p.batch}p{p.prompt_len}",
                     p.measured_ns / 1e3,
                     f"predicted={p.predicted_ns:.0f}ns ratio={p.ratio:.3f} "
                     f"coverage={p.coverage:.2f} (perfmodel x LatencyDB)"))
    return rows


# ------------------------------------------------- framework: attention path
def bench_attention_impls(timer: Timer) -> list[tuple[str, float, str]]:
    from repro.models import common
    b, s, h, kh, d = 2, 1024, 8, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kh, d), jnp.float32)
    rows = []
    for impl in ("plain", "blockwise"):
        fn = jax.jit(lambda q, k, v, impl=impl: common.attention(
            q, k, v, causal=True, impl=impl, block_k=256))
        m = timer.time_callable(fn, q, k, v, reps=10)
        rows.append((f"attention.{impl}", m.median_ns / 1e3,
                     f"B{b} S{s} H{h} D{d} f32 (host CPU)"))
    return rows

