"""Readings that a cell's correctness limits are set from, in one process.

    python3 bench/tools/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --seconds 15 [--out chiprun_out/calibrate.jsonl]

Serving cells: for each seed, weights from the seed in the one engine, a
fresh slot pool, the ramp and a short window at the cell's own rate, then
the sample a run compares, read against the float32 reference: the
program's widest gap (``worst_gap_std``, the lower reading's candidates) and
the widest gap of the controls' first choices, the reference computed with
every matmul input in int8 and in fp8 (the upper reading's candidates).
With ``--eps``, the program's sample is also read against the reference at
each of those RMSNorm epsilons (a witness for an epsilon the program
departs to), request by request.

Characterization cells: for each seed, a short window of the control, the
cell's pass at the Pallas interpreter's chain lengths, and its
``row_noise``; and the held-out programs computed with fp8 matmul inputs,
against their float32 references (``heldout_err``'s control).

One JSON line per seed. The benchmark's own runs never run a control.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

QUANTS = ("int8", "fp8")


def serve_seeds(cell, devices, seeds, seconds, eps=()):
    import jax

    from bench import harness

    drv = harness.driver(ROOT, cell)
    cfg = cell["config_data"]
    adapter = harness.adapter(ROOT, cfg)
    ref = harness.reference(ROOT, cfg)
    engine = None
    for seed in seeds:
        run = harness.Run(root=ROOT, cell=cell, seed=seed, seconds=seconds,
                          trace=False, t_start=harness.now(), devices=devices)
        if engine is None:
            state = drv.setup(run)
            engine, pool = state["engine"], state["pool"]
            del state
        else:
            weights = ref.init_weights(seed, cfg)
            engine.params = adapter.program(cfg, weights)[0]
            del weights
            pool = engine.slots(cell["slots"])
            for n in run.mix["prompt_len"]["values"]:
                pool.admit(0, [1] * n, uid=-1, max_new=1)
                pool.evict(0)
            jax.block_until_ready(pool.cache)
        drv.window(run, pool, drv.traffic(run, cell["rate_rps"]))
        sample = drv._sample(run.data["requests"], run.window, seed,
                             cell["check"])
        del pool
        engine.params = None
        gc.collect()
        t = harness.now()
        worst, controls = drv.compare(run, sample, quants=QUANTS)
        line = {"seed": seed, "worst_gap_std": worst,
                "compare_s": harness.now() - t,
                **{f"control_{q}_gap_std": v for q, v in controls.items()},
                "sampled": len(sample),
                "served_tokens": sum(len(r["tokens"]) for r in sample)}
        for e in eps:
            line[f"eps_{e:g}"] = witness(run, ref, sample, e)
        yield line


def witness(run, ref, sample, eps):
    """Per request of the sample: prompt length, served tokens, and the
    widest gap and its position against the reference at RMSNorm ``eps``."""
    cfg = {**run.cfg, "rms_norm_eps": eps}
    weights = ref.init_weights(run.seed, cfg)
    out = []
    for r in sample:
        gaps, _ = ref.served_gaps(weights, cfg, r["prompt"], r["tokens"],
                                  run.cell["max_len"],
                                  run.mix["max_new"]["hi"])
        out.append([len(r["prompt"]), len(r["tokens"]), float(gaps.max()),
                    int(gaps.argmax())])
    del weights
    gc.collect()
    return out


def characterize_seeds(cell, devices, seeds, seconds):
    import jax.numpy as jnp

    from bench import harness, heldout
    from bench.models.dense_decoder import seed_key

    drv = harness.driver(ROOT, cell)
    for seed in seeds:
        run = harness.Run(root=ROOT, cell=cell, seed=seed, seconds=seconds,
                          trace=False, t_start=harness.now(), devices=devices)
        drv.run(run, lens="interpret")
        key = seed_key(seed)
        fp8 = max(drv.heldout_error(name, heldout.program(
            name, jnp.float8_e4m3fn)(*heldout.inputs(name, key)), key)
            for name in heldout.NAMES if name != "small_step")
        yield {"seed": seed, "control": "interpret chain lengths",
               "control_row_noise": run.checks["row_noise"][0],
               "control_rows": run.data["rows"],
               "control_fp8_heldout_err": fp8,
               "program_heldout_err": run.checks["heldout_err"][0],
               "chase_mismatch": run.checks["chase_mismatch"][0]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--eps", default="",
                    help="comma-separated RMSNorm epsilons to read the "
                         "serving sample against as well")
    args = ap.parse_args()

    from bench import harness

    cell = harness.load_cell(ROOT, args.workload)
    devices = harness.require_chips(cell["chips"])
    harness.use_compile_cache(harness.compile_cache_dir(ROOT))
    seeds = [int(s) for s in args.seeds.split(",")]
    if cell["driver"] == "serve":
        eps = tuple(float(e) for e in args.eps.split(",") if e)
        readings = serve_seeds(cell, devices, seeds, args.seconds, eps)
    else:
        readings = characterize_seeds(cell, devices, seeds, args.seconds)
    for line in readings:
        line = {"workload": args.workload, **line}
        print(json.dumps(line), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
