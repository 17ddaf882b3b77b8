"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. Sets the cell up (weights and inputs from the
seed, every shape it uses compiled or read from the compile cache at
``bench/.cache/jax``), measures for ``--seconds``, then checks what the
timed path produced against the plain reference. The last line on standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared with its limit, which also close standard
error. The compile cache stays in the checkout even where
``JAX_COMPILATION_CACHE_DIR`` is set. Exits 3, printing no result, when JAX
finds no accelerator or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, root: str = ROOT, t_start: float = T_START) -> int:
    args = parse(argv)
    from bench import harness

    cell = harness.load_cell(root, args.workload)
    try:
        devices = harness.require_chips(cell["chips"])
    except harness.NoAccelerator as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 3
    harness.use_compile_cache(harness.compile_cache_dir(root))
    run = harness.Run(root=root, cell=cell, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      t_start=t_start, devices=devices)
    harness.driver(root, cell).run(run)
    out = harness.result(run)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
