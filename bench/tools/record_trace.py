"""Record a small profiler trace on the chip, for the trace reduction's test.

    python3 bench/tools/record_trace.py --out chiprun_out/v5e_small.xplane.pb

A tenth of a second or so of small jitted steps between the benchmark's
window marks, with ``bench.step`` and ``bench.wait`` host spans, traced as
the harness traces (host tracer on, Python tracer off); the ``.xplane.pb``
is copied to ``--out``.
"""
from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from bench import harness

    harness.require_chips(1)
    step = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    step(x).block_until_ready()
    log_dir = tempfile.mkdtemp(dir=os.path.join(ROOT, "bench", ".cache")
                               if os.path.isdir(os.path.join(
                                   ROOT, "bench", ".cache")) else None)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window_start"):
        pass
    for _ in range(20):
        with jax.profiler.TraceAnnotation("bench.step"):
            step(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(0.002)
    with jax.profiler.TraceAnnotation("bench.window_end"):
        pass
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    shutil.copy(path, args.out)
    shutil.rmtree(log_dir, ignore_errors=True)
    print(f"{args.out}: {os.path.getsize(args.out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
