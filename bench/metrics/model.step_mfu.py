"""Model operations of every prefill and decode token completed in the
window (``bench/shapes.py``, from the configuration's shapes), over the
window's length times the chip's peak bf16 rate, in percent."""
from bench import shapes


def read(run):
    steps = run.data.get("steps")
    if steps is None:
        return None
    w0, w1 = run.window
    flops = sum(shapes.prefill_flops(run.cfg, n)
                for _, e, n in run.data["admits"] if w0 <= e <= w1)
    flops += sum(shapes.decode_flops(run.cfg, rows, keys)
                 for _, e, rows, keys in steps if w0 <= e <= w1)
    peak = run.peak("bf16_flops_per_s") * len(run.devices)
    return 100.0 * flops / (run.window_s * peak)
