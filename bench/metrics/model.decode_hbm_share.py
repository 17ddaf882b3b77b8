"""Bytes the window's decode steps need (``bench/shapes.py``: every layer's
weights and the head once a step, plus each served request's live keys and
values, not the cache's whole length), over their time at the chip's peak
HBM rate, in percent."""
from bench import shapes


def read(run):
    steps = run.data.get("steps")
    if steps is None:
        return None
    w0, w1 = run.window
    inside = [(e - s, rows, keys) for s, e, rows, keys in steps if w0 <= s < w1]
    if not inside:
        return None
    need = sum(shapes.decode_bytes(run.cfg, rows, keys)
               for _, rows, keys in inside)
    busy = sum(t for t, _, _ in inside)
    return 100.0 * need / (busy * run.peak("hbm_bytes_per_s"))
