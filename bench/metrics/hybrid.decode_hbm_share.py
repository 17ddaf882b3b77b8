"""Bytes the window's decode steps need (``bench/shapes_hybrid.py``: every
layer's weights with the held experts, the head, the live keys and values
of the attention layers and the served rows' Mamba state, read and
written), over their time at the chip's peak HBM rate, in percent. The
hybrid counterpart of ``model.decode_hbm_share``."""
from bench import shapes_hybrid


def read(run):
    steps = run.data.get("steps")
    if steps is None:
        return None
    w0, w1 = run.window
    inside = [(e - s, rows, keys) for s, e, rows, keys in steps if w0 <= s < w1]
    if not inside:
        return None
    need = sum(shapes_hybrid.decode_bytes(run.cfg, rows, keys)
               for _, rows, keys in inside)
    busy = sum(t for t, _, _ in inside)
    return 100.0 * need / (busy * run.peak("hbm_bytes_per_s"))
