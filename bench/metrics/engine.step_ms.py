"""The window's total decode-step time over its number of steps (each step
ended by ``block_until_ready``)."""


def read(run):
    steps = run.data.get("steps")
    if steps is None:
        return None
    w0, w1 = run.window
    ts = [e - s for s, e, _, _ in steps if w0 <= s < w1]
    return sum(ts) / len(ts) * 1e3 if ts else None
