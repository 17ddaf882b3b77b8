"""Mean time of one admission (batch-1 prefill and slot write, ended by
``block_until_ready``), over the admissions that start in the window."""


def read(run):
    admits = run.data.get("admits")
    if admits is None:
        return None
    w0, w1 = run.window
    ts = [e - s for s, e, _ in admits if w0 <= s < w1]
    return sum(ts) / len(ts) * 1e3 if ts else None
