"""Output tokens emitted in the window over the window's length (every
token of every request, the first one from prefill included)."""


def read(run):
    reqs = run.data.get("requests")
    if reqs is None:
        return None
    w0, w1 = run.window
    n = sum(1 for r in reqs for t in r["times"] if w0 <= t <= w1)
    return n / run.window_s
