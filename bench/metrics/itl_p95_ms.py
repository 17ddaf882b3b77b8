"""95th percentile of the gap between a request's consecutive tokens, over
every gap of every request that ends in the window: the tail of the
inter-token latency users see, which the decode step and every admission
landing between two steps set."""
from bench import stats


def read(run):
    reqs = run.data.get("requests")
    if reqs is None:
        return None
    w0, w1 = run.window
    gaps = [b - a for r in reqs for a, b in zip(r["times"], r["times"][1:])
            if w0 <= b <= w1]
    return stats.percentile(gaps, 95) * 1e3 if gaps else None
