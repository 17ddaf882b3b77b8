"""95th percentile, over every request due in the window, of the wait in the
scheduler's queue: admission start minus due time (the window's end for a
request not admitted by then)."""
from bench import stats


def read(run):
    reqs = run.data.get("requests")
    if reqs is None:
        return None
    w0, w1 = run.window
    admits = {r["uid"]: r["admit"] for r in reqs}
    waits = [min(admits.get(uid, w1), w1) - t for uid, t in run.data["due"]]
    return stats.percentile(waits, 95) * 1e3 if waits else None
