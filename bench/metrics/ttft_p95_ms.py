"""95th percentile, over every request due in the window, of its first
token's time minus the time it was due (queueing included). A request with
no first token by the window's end counts at the window's end."""
from bench import stats


def read(run):
    reqs = run.data.get("requests")
    if reqs is None:
        return None
    w0, w1 = run.window
    firsts = {r["uid"]: r["times"][0] for r in reqs}
    ttft = [min(firsts.get(uid, w1), w1) - t for uid, t in run.data["due"]]
    return stats.percentile(ttft, 95) * 1e3 if ttft else None
