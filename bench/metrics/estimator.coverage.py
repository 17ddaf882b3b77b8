"""Mean, over the held-out programs, of ``PricedReport.coverage``: the share
of their operation instances the estimator priced from a measured row, in
percent."""


def read(run):
    cov = run.data.get("heldout_coverage")
    if not cov:
        return None
    return 100.0 * sum(cov.values()) / len(cov)
