"""Rows measured in the window (records that reached a pass's DB before it
closed) over the window's minutes."""


def read(run):
    rows = run.data.get("rows")
    if rows is None:
        return None
    return rows / (run.window_s / 60.0)
