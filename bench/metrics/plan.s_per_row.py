"""Seconds of ``Session.run``'s timing stage (``ResultSet.stage_ns['time']``)
per row, over the whole passes of the window."""


def read(run):
    pass_s = run.data.get("pass_s")
    if not pass_s:
        return None
    return (run.data["stage_ns"]["time"] * 1e-9
            / (len(pass_s) * run.data["rows_per_pass"]))
