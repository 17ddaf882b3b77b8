"""Share of the traced window of a serving cell in which no operation ran on
the device (profiler trace, ``bench/trace_reduce.py``), in percent."""


def read(run):
    if run.profile is None or "requests" not in run.data:
        return None
    return 100.0 * (1.0 - run.profile["busy_s"] / run.profile["window_s"])
