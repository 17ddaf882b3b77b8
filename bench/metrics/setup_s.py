"""Seconds from process start to the measured window's start: loading,
weights, compiles or compile-cache reads, warm-up and any ramp."""


def read(run):
    return run.setup_s
