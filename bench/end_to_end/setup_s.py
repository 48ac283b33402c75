"""Seconds from process start to the start of the measured window: data,
load, compiles or compile-cache loads, and warm-up."""


def read(run):
    return run.setup_s
