"""Seconds from process start to the start of the window: loading,
data and weights, compiling or loading every program, warm-up."""


def read(run):
    return run.setup_s
