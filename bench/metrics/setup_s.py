"""Set-up time: process start to the start of the window (weights,
engine, warm-up compiles or cache loads, a training cell's first
steps)."""


def read(run):
    return run.setup_s
