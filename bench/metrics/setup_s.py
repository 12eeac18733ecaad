"""Seconds from the start of the process to the opening of the window:
weights, loading, warm-up (compiles on a cold cache) and the ramp."""


def read(run):
    return run.setup_s
