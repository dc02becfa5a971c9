"""Seconds from process start to the start of the window: building, the
weights, warm-up and, in a run that compiles, compilation (host clock)."""


def read(run):
    return run.setup_s
