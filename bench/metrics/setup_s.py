"""Seconds from process start to the window: data, start-up, warm-up
and, in a run that compiles, compilation."""


def read(run):
    return run.setup_s
