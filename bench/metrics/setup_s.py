"""Seconds from process start to the window's opening: weights, engine,
warm-up (and, in a cell's first run, compilation)."""


def read(run):
    return run.setup_s
