"""Seconds per campaign iteration: the whole window over the whole
iterations in it."""


def read(run):
    return run.window_s / len(run.iterations) if run.iterations else None
