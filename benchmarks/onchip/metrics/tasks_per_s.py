"""Tasks that finished DONE inside the window, per second of the window."""
from harness.readers import counted


def read(run):
    return len(counted(run)) / run.window_s
