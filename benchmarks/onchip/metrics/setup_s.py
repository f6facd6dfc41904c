"""Set-up seconds: from the start of the process to the opening of the
window (imports, weights, warm-up and any compilation)."""


def read(run):
    return run.setup_s
