"""Mean QUEUED -> ``picked`` of the scoring tasks counted in the window:
the wait for a free dragon worker thread."""
from harness.stamps import interval_mean_ms


def read(run):
    return interval_mean_ms(run, "score", ("QUEUED", "picked"))
