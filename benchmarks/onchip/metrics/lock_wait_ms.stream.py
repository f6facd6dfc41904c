"""Mean (``picked`` -> RUNNING) + (``ready`` -> DONE) of the scoring tasks
counted in the window: the worker thread's two commits under the engine
lock, with its waits for that lock."""
from harness.stamps import interval_mean_ms


def read(run):
    return interval_mean_ms(run, "score", ("picked", "RUNNING"),
                            ("ready", "DONE"))
