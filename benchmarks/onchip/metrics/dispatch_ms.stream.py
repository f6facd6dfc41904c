"""Mean hold + dispatch phase (SCHEDULING -> QUEUED) of the scoring tasks
counted in the window: task manager, scheduler and agent dispatch."""
from harness.readers import phase_mean_ms


def read(run):
    return phase_mean_ms(run, "score", "dispatch")
