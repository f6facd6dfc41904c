"""Mean SCHEDULING -> ``tick_due`` of the scoring tasks counted in the
window: the wait for the due time of the dispatch tick that queued them,
the agent's modeled dispatch pacing (ROADMAP S3)."""
from harness.stamps import interval_mean_ms


def read(run):
    return interval_mean_ms(run, "score", ("SCHEDULING", "tick_due"))
