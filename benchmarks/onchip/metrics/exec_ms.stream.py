"""Mean exec phase (RUNNING -> DONE) of the scoring tasks counted in the
window: the payload, from its call to its host result."""
from harness.readers import phase_mean_ms


def read(run):
    return phase_mean_ms(run, "score", "exec")
