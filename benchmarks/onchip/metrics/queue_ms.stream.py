"""Mean queue + launch phase (QUEUED -> RUNNING) of the scoring tasks
counted in the window: the executors' queues and worker threads."""
from harness.readers import phase_mean_ms


def read(run):
    return phase_mean_ms(run, "score", "queue")
