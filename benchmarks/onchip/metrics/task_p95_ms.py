"""95th percentile, over every task that finished DONE inside the window,
of the time from its client's submit to the moment the client saw it
finished (numpy's linear percentile), in milliseconds."""
import numpy as np

from harness.readers import counted


def read(run):
    lat = [r["seen_t"] - r["submit_t"] for r in counted(run)]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
