"""Runtime seconds on the critical path, per iteration: the iteration's
time minus, for each of its stages, the union of its tasks' exec phases
(RUNNING -> DONE)."""
import numpy as np

from harness.lifecycle import union_seconds


def read(run):
    if not run.iterations:
        return None
    over = []
    for it in run.iterations:
        execs = 0.0
        for stage in it["stages"]:
            iv = [(r["stamps"]["RUNNING"], r["stamps"]["DONE"])
                  for r in run.tasks if r.get("iteration") == it["it"]
                  and r["stamps"].get("DONE") is not None
                  and r["stamps"].get("RUNNING") is not None
                  and r.get("stage") == stage]
            execs += union_seconds(iv)
        over.append(it["end"] - it["start"] - execs)
    return float(np.mean(over))
