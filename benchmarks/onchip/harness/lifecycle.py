"""The runtime's five lifecycle phases per completed task, from its state
timestamps (a copy of the tiling in ``observability/lifecycle.py``):
hold + dispatch = SCHEDULING -> QUEUED, queue + launch = QUEUED -> RUNNING,
exec = RUNNING -> DONE. With the FIFO passthrough scheduler hold is 0."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

STAMPS = ("SCHEDULING", "QUEUED", "LAUNCHING", "RUNNING", "DONE")


def phases(stamps: List[Dict[str, float]]) -> Dict[str, np.ndarray]:
    """Seconds per task of dispatch (hold + dispatch), queue (queue +
    launch) and exec, for tasks that have every stamp."""
    rows = [[s[k] for k in STAMPS] for s in stamps
            if all(k in s for k in STAMPS)]
    if not rows:
        return {}
    a = np.asarray(rows, dtype=np.float64)
    return {"dispatch": a[:, 1] - a[:, 0], "queue": a[:, 3] - a[:, 1],
            "exec": a[:, 4] - a[:, 3]}


def union_seconds(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total
