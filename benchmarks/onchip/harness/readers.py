"""What metric readers share: the tasks a window counts, and FLOP shares."""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from . import device as D
from .lifecycle import phases


def counted(run, payload: str = None) -> List[Dict]:
    """Tasks submitted in the window that finished DONE inside it."""
    t1 = run.window[1]
    return [r for r in run.tasks
            if r["state"] == "DONE" and r["in_window"]
            and r["seen_t"] is not None and r["seen_t"] <= t1
            and (payload is None or r["payload"] == payload)]


def phase_mean_ms(run, payload: str, phase: str):
    ph = phases([r["stamps"] for r in counted(run, payload)])
    if not ph:
        return None
    return float(np.mean(ph[phase])) * 1e3


def mfu_percent(run, payload: str = None):
    """Model FLOPs of the counted tasks over the window's peak, in %."""
    recs = counted(run, payload)
    if not recs:
        return None
    done = sum(r["flops"] for r in recs)
    if done <= 0:
        return None
    peak = D.peak(run.device["kind"])["bf16_flops"]
    return 100.0 * done / (run.window_s * run.chips * peak)
