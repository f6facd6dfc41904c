"""Docking scores against the same formula computed here, exactly: every
docking task that finished in the window (``docking_mismatch``, limit 0)."""
from __future__ import annotations

import numpy as np


def check(run):
    recs = [r for r in run.tasks if r["payload"] == "docking"
            and r["in_window"] and r["state"] == "DONE"]
    bad = sum(1 for r in recs
              if r["result"] != float(np.sum(np.sin(r["mol"]) ** 2)))
    run.compare("docking_mismatch", bad, 0)
