"""Intervals between a counted task's stamps: the real engine's own stamps
beside its state stamps, ``tick_due`` (the due time of the dispatch tick
that queued it), ``picked`` (a worker thread took it), ``returned`` (the
payload returned) and ``ready`` (its device arrays were done). A program
that does not stamp them reads nothing."""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from .readers import counted


def interval_mean_ms(run, payload: str, *pairs: Tuple[str, str]
                     ) -> Optional[float]:
    """Mean over the counted tasks of ``payload`` of the summed
    ``stamps[end] - stamps[start]`` over ``pairs``, in ms; None where no
    counted task carries every stamp named."""
    keys = {k for pair in pairs for k in pair}
    rows = [r["stamps"] for r in counted(run, payload)
            if keys <= r["stamps"].keys()]
    if not rows:
        return None
    return float(np.mean([sum(s[e] - s[b] for b, e in pairs)
                          for s in rows])) * 1e3
