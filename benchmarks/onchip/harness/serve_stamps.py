"""What the serving stamps of a task's result read: ``launch.serve.generate``
fills a dict of host-clock stamps and counters, which the generation
payload hands back as the third item of its result."""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


def stamps_of(rec: Dict) -> Dict:
    res = rec.get("result")
    if isinstance(res, (tuple, list)) and len(res) > 2 \
            and isinstance(res[2], dict):
        return res[2]
    return {}


def mean_ms(recs: List[Dict], start: str, end: str,
            per: Optional[str] = None) -> Optional[float]:
    """Mean over ``recs`` of ``end - start`` (divided by the counter
    ``per``, where given), in ms; None where no task carries them."""
    keys = {start, end} | ({per} if per else set())
    rows = [s for s in map(stamps_of, recs) if keys <= s.keys()
            and (per is None or s[per] > 0)]
    if not rows:
        return None
    return float(np.mean([(s[end] - s[start]) / (s[per] if per else 1)
                          for s in rows])) * 1e3
