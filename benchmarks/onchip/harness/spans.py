"""The device's idle time under the runtime's own host spans.

The real engine opens ``rp:*`` host spans around each layer of a task's
host path (submit, the dispatch tick, the RUNNING commit, the payload, the
device wait, the DONE commit, and every wait for the engine lock). For
each span name this reads, from a ``jax.profiler`` trace, the seconds in
which no operation ran on a device and some thread was inside that span,
inside the traced window (``bench:window``), averaged over devices as
``trace.reduce_trace`` averages its idle gaps. Threads overlap, so the
names' seconds need not add up to the idle time.
"""
from __future__ import annotations

import collections
import os
from typing import Callable, Dict, List, Optional, Tuple

from .trace import (TPU, WINDOW, _clip, _events, _overlap, _union,
                    find_xplane)

PREFIX = "rp:"


def program_idle(path: str, device: Dict[str, Callable] = TPU
                 ) -> Optional[Dict[str, float]]:
    """``path``: an ``.xplane.pb`` file or a trace directory. Returns
    {span name: device-idle seconds under it}, empty where the program
    opened no ``rp:`` span, and None where the trace holds no window span
    or no device operation."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    windows: List[Tuple[float, float]] = []
    spans: Dict[str, List[Tuple[float, float]]] = collections.defaultdict(
        list)
    per_device: List[List[Tuple[float, float]]] = []
    for plane in pd.planes:
        is_dev = device["plane"](plane.name)
        dev: List[Tuple[float, float]] = []
        for line in plane.lines:
            if is_dev and device["line"](line.name):
                dev.extend((s, e) for _, s, e in _events(line))
            elif plane.name.startswith("/host:"):
                for n, s, e in _events(line):
                    if n == WINDOW:
                        windows.append((s, e))
                    elif n.startswith(PREFIX):
                        spans[n].append((s, e))
        if is_dev and dev:
            per_device.append(dev)
    if not windows or not per_device:
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    unions = {n: _union(_clip(iv, lo, hi)) for n, iv in spans.items()}
    idle: Dict[str, float] = {n: 0.0 for n in unions}
    for dev in per_device:
        busy = _union(_clip(dev, lo, hi))
        edges = [lo] + [x for se in busy for x in se] + [hi]
        gaps = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                if g1 > g0]
        for n, iv in unions.items():
            idle[n] += sum(_overlap(iv, g0, g1)
                           for g0, g1 in gaps) / len(per_device)
    return idle
