"""Reduce a ``jax.profiler`` trace to the device's busy time, the device
operations that took most time, and the idle gaps named by what the host
was doing in them.

Busy time on a device is the union of the intervals of its operations;
an operation's own time leaves out the operations nested inside it.
The traced window is the host span named ``WINDOW``, which the benchmark
opens around the traced part of its measured window. A gap in which no
operation ran on a device is charged to the benchmark span (``bench:*``)
that overlaps it most, or to ``untraced``.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Callable, Dict, List, Optional, Tuple

WINDOW = "bench:window"
SPAN_PREFIX = "bench:"

# TPU traces: one plane per chip, the operations on its "XLA Ops" line
TPU = {"plane": lambda name: name.startswith("/device:TPU:"),
       "line": lambda name: name == "XLA Ops"}
# CPU traces (tests only): the XLA CPU client's thread lines on the host plane
CPU = {"plane": lambda name: name == "/host:CPU",
       "line": lambda name: name.startswith("tf_XLAPjRtCpuClient")}


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _events(line) -> List[Tuple[str, float, float]]:
    """(name, start s, end s) of the line's events; a device operation is
    named by its HLO instruction (``%fusion.12``), not its whole text."""
    return [(e.name.split(" = ", 1)[0], e.start_ns * 1e-9,
             (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events if e.duration_ns > 0]


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def reduce_trace(path: str, device: Dict[str, Callable] = TPU, top: int = 10
                 ) -> Optional[Dict]:
    """``path``: an ``.xplane.pb`` file or a trace directory. Returns None
    where the trace holds no window span or no device operation."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    spans: List[Tuple[str, float, float]] = []
    per_device: List[List[Tuple[str, float, float]]] = []
    for plane in pd.planes:
        is_dev = device["plane"](plane.name)
        dev_events: List[Tuple[str, float, float]] = []
        for line in plane.lines:
            if is_dev and device["line"](line.name):
                dev_events.extend(_events(line))
            elif plane.name.startswith("/host:"):
                spans.extend(ev for ev in _events(line)
                             if ev[0].startswith(SPAN_PREFIX))
        if is_dev and dev_events:
            per_device.append(dev_events)
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows or not per_device:
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    window_s = hi - lo
    by_name: Dict[str, List[Tuple[float, float]]] = collections.defaultdict(
        list)
    for n, s, e in spans:
        if n != WINDOW:
            by_name[n].append((s, e))
    others = {n: _union(iv) for n, iv in by_name.items()}

    busy = []
    op_time: Dict[str, float] = collections.Counter()
    gap_time: Dict[str, float] = collections.Counter()
    for evs in per_device:
        iv = _union(_clip([(s, e) for _, s, e in evs], lo, hi))
        busy.append(sum(e - s for s, e in iv))
        for name, self_s in _self_times(_clip_events(evs, lo, hi)):
            op_time[name] += self_s / len(per_device)
        edges = [lo] + [x for se in iv for x in se] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gap_time[_label(others, g0, g1)] += (g1 - g0) / len(per_device)
    return {"busy_s": sum(busy) / len(busy), "window_s": window_s,
            "devices": len(per_device),
            "device_ops": _top(op_time, top),
            "idle_gaps": _top(gap_time, top)}


def _clip_events(evs, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs if e > lo and s < hi]


def _self_times(evs):
    """(name, seconds not covered by operations nested inside it): a loop
    or call on the ops line encloses the operations of its body."""
    out = []
    stack: List[List] = []              # [name, end, self seconds]
    for name, s, e in sorted(evs, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    out.extend((n, t) for n, _, t in stack)
    return out


def _overlap(iv: List[Tuple[float, float]], g0: float, g1: float) -> float:
    """Overlap of disjoint sorted intervals ``iv`` with (g0, g1)."""
    i = max(0, bisect.bisect_right(iv, (g0, g0)) - 1)
    total = 0.0
    while i < len(iv) and iv[i][0] < g1:
        total += max(0.0, min(iv[i][1], g1) - max(iv[i][0], g0))
        i += 1
    return total


def _label(spans: Dict[str, List[Tuple[float, float]]], g0, g1) -> str:
    best, best_ov = "untraced", 0.0
    for name, iv in spans.items():
        ov = _overlap(iv, g0, g1)
        if ov > best_ov:
            best, best_ov = name, ov
    return best


def _top(counter: Dict[str, float], n: int) -> List[List]:
    return [[k, v] for k, v in sorted(counter.items(), key=lambda kv: -kv[1])
            [:n]]
