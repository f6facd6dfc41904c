"""One run of one cell: set-up, the measured window, the traced part of it,
the comparison that decides ``correct``, and the result line.

The traffic mix names its driver (``drivers/<kind>.py``), which builds the
runtime from the configuration, warms every shape, drives the window
through ``Session(mode="real")`` / ``TaskManager`` and keeps what the
metric readers (``metrics/<name>.py``) and the checks
(``checks/<payload>.py``) read.
"""
from __future__ import annotations

import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

from . import device as D
from . import spec as S
from .seeds import derive
from .trace import WINDOW

TRACE_SECONDS = 3.0       # the closed loop's traced span


class Run:
    """What one run has learned; drivers, checks and readers share it."""

    def __init__(self, cell: S.Cell, seed: int, seconds: float, trace: bool,
                 devices, t_process: float):
        from .compile_meter import CompileMeter
        self.cell = cell
        self.seed = seed
        self.seconds = float(seconds)
        self.trace = trace
        self.devices = devices
        self.chips = len(devices)
        self.device = D.describe(devices)
        self.t_process = t_process
        self.meter = CompileMeter()
        self.models: Dict[str, Any] = {}
        self.payloads: Dict[str, Any] = {}      # by payload kind
        self.tasks: List[Dict[str, Any]] = []     # one record per task
        self.iterations: List[Dict[str, Any]] = []
        self.window: Optional[tuple] = None       # (t0, t1) host clock
        self.setup_s: Optional[float] = None
        self.compile_in_window: Optional[Dict[str, float]] = None
        self.checks: List[tuple] = []             # (name, value, limit)
        self.problems: List[str] = []
        self.trace_summary: Optional[Dict] = None
        self._trace_dir: Optional[str] = None
        self._trace_span = None
        self._compile0 = None

    # ------------------------------------------------------------ inputs
    def model(self, name: str):
        if name not in self.models:
            from .models import Model
            self.models[name] = Model(name, self.cell.config["models"][name])
        return self.models[name]

    def derive(self, *tags: int) -> int:
        return derive(self.seed, *tags)

    # ------------------------------------------------------------ window
    def open_window(self):
        """Set-up is over: from here on the host clock measures the cell."""
        t0 = time.perf_counter()
        self.setup_s = t0 - self.t_process
        self._compile0 = self.meter.snapshot()
        self.window = (t0, None)
        return t0

    def close_window(self, t1: float):
        self.window = (self.window[0], t1)
        c0, c1 = self._compile0, self.meter.snapshot()
        self.compile_in_window = {k: c1[k] - c0[k] for k in c1}

    def start_trace(self):
        """With ``--trace 1``: profile the traffic that follows the closed
        window, so the window's own
        numbers are not disturbed by the profiler. Returns whether it did."""
        if not self.trace:
            return False
        import jax
        self._trace_dir = tempfile.mkdtemp(prefix="onchip_trace_")
        jax.profiler.start_trace(self._trace_dir)
        self._trace_span = jax.profiler.TraceAnnotation(WINDOW)
        self._trace_span.__enter__()
        self._trace_t0 = time.perf_counter()
        return True

    def trace_due(self) -> bool:
        """Whether the traced span should end now."""
        return (self._trace_span is not None and time.perf_counter()
                - self._trace_t0 >= TRACE_SECONDS)

    def stop_trace(self):
        if self._trace_span is not None:
            import jax
            self._trace_span.__exit__(None, None, None)
            self._trace_span = None
            jax.profiler.stop_trace()

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def reduce_trace(self):
        if self._trace_dir is None:
            return
        from .trace import CPU, TPU, reduce_trace
        which = TPU if self.device["platform"] == "tpu" else CPU
        try:
            self.trace_summary = reduce_trace(self._trace_dir, which)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None

    # ------------------------------------------------------------ checks
    def compare(self, name: str, value: float, limit: float):
        self.checks.append((name, float(value), float(limit)))

    def problem(self, what: str):
        self.problems.append(what)

    @property
    def correct(self) -> bool:
        return not self.problems and bool(self.checks) and all(
            v <= lim for _, v, lim in self.checks)


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            t_process: float, require_tpu: bool = True,
            bench: Optional[Dict] = None, root: Path = S.BENCH_DIR,
            out=sys.stdout, err=sys.stderr, tweak=None) -> int:
    """Run one cell once and print its result line. Returns the exit code.

    ``tweak(run)``, where given, runs after the cell's parts are loaded and
    before set-up (tests plant faults or shrink sizes through it)."""
    bench = bench if bench is not None else S.load_benchmark()
    cell = S.Cell(bench, workload, root)
    try:
        devices = D.claim(cell.chips, require_tpu=require_tpu)
    except D.DeviceError as e:
        print(f"onchip: {e}", file=err)
        return 2
    if require_tpu:
        D.peak(devices[0].device_kind)          # unknown kinds are refused
    run = Run(cell, seed, seconds, trace, devices, t_process)
    driver = S.load_module("drivers", cell.traffic["kind"], root)
    if tweak is not None:
        tweak(run)
    state = driver.setup(run)
    driver.window(run, state)
    run.device["memory_peak_bytes"] = D.memory_peak_bytes(devices)
    driver.teardown(run, state)
    del state
    gc.collect()
    run.reduce_trace()
    from . import runtime_check
    runtime_check.check(run)
    for payload in sorted({t["payload"] for t in run.tasks}):
        S.load_module("checks", payload, root).check(run)
    metrics = {}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]
             + bench["per_layer"]}
    for m in cell.metrics(trace):
        value = cell.reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": units[m["name"]]}
    result: Dict[str, Any] = {
        "correct": run.correct,
        "attempted": sum(1 for t in run.tasks if t["in_window"]),
        "failed": sum(1 for t in run.tasks if t["in_window"]
                      and t["state"] != "DONE"),
        "metrics": metrics, "device": dict(run.device)}
    if trace:
        ts = run.trace_summary or {}
        result["device"]["busy_s"] = ts.get("busy_s", 0.0)
        result["device"]["window_s"] = ts.get("window_s", 0.0)
        if ts:
            result["breakdown"] = {"device_ops": ts["device_ops"],
                                   "idle_gaps": ts["idle_gaps"]}
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in run.checks}
    for p in run.problems:
        print(f"onchip: not correct: {p}", file=err)
    for n, v, lim in run.checks:
        print(f"check {n}: {v!r} limit {lim!r}"
              f"{'' if v <= lim else '  FAILS'}", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
    return 0
