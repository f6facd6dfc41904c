#!/usr/bin/env python3
"""One traced run of a cell, in this process, that also reads the device's
idle time under each of the runtime's ``rp:*`` host spans
(``harness/spans.py``) before the run removes its trace.

    python3 benchmarks/onchip/tools/spans.py --workload stream.short \
        --seed 11 --seconds 51

Prints the run's own result line, then one JSON line: ``program_idle``
(seconds per span name), each as a share of the traced window's idle
seconds, the two sides of ``lock_wait_ms`` apart (``picked`` -> RUNNING and
``ready`` -> DONE), and the tasks per second completed in the traced span
against the window's (what the profiler costs while it records).
``--python-tracer 0`` takes the profile without the profiler's Python
tracer (``ProfileOptions.python_tracer_level``; the harness's traced runs
keep JAX's default, 1): the ``rp:`` and ``bench:`` spans are recorded
either way.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import io  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--python-tracer", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if args.python_tracer == 0:
        # the harness starts its trace with jax.profiler.start_trace(path)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        start_trace = jax.profiler.start_trace
        jax.profiler.start_trace = functools.partial(
            start_trace, profiler_options=options)
    from harness.cell import TRACE_SECONDS, execute
    from harness.readers import counted
    from harness.stamps import interval_mean_ms
    from harness.spans import program_idle
    from harness.trace import CPU, TPU

    kept = {}

    def keep(run):
        reduce = run.reduce_trace

        def read_then_reduce():
            if run._trace_dir is not None:
                which = TPU if run.device["platform"] == "tpu" else CPU
                kept["program_idle"] = program_idle(run._trace_dir, which)
                kept["trace_t0"] = run._trace_t0
            reduce()
        run.reduce_trace = read_then_reduce
        kept["run"] = run

    out = io.StringIO()
    rc = execute(args.workload, args.seed, args.seconds, True,
                 t_process=T_PROCESS, out=out, tweak=keep)
    line = out.getvalue().strip().splitlines()[-1] if out.getvalue() else ""
    print(line, flush=True)
    if rc or not line:
        return rc or 1
    res = json.loads(line)
    run = kept["run"]
    dev = res["device"]
    idle_s = dev.get("window_s", 0.0) - dev.get("busy_s", 0.0)
    pi = kept.get("program_idle") or {}
    t0 = kept.get("trace_t0")
    traced = (None if t0 is None else sum(
        1 for r in run.tasks if r["state"] == "DONE" and r["seen_t"]
        is not None and t0 <= r["seen_t"] < t0 + TRACE_SECONDS)
        / TRACE_SECONDS)
    window = len(counted(run)) / run.window_s
    print(json.dumps({
        "program_idle": pi, "idle_s": idle_s,
        "idle_share_pct": {n: 100.0 * v / idle_s for n, v in pi.items()}
        if idle_s > 0 else {},
        "start_lock_ms": interval_mean_ms(run, None, ("picked", "RUNNING")),
        "commit_lock_ms": interval_mean_ms(run, None, ("ready", "DONE")),
        "traced_tasks_per_s": traced, "window_tasks_per_s": window,
        "python_tracer": args.python_tracer}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
