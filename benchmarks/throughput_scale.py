"""Throughput/scale benchmark for the runtime substrate: Fig-5-style
null-task campaigns at 10k/100k tasks and at the paper's largest run
(Fig. 5 at 1,024 nodes: ``CAL.tasks_for_nodes(1024)`` = 229,376 tasks),
measuring the *harness* (wall time, sim-events/s, tasks/s, peak RSS)
rather than the simulated system. Every wall time here is a host-CPU
record of the sim engine, not a device measurement.

This seeds the BENCH perf trajectory: every run writes ``BENCH_runtime.json``
so CI can track sim throughput across PRs. The paper's characterization
methodology (Merzky et al. SC-W'25 §4.1; RADICAL-Pilot characterization,
arXiv:2103.00091) runs 10^5-10^6 null tasks to measure runtime overheads —
this benchmark makes sure our simulator can replay campaigns at that scale
without itself becoming the bottleneck.

Usage:
    PYTHONPATH=src python benchmarks/throughput_scale.py            # 10k/100k/229,376
    PYTHONPATH=src python benchmarks/throughput_scale.py --quick    # CI: 10k + 229,376
    PYTHONPATH=src python benchmarks/throughput_scale.py --scales 100000
"""
from __future__ import annotations

import argparse
import json
import resource
import sys
import time
from typing import Dict, List

from repro.core import calibration as CAL
from repro.core.analytics import (compute_metrics, concurrency_series,
                                  occupancy_utilization)
from repro.core.pilot import PilotDescription
from repro.core.task import DescriptionBatch, TaskDescription
from repro.observability import RunReport
from repro.runtime import PilotManager, Session, TaskManager

PAPER_SCALE = CAL.tasks_for_nodes(1024)     # Fig. 5's largest run
DEFAULT_SCALES = (10_000, 100_000, PAPER_SCALE)
NODES = 64


def _peak_rss_mb() -> float:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_maxrss / 1024.0          # linux reports KiB


def run_campaign(n_tasks: int, hybrid: bool, seed: int = 0) -> Dict:
    """One end-to-end Fig-5-style run: build descriptions, submit through
    the Session facade, drain, compute metrics. Returns the measurement.

    At >=1M tasks the non-hybrid config builds a columnar
    ``DescriptionBatch.from_template`` payload (one shared template, O(1)
    description memory per task) instead of a list of description
    objects, so the large tiers measure the batch submission path and do
    not spend gigabytes — or noisy seconds — on object construction.
    The sub-1M tiers keep the object-list path covered."""
    t0 = time.time()
    if hybrid:
        # Fig 5d: mixed executable+function load over flux+dragon
        backends = {"flux": {"partitions": 8, "nodes": NODES // 2},
                    "dragon": {"partitions": 8, "nodes": NODES // 2}}
    else:
        backends = {"flux": {"partitions": 8}}
    with Session(mode="sim", seed=seed) as session:
        pilot = PilotManager(session).submit_pilots(
            PilotDescription(nodes=NODES, backends=backends))
        tmgr = TaskManager(session)
        tmgr.add_pilots(pilot)
        build0 = time.perf_counter()
        if not hybrid and n_tasks >= 1_000_000:
            # all-scalar columnar batch: O(1) description memory per task
            payload = DescriptionBatch.from_template(
                TaskDescription(cores=1, duration=0.0), n_tasks)
        elif hybrid:
            payload = [TaskDescription(cores=1, duration=0.0,
                                       kind="function" if i % 2
                                       else "executable")
                       for i in range(n_tasks)]
        else:
            payload = [TaskDescription(cores=1, duration=0.0)
                       for _ in range(n_tasks)]
        desc_build_s = time.perf_counter() - build0
        submit0 = time.perf_counter()
        tmgr.submit_tasks(payload)
        submit_s = time.perf_counter() - submit0
        tmgr.wait_tasks()
        agent = pilot.agent
        engine = session.engine
        tasks = agent.all_tasks()
        m = compute_metrics(tasks, agent.total_cores)
        series = concurrency_series(tasks)
        # null tasks have zero execution time, so the §4 RUNNING->DONE
        # utilization is degenerately 0; report allocation occupancy
        # (LAUNCHING->DONE), which the launch pipeline actually sustains
        occ = occupancy_utilization(tasks, agent.total_cores)
        wall = time.time() - t0
        return {
            "config": "flux+dragon hybrid" if hybrid else "flux x8",
            "n_tasks": n_tasks,
            "wall_s": round(wall, 3),
            "tasks_per_s": round(n_tasks / wall),
            # description build + submit-call cost, so the trajectory
            # tracks whether the description layer (not the state
            # machine) dominates: desc_build_s is pure construction,
            # submit_calls_per_s is n over the submit_tasks call wall
            # (routing and SCHEDULING stamps included)
            "desc_build_s": round(desc_build_s, 3),
            "submit_s": round(submit_s, 3),
            "submit_calls_per_s": round(n_tasks / max(submit_s, 1e-9)),
            "sim_events": engine.events_fired,
            "sim_events_per_s": round(engine.events_fired / wall),
            "trace_events": len(session.profiler),
            "peak_rss_mb": round(_peak_rss_mb(), 1),
            "sim_throughput_avg": round(m.throughput_avg, 1),
            "sim_utilization": round(occ, 4),
            "concurrency_samples": len(series),
        }


def main(argv: List[str] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI tier: 10k smoke + the paper-scale (229,376) "
                         "regression gate")
    ap.add_argument("--scales", type=int, nargs="+", default=None,
                    help="explicit task counts")
    ap.add_argument("--hybrid", action="store_true",
                    help="flux+dragon mixed-modality config (Fig 5d)")
    ap.add_argument("--max-rss-mb", type=float, default=4096.0,
                    help="fail if peak RSS exceeds this")
    ap.add_argument("--no-regress-check", action="store_true",
                    help="skip the wall-time comparison against the "
                         "committed baseline in --output")
    ap.add_argument("--output", default="BENCH_runtime.json")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    scales = (args.scales if args.scales
              else ((10_000, PAPER_SCALE) if args.quick else DEFAULT_SCALES))
    # the committed results are the regression baseline: read them before
    # overwriting, keep them as *_prev columns in the new payload
    baseline: Dict = {}
    try:
        with open(args.output) as f:
            for b in json.load(f).get("results", []):
                baseline[(b["config"], b["n_tasks"])] = b
    except (OSError, ValueError, KeyError):
        pass
    failures: List[str] = []
    results = []
    for n in scales:
        r = run_campaign(n, hybrid=args.hybrid, seed=args.seed)
        prev = baseline.get((r["config"], r["n_tasks"]))
        if prev is not None:
            for k in ("wall_s", "tasks_per_s", "peak_rss_mb",
                      "sim_events_per_s", "desc_build_s",
                      "submit_calls_per_s"):
                if k in prev:
                    r[k + "_prev"] = prev[k]
            # enforce at the run's largest scale only, where the wall is
            # long enough for a 10% band to mean something; smaller tiers
            # are noise-dominated but still report their *_prev columns
            if (not args.no_regress_check and n == max(scales)
                    and r["wall_s"] > 1.10 * prev["wall_s"]):
                failures.append(
                    f"wall-time regression at n={n:,}: {r['wall_s']:.2f}s "
                    f"vs baseline {prev['wall_s']:.2f}s (>10%)")
        if r["peak_rss_mb"] > args.max_rss_mb:
            failures.append(
                f"peak RSS {r['peak_rss_mb']:.0f}MB exceeds "
                f"{args.max_rss_mb:.0f}MB at n={n:,}")
        results.append(r)
        print(f"{r['config']:>20}  n={n:>9,}  wall={r['wall_s']:>8.2f}s  "
              f"tasks/s={r['tasks_per_s']:>7,}  "
              f"sim-events/s={r['sim_events_per_s']:>8,}  "
              f"rss={r['peak_rss_mb']:.0f}MB", flush=True)

    # merge: tiers not re-measured by this invocation keep their committed
    # rows, so the CI quick lane doesn't clobber the 100k row
    measured = {(r["config"], r["n_tasks"]) for r in results}
    results = results + [b for key, b in baseline.items()
                         if key not in measured]
    results.sort(key=lambda r: (r["config"], r["n_tasks"]))

    RunReport(extra={
        "benchmark": "throughput_scale",
        "protocol": ("end-to-end per scale: build TaskDescriptions, submit "
                     "via Session/TaskManager, drain the sim engine, "
                     "compute_metrics + concurrency_series; fresh Session "
                     "per scale, single process"),
        "clock": "host CPU wall time of the sim engine, not a device metric",
        "nodes": NODES,
        "seed": args.seed,
    }, results=results).save(args.output)
    print(f"wrote {args.output}")
    if failures:
        for msg in failures:
            print(f"FAIL: {msg}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
