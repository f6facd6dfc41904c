"""Observability overhead benchmark: what does watching the run cost?

Two passes per scale over the same seeded null campaign (the
throughput_scale flux-x8 configuration, whose committed wall time in
``BENCH_runtime.json`` is the regression baseline; the largest default
scale is the paper's Fig. 5 run at 1,024 nodes, 229,376 tasks). Every
wall time here is a host-CPU record, not a device measurement:

* **off** — campaign only, nothing derived after the drain;
* **on**  — campaign with a gauge-only Watcher attached (trace recording is
  always on), then the full post-hoc stack: RunReport.collect (all
  metric families + lifecycle breakdown + reconstructed timeseries)
  plus a capped Chrome trace export, each stage timed.
* **stream** (``--stream``) — campaign with a full streaming Watcher
  attached: every tick folds the trace delta into the live aggregators
  (throughput/inflight/occupancy levels + lifecycle breakdown) and runs
  the health rules. The streamed campaign wall is held to the same 10%
  band, and the per-tick fold cost is reported.

Gates (exit nonzero on miss):

* the *observed campaign* wall (drain with live sampling active) <=
  1.10 x the committed BENCH_runtime.json wall for the same
  (config, n_tasks) tier — watching the run live must fit inside the
  same 10% band the campaign itself is held to;
* with ``--stream``, the *streamed campaign* wall (full Watcher folding
  every tick) is held to the same 1.10x band;
* post-hoc analysis (RunReport.collect) < 2s.

All three gates apply at the run's largest scale.

Usage:
    PYTHONPATH=src python benchmarks/observability_overhead.py          # 10k + 229,376
    PYTHONPATH=src python benchmarks/observability_overhead.py --quick  # CI: same
    PYTHONPATH=src python benchmarks/observability_overhead.py --scales 10000
    PYTHONPATH=src python benchmarks/observability_overhead.py --stream
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from typing import Dict, List, Optional

from repro.core import calibration as CAL
from repro.core.pilot import PilotDescription
from repro.core.task import DescriptionBatch, TaskDescription
from repro.observability import RunReport, Watcher, export_chrome_trace
from repro.runtime import PilotManager, Session, TaskManager

DEFAULT_SCALES = (10_000, CAL.tasks_for_nodes(1024))
NODES = 64
ANALYSIS_GATE_S = 2.0
WALL_BAND = 1.10


def run_campaign(n_tasks: int, seed: int, observe: bool) -> Dict:
    """One flux-x8 null campaign (throughput_scale protocol); with
    ``observe`` a gauge-only Watcher rides the drain and the full post-hoc
    stack runs afterwards, every stage timed individually."""
    t0 = time.time()
    with Session(mode="sim", seed=seed) as session:
        pilot = PilotManager(session).submit_pilots(
            PilotDescription(nodes=NODES,
                             backends={"flux": {"partitions": 8}}))
        tmgr = TaskManager(session)
        tmgr.add_pilots(pilot)
        # same payload protocol as throughput_scale: the >=1M tiers go
        # through the columnar batch path, smaller tiers the object list
        if n_tasks >= 1_000_000:
            payload = DescriptionBatch.from_template(
                TaskDescription(cores=1, duration=0.0), n_tasks)
        else:
            payload = [TaskDescription(cores=1, duration=0.0)
                       for _ in range(n_tasks)]
        tmgr.submit_tasks(payload)
        sampler = None
        if observe:
            sampler = Watcher(pilot.agent, interval=1.0,
                              aggregate=False).start()
        tmgr.wait_tasks()
        campaign_wall = time.time() - t0
        out: Dict = {"config": "flux x8", "n_tasks": n_tasks,
                     "campaign_wall_s": round(campaign_wall, 3)}
        if not observe:
            out["wall_s"] = round(campaign_wall, 3)
            return out
        out["live_samples"] = len(sampler.samples)
        agent = pilot.agent
        tasks = agent.all_tasks()
        t1 = time.time()
        report = RunReport.collect(tasks, agent.total_cores,
                                   profiler=session.profiler)
        analysis_s = time.time() - t1
        t2 = time.time()
        fd, trace_path = tempfile.mkstemp(suffix=".json")
        os.close(fd)
        try:
            summary = export_chrome_trace(trace_path, tasks,
                                          session.profiler,
                                          total_cores=agent.total_cores)
            trace_bytes = os.path.getsize(trace_path)
        finally:
            os.unlink(trace_path)
        export_s = time.time() - t2
        out.update({
            "wall_s": round(time.time() - t0, 3),
            "analysis_wall_s": round(analysis_s, 3),
            "export_wall_s": round(export_s, 3),
            "export_slices": summary["n_slices"],
            "export_slices_dropped": summary["n_slices_dropped"],
            "export_file_bytes": trace_bytes,
            "cost": report.cost,
            "breakdown_exec_share": _exec_share(report),
        })
        return out


def run_streamed(n_tasks: int, seed: int) -> Dict:
    """Same campaign with a full streaming Watcher riding the drain:
    every tick folds the new trace rows into the live aggregators and
    evaluates the health rules, so this wall is the true cost of
    watching with streaming analytics on. At drain the folded totals
    must match the task table exactly (cross-check, not a timing)."""
    t0 = time.time()
    with Session(mode="sim", seed=seed) as session:
        pilot = PilotManager(session).submit_pilots(
            PilotDescription(nodes=NODES,
                             backends={"flux": {"partitions": 8}}))
        tmgr = TaskManager(session)
        tmgr.add_pilots(pilot)
        if n_tasks >= 1_000_000:
            payload = DescriptionBatch.from_template(
                TaskDescription(cores=1, duration=0.0), n_tasks)
        else:
            payload = [TaskDescription(cores=1, duration=0.0)
                       for _ in range(n_tasks)]
        tmgr.submit_tasks(payload)
        watcher = Watcher(pilot.agent, interval=1.0).start()
        tmgr.wait_tasks()
        campaign_wall = time.time() - t0
        watcher.finalize()
        m = watcher.metrics()
        if m["n_done"] != n_tasks:
            raise AssertionError(
                f"streamed fold saw {m['n_done']:,} completions, "
                f"expected {n_tasks:,}")
        ticks = max(watcher.n_ticks, 1)
        return {
            "stream_campaign_wall_s": round(campaign_wall, 3),
            "stream_fold_wall_s": round(watcher.fold_wall_s, 3),
            "stream_fold_per_tick_ms": round(
                1e3 * watcher.fold_wall_s / ticks, 3),
            "stream_ticks": watcher.n_ticks,
            "stream_rows_folded": watcher.n_rows_folded,
            "stream_alerts": len(watcher.monitor.alerts),
        }


def _exec_share(report: RunReport) -> float:
    total = report.breakdown["total"]
    span = total["span_sum"] or 1.0
    return round(total["phases"]["exec"]["sum"] / span, 4)


def _runtime_baseline(path: str) -> Dict:
    """(config, n_tasks) -> wall_s from the committed BENCH_runtime.json."""
    out: Dict = {}
    try:
        with open(path) as f:
            for b in json.load(f).get("results", []):
                out[(b["config"], b["n_tasks"])] = b["wall_s"]
    except (OSError, ValueError, KeyError):
        pass
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI tier (same scales as the default run)")
    ap.add_argument("--scales", type=int, nargs="+", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--runtime-baseline", default="BENCH_runtime.json",
                    help="committed throughput_scale results; the obs-on "
                         "wall must stay within the 10%% band of these")
    ap.add_argument("--stream", action="store_true",
                    help="also run the streaming-Watcher lane per scale "
                         "and gate its campaign wall to the same band")
    ap.add_argument("--no-regress-check", action="store_true")
    ap.add_argument("--output", default="BENCH_observability.json")
    args = ap.parse_args(argv)
    scales = tuple(args.scales) if args.scales else DEFAULT_SCALES

    baseline = _runtime_baseline(args.runtime_baseline)
    failures: List[str] = []
    results: List[Dict] = []
    for n in scales:
        off = run_campaign(n, args.seed, observe=False)
        on = run_campaign(n, args.seed, observe=True)
        r = {**on, "campaign_only_wall_s": off["wall_s"],
             "obs_overhead_s": round(on["wall_s"] - off["wall_s"], 3)}
        if args.stream:
            r.update(run_streamed(n, args.seed))
        gated = n == max(scales)
        base = baseline.get((r["config"], n))
        if base is not None:
            r["runtime_baseline_wall_s"] = base
            if (not args.no_regress_check and gated
                    and r["campaign_wall_s"] > WALL_BAND * base):
                failures.append(
                    f"observed campaign wall at n={n:,}: "
                    f"{r['campaign_wall_s']:.2f}s exceeds "
                    f"{WALL_BAND:.0%} of the committed runtime baseline "
                    f"{base:.2f}s")
            if (args.stream and not args.no_regress_check and gated
                    and r["stream_campaign_wall_s"] > WALL_BAND * base):
                failures.append(
                    f"streamed campaign wall at n={n:,}: "
                    f"{r['stream_campaign_wall_s']:.2f}s exceeds "
                    f"{WALL_BAND:.0%} of the committed runtime baseline "
                    f"{base:.2f}s")
        if gated and r["analysis_wall_s"] > ANALYSIS_GATE_S:
            failures.append(
                f"analysis at n={n:,} took {r['analysis_wall_s']:.2f}s "
                f"(gate {ANALYSIS_GATE_S:.1f}s)")
        results.append(r)
        line = (f"n={n:>9,}  campaign={r['campaign_only_wall_s']:>7.2f}s  "
                f"observed={r['campaign_wall_s']:>7.2f}s  "
                f"analysis={r['analysis_wall_s']:>6.3f}s  "
                f"export={r['export_wall_s']:>6.3f}s  "
                f"events/task={r['cost']['events_per_task']}")
        if args.stream:
            line += (f"  streamed={r['stream_campaign_wall_s']:>7.2f}s "
                     f"(fold {r['stream_fold_per_tick_ms']:.2f}ms/tick "
                     f"x {r['stream_ticks']})")
        print(line, flush=True)

    RunReport(extra={
        "benchmark": "observability_overhead",
        "protocol": ("two passes per scale over the seeded throughput_scale "
                     "flux-x8 null campaign: campaign-only wall vs campaign "
                     "with a gauge-only Watcher + RunReport.collect + capped Chrome "
                     "export; the observed campaign wall is gated to 110% "
                     "of the committed BENCH_runtime wall, post-hoc "
                     "analysis gated to <2s, both at the largest scale; "
                     "--stream adds a third pass with a full streaming "
                     "Watcher (per-tick delta folds + health rules) held "
                     "to the same 110% band"),
        "clock": "host CPU wall time of the sim engine, not a device metric",
        "stream_lane": bool(args.stream),
        "nodes": NODES,
        "seed": args.seed,
        "analysis_gate_s": ANALYSIS_GATE_S,
        "wall_band": WALL_BAND,
    }, results=results).save(args.output)
    print(f"wrote {args.output}")
    if failures:
        for msg in failures:
            print(f"FAIL: {msg}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
