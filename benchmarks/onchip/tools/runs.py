#!/usr/bin/env python3
"""Run cells of the benchmark one process after another, as the check runs
them, keep each run's output, and summarize each metric's spread.

    python3 benchmarks/onchip/tools/runs.py --out runs_out/sets \
        --workload stream.short --seeds 11,12,13 --seconds 10 --trace 0

``--runs w:seed:seconds:trace ...`` gives runs one by one instead. The
spread of a metric is the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) over its median. This process
never imports JAX: each run holds the chip alone.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]


def one(out: Path, w: str, seed: int, seconds: float, trace: int,
        timeout: float) -> dict:
    tag = f"{w}.s{seed}.t{trace}"
    cmd = [sys.executable, "benchmarks/onchip/run.py", "--workload", w,
           "--seed", str(seed), "--seconds", str(seconds), "--trace",
           str(trace)]
    t0 = time.perf_counter()
    try:
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                           timeout=timeout)
        rc, so, se = p.returncode, p.stdout, p.stderr
    except subprocess.TimeoutExpired as e:
        rc, so, se = 124, e.stdout or "", e.stderr or ""
        so = so if isinstance(so, str) else so.decode()
        se = se if isinstance(se, str) else se.decode()
    wall = time.perf_counter() - t0
    (out / f"{tag}.out").write_text(so)
    (out / f"{tag}.err").write_text(se)
    res = None
    lines = so.strip().splitlines()
    if lines:
        try:
            res = json.loads(lines[-1])
        except json.JSONDecodeError:
            pass
    return {"tag": tag, "workload": w, "seed": seed, "trace": trace,
            "rc": rc, "wall_s": wall, "result": res,
            "stderr_tail": se[-1500:] if rc or res is None
            or not res.get("correct") else ""}


def spread(values):
    if len(values) < 2:
        return None
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--workload")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--runs", nargs="*", default=[])
    ap.add_argument("--timeout", type=float, default=1300)
    args = ap.parse_args(argv)
    out = ROOT / args.out
    out.mkdir(parents=True, exist_ok=True)
    plan = [(args.workload, int(s), args.seconds, args.trace)
            for s in args.seeds.split(",") if s]
    for r in args.runs:
        w, s, sec, tr = r.split(":")
        plan.append((w, int(s), float(sec), int(tr)))
    done = []
    for w, s, sec, tr in plan:
        r = one(out, w, s, sec, tr, args.timeout)
        done.append(r)
        res = r["result"] or {}
        print(json.dumps({"tag": r["tag"], "rc": r["rc"],
                          "wall_s": round(r["wall_s"], 3),
                          "correct": res.get("correct"),
                          "metrics": {k: v["value"] for k, v in
                                      res.get("metrics", {}).items()},
                          "checks": {k: v["value"] for k, v in
                                     res.get("checks", {}).items()},
                          "device": res.get("device"),
                          "breakdown": res.get("breakdown")}), flush=True)
        if r["stderr_tail"]:
            print("STDERR-TAIL " + r["stderr_tail"].replace("\n", "\n  "),
                  flush=True)
    by = {}
    for r in done:
        for k, v in ((r["result"] or {}).get("metrics") or {}).items():
            by.setdefault((r["workload"], r["trace"], k), []).append(
                v["value"])
    for (w, tr, k), vals in sorted(by.items()):
        print(f"SUMMARY {w} trace={tr} {k}: n={len(vals)} median="
              f"{statistics.median(vals)!r} spread={spread(vals)!r} "
              f"values={vals!r}")
    (out / "summary.json").write_text(json.dumps(done, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
