"""The runtime's part of ``correct``: every task submitted in the window
reaches a terminal state exactly once, and none fails."""
from __future__ import annotations


def check(run):
    recs = [r for r in run.tasks if r["in_window"]]
    calls = getattr(run, "done_calls", {})
    run.compare("tasks_lost", sum(1 for r in recs if r["state"] == "LOST"), 0)
    run.compare("tasks_finished_twice",
                sum(1 for r in recs if calls.get(r["uid"], 0) > 1), 0)
    failed = [r for r in recs if r["state"] not in ("DONE", "LOST")]
    run.compare("tasks_failed", len(failed), 0)
    for r in failed[:3]:
        run.problem(f"task {r['uid']} {r['state']}: {r.get('error')}")
