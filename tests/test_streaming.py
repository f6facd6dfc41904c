"""Streaming telemetry tests: the TraceCursor exactly-once/O(delta)
contract, the golden guarantee that streamed aggregates equal the post-hoc
reconstruction at drain (both engines, list and batch submission), edge-triggered
health alerts (stall, service p99 SLO breach under replica kill), the
watch CLI emit/follow round-trip, the Perfetto instants/service slices,
and the group-aware cross-run diff."""
import copy
import json

import numpy as np
import pytest

from repro.core.agent import Agent, SimEngine
from repro.core.events import Profiler
from repro.core.pilot import PilotDescription
from repro.core.task import DescriptionBatch, TaskDescription
from repro.observability import (PHASES, RunReport, chrome_trace,
                                 lifecycle_breakdown)
from repro.observability.__main__ import main as obs_main
from repro.observability.report import diff_payloads
from repro.observability.stream import (ALERT_EVENT, ServiceLatencyRule,
                                        StallRule, TraceCursor, Watcher)
from repro.observability.timeseries import inflight, occupancy, throughput
from repro.runtime.session import PilotManager, Session, TaskManager
from repro.services.service import Service

REL = 1e-9


# --------------------------------------------------------------------------
# cursor
# --------------------------------------------------------------------------

def test_cursor_exactly_once_and_o_delta():
    """Each poll returns exactly the rows appended since the previous
    poll — no row twice, no row skipped — and reports new names once."""
    prof = Profiler()
    cur = TraceCursor(prof)
    d = cur.poll()
    assert d.n == 0 and d.lo == d.hi == 0

    prof.record(1.0, "t.0", "task:run")
    prof.record(2.0, "t.1", "task:run")
    d = cur.poll()
    assert (d.lo, d.hi, d.n) == (0, 2, 2)
    assert np.array_equal(d.times, [1.0, 2.0])
    assert dict(d.new_names)[cur.profiler.nid_of("task:run")] == "task:run"

    assert cur.poll().n == 0                      # idempotent when quiet

    prof.record(3.0, "t.0", "task:done")
    d = cur.poll()
    assert (d.lo, d.hi, d.n) == (2, 3, 1)
    names = dict(d.new_names)
    assert list(names.values()) == ["task:done"]  # only the new name

    total = 0
    cur2 = TraceCursor(prof)
    while True:
        d = cur2.poll()
        if d.n == 0:
            break
        total += d.n
    assert total == prof.n_rows


# --------------------------------------------------------------------------
# golden: streamed == post-hoc at drain
# --------------------------------------------------------------------------

def _watched_run(n=400, duration=0.25, as_batch=False, mode="sim", seed=7):
    with Session(mode=mode, seed=seed) as session:
        pilot = PilotManager(session).submit_pilots(
            PilotDescription(nodes=16,
                             backends={"flux": {"partitions": 4}}))
        tm = TaskManager(session)
        tm.add_pilots(pilot)
        w = tm.watch(interval=1.0)
        if mode == "real":
            descs = [TaskDescription(kind="function", fn=lambda: 1)
                     for _ in range(n)]
        else:
            descs = [TaskDescription(cores=1, duration=duration)
                     for _ in range(n)]
        tm.submit_tasks(DescriptionBatch.from_descriptions(descs)
                        if as_batch else descs)
        assert tm.wait_tasks(timeout=120)
        w.finalize()
        # session close records a few shutdown rows after this returns, so
        # capture the row count the watcher was accountable for now
        assert w.n_rows_folded == session.profiler.n_rows
        agent = pilot.agent
        return (w, agent.all_tasks(), agent.total_cores, session.profiler)


def _assert_golden(w, tasks, cores, prof, levels=True):
    """The streamed aggregates must equal the post-hoc reconstruction of
    the same trace bit-for-bit (counts) / to 1e-9 (sums).  ``levels=False``
    skips the inflight/occupancy comparison: under retries the stream
    counts every killed attempt's real core occupancy while the post-hoc
    reconstruction only sees the final RUNNING span (documented
    divergence)."""
    th = w.throughput.series()
    ref = throughput(prof, tasks, dt=w.dt)
    assert np.array_equal(th.t, ref.t) and np.array_equal(th.v, ref.v)

    if levels:
        inf = w.inflight.series()
        ref = inflight(tasks, dt=w.dt)
        assert np.array_equal(inf.t, ref.t)
        assert np.array_equal(inf.v, ref.v)

        occ = w.occupancy_series()
        ref = occupancy(tasks, cores, dt=w.dt)
        assert np.array_equal(occ.t, ref.t)
        assert np.array_equal(occ.v, ref.v)

    st = w.breakdown.stats(exact_quantiles=True)
    post = lifecycle_breakdown(tasks, prof).total.as_dict()
    assert st["n"] == post["n"]
    assert st["span_sum"] == pytest.approx(post["span_sum"], rel=REL)
    for p in PHASES:
        sp, pp = st["phases"][p], post["phases"][p]
        assert sp["n"] == pp["n"]
        assert sp["sum"] == pytest.approx(pp["sum"], rel=REL, abs=1e-12)
        # same multiset of durations -> identical order statistics
        assert sp["p50"] == pp["p50"]
        assert sp["p99"] == pp["p99"]
        assert sp["max"] == pp["max"]


@pytest.mark.parametrize("as_batch", [False, True],
                         ids=["objects", "batch"])
def test_streamed_equals_posthoc_sim(as_batch):
    w, tasks, cores, prof = _watched_run(as_batch=as_batch)
    assert w.n_ticks > 0
    _assert_golden(w, tasks, cores, prof)


def test_streamed_equals_posthoc_real():
    w, tasks, cores, prof = _watched_run(n=120, mode="real")
    _assert_golden(w, tasks, cores, prof)


def test_streamed_survives_retries():
    """Walltime kills with checkpoint-banked progress retry to DONE: the
    streamed join must still match post-hoc exactly (retried lifecycles
    use first-wins sched/queued stamps)."""
    eng = SimEngine(seed=3)
    agent = Agent(eng, 8, {"flux": {"partitions": 2}})
    agent.start()
    w = Watcher(agent, interval=1.0).start()
    descs = [TaskDescription(cores=1, duration=2.0) for _ in range(20)]
    descs += [TaskDescription(cores=1, duration=30.0, walltime=12.0,
                              max_retries=3, checkpoint_period=5.0,
                              checkpoint_dir=f"ckpt://t{i}")
              for i in range(3)]
    tasks = agent.submit(descs)
    agent.run_until_complete()
    w.finalize()
    assert all(t.state.name == "DONE" for t in tasks)
    assert any(t.retries for t in tasks), "no retry was exercised"
    _assert_golden(w, tasks, agent.total_cores, eng.profiler,
                   levels=False)


# --------------------------------------------------------------------------
# health rules
# --------------------------------------------------------------------------

def test_stall_alert_fires_exactly_once():
    """A ~48s completion gap with work outstanding raises one stall alert
    (edge-triggered — one alert, not one per tick in breach), recorded as
    an obs:alert trace row.  The window is wider than pilot warmup so
    only the long-task gap breaches."""
    with Session(mode="sim", seed=0) as session:
        pilot = PilotManager(session).submit_pilots(
            PilotDescription(nodes=8,
                             backends={"flux": {"partitions": 2}}))
        tm = TaskManager(session)
        tm.add_pilots(pilot)
        w = tm.watch(interval=1.0, rules=[StallRule(window=30.0)])
        descs = [TaskDescription(cores=1, duration=0.5)
                 for _ in range(20)]
        descs.append(TaskDescription(cores=1, duration=50.0))
        tm.submit_tasks(descs)
        assert tm.wait_tasks(timeout=120)
        w.finalize()
        stalls = [a for a in w.monitor.alerts if a.rule == "stall"]
        assert len(stalls) == 1
        prof = session.profiler
        assert len(prof.rows_np(ALERT_EVENT)) == 1
        (ev,) = list(prof.iter_name(ALERT_EVENT))
        assert ev.data["rule"] == "stall"


def test_service_p99_breach_fires_exactly_once():
    """Killing a replica mid-stream dumps its queue onto the survivor;
    the rolling p99 crosses the SLO once and the alert edge-triggers."""
    eng = SimEngine(seed=0)
    agent = Agent(eng, 8, {"flux": {"partitions": 2}})
    agent.start()
    svc = Service(agent, replicas=2, nodes=1, rate=1.0, max_retries=3,
                  name="infer")
    svc.submit()
    rule = ServiceLatencyRule(svc, slo_p99=2.0, min_requests=8)
    w = Watcher(agent, interval=1.0, rules=[rule]).start()
    svc.submit_requests(range(40))
    svc.stop()
    eng.schedule(5.0, svc.kill_replica)
    agent.run_until_complete()
    w.finalize()
    breaches = [a for a in w.monitor.alerts if a.rule == "service_p99"]
    assert len(breaches) == 1
    assert "infer" in breaches[0].message


# --------------------------------------------------------------------------
# watch CLI: emit -> follow round-trip
# --------------------------------------------------------------------------

def test_watch_cli_emit_and_follow(tmp_path, capsys):
    emit = str(tmp_path / "metrics.jsonl")
    prom = str(tmp_path / "metrics.prom")
    rc = obs_main(["watch", "--tasks", "80", "--duration", "0.25",
                   "--no-clear", "--emit", emit, "--promfile", prom])
    assert rc == 0
    records = [json.loads(l) for l in open(emit) if l.strip()]
    assert records and records[-1]["final"]
    assert records[-1]["n_done"] == 80
    ticks = [r["tick"] for r in records]
    assert ticks == sorted(ticks)
    assert "repro_n_done 80" in open(prom).read()
    capsys.readouterr()

    rc = obs_main(["watch", "--follow", emit, "--no-wait", "--no-clear"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "final" in out and "80" in out


# --------------------------------------------------------------------------
# perfetto: service slices + instant markers
# --------------------------------------------------------------------------

def test_chrome_trace_service_slices_and_alert_instants():
    eng = SimEngine(seed=0)
    agent = Agent(eng, 8, {"flux": {"partitions": 2}})
    agent.start()
    w = Watcher(agent, interval=1.0, rules=[StallRule(window=5.0)]).start()
    svc = Service(agent, replicas=2, rate=5.0, name="infer")
    svc.submit()
    svc.submit_requests(range(30))
    svc.stop()
    agent.submit([TaskDescription(cores=1, duration=1.0)
                  for _ in range(40)])
    agent.run_until_complete()
    w.finalize()
    tasks = agent.all_tasks()
    doc = chrome_trace(tasks, eng.profiler, total_cores=agent.total_cores,
                       services=[svc])
    procs = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert "service:infer" in procs
    req = [e for e in doc["traceEvents"]
           if e["ph"] == "X" and e["name"].startswith("req.")]
    assert len(req) == 30
    instants = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert doc["otherData"]["n_instants"] == len(instants)
    if w.monitor.alerts:
        assert any(e["name"] == ALERT_EVENT and e["cat"] == "alert"
                   for e in instants)


def test_chrome_trace_cap_includes_service_slices():
    """The global max_slices cap spans service segments too, and the
    dropped count stays non-silent."""
    eng = SimEngine(seed=0)
    agent = Agent(eng, 8, {"flux": {"partitions": 2}})
    agent.start()
    svc = Service(agent, replicas=2, rate=20.0, name="infer")
    svc.submit()
    svc.submit_requests(range(60))
    svc.stop()
    agent.submit([TaskDescription(cores=1, duration=1.0)
                  for _ in range(40)])
    agent.run_until_complete()
    doc = chrome_trace(agent.all_tasks(), eng.profiler,
                       total_cores=agent.total_cores, services=[svc],
                       max_slices=50)
    x = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(x) <= 50
    assert doc["otherData"]["n_slices_dropped"] == 100 - len(x)


# --------------------------------------------------------------------------
# cross-run diff: overlapping groups only, added/removed listed
# --------------------------------------------------------------------------

def _report_payload():
    eng = SimEngine(seed=1)
    agent = Agent(eng, 8, {"flux": {"partitions": 2}})
    agent.start()
    agent.submit([TaskDescription(cores=1, duration=1.0)
                  for _ in range(40)])
    agent.run_until_complete()
    return RunReport.collect(agent.all_tasks(), agent.total_cores,
                             profiler=eng.profiler).to_json()


def test_diff_lists_added_and_removed_groups():
    base = _report_payload()
    cand = copy.deepcopy(base)
    g = cand["breakdown"]["groups"]
    k = sorted(g)[0]
    g["renamed:" + k] = g.pop(k)
    lines, viols = diff_payloads(base, cand, tolerance=0.1)
    out = "\n".join(lines)
    assert f"groups added:   renamed:{k}" in out
    assert f"groups removed: {k}" in out
    assert not viols                       # disjoint groups never compared


def test_diff_flags_overlapping_group_regression():
    base = _report_payload()
    cand = copy.deepcopy(base)
    (k, grp), = list(cand["breakdown"]["groups"].items())[:1] or [(None, None)]
    grp["phases"]["exec"]["mean"] *= 2.0
    lines, viols = diff_payloads(base, cand, tolerance=0.1)
    out = "\n".join(lines)
    assert any(k in v for v in viols)
    assert "groups added" not in out and "groups removed" not in out
