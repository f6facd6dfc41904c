"""Observability layer tests: the vectorized profiler name index against a
reference loop (golden), lifecycle decomposition telescoping + reconciliation
with compute_metrics on both engines and both submission forms (description
list vs DescriptionBatch), reconstructed timeseries invariants, Chrome trace export round-trip
(schema + per-track monotonicity + non-silent slice cap), the gauge-only
Watcher's drain guarantee, and the unified RunReport payload/render/CLI surface."""
import json

import numpy as np
import pytest

from repro.core import analytics as A
from repro.core.events import _NAME_MASK, Profiler
from repro.core.pilot import PilotDescription
from repro.core.task import (STATE_EVENTS, DescriptionBatch,
                             TaskDescription, TaskState)
from repro.observability import (PHASES, RunReport, Watcher,
                                 backend_inflight, chrome_trace,
                                 export_chrome_trace, inflight,
                                 lifecycle_breakdown, occupancy,
                                 render_payload, sched_hold_depth,
                                 service_queue_depth, throughput, timeseries)
from repro.observability.__main__ import main as obs_main
from repro.runtime.session import PilotManager, Session, TaskManager

REL = 1e-9


# --------------------------------------------------------------------------
# campaign harness
# --------------------------------------------------------------------------

def _run(n=400, duration=0.25, as_batch=False, hybrid=False, mode="sim",
         seed=7):
    backends = ({"flux": {"nodes": 8, "partitions": 2},
                 "dragon": {"nodes": 8, "partitions": 2}} if hybrid
                else {"flux": {"partitions": 4}})
    with Session(mode=mode, seed=seed) as session:
        pilot = PilotManager(session).submit_pilots(
            PilotDescription(nodes=16, backends=backends))
        tm = TaskManager(session)
        tm.add_pilots(pilot)
        if mode == "real":
            descs = [TaskDescription(kind="function", fn=lambda: 1)
                     for _ in range(n)]
        elif hybrid:
            descs = [TaskDescription(cores=1, duration=duration,
                                     kind="function" if i % 2
                                     else "executable")
                     for i in range(n)]
        else:
            descs = [TaskDescription(cores=1, duration=duration)
                     for _ in range(n)]
        tm.submit_tasks(DescriptionBatch.from_descriptions(descs)
                        if as_batch else descs)
        assert tm.wait_tasks(timeout=120)
        agent = pilot.agent
        return (agent.all_tasks(), agent.total_cores, session.profiler,
                mode)


def _assert_telescopes(bd, tasks, total_cores, profiler, mode="sim"):
    """Phase sums tile submit->done exactly and reconcile with the §4
    metrics derived independently by compute_metrics."""
    total = bd.total
    phase_sum = sum(total.phases[p].sum for p in PHASES)
    assert phase_sum == pytest.approx(total.span_sum, rel=REL)
    for g in bd.groups.values():
        gsum = sum(g.phases[p].sum for p in PHASES)
        assert gsum == pytest.approx(g.span_sum, rel=REL, abs=1e-12)
    m = A.compute_metrics(tasks, total_cores, mode=mode)
    assert bd.n_tasks == m.n_done
    if mode == "sim" and m.makespan > 0 and m.utilization < 1.0:
        # utilization is RUNNING->DONE core-seconds over cores x the
        # execution window (makespan minus bootstrap overhead): exactly
        # the decomposition's exec_core_s, when the 1.0 clamp is inactive
        busy = m.utilization * total_cores * (m.makespan - m.overhead)
        assert total.exec_core_s == pytest.approx(busy, rel=1e-6, abs=1e-6)


# --------------------------------------------------------------------------
# profiler satellites: vectorized name index golden, nid validation,
# numpy accessors
# --------------------------------------------------------------------------

def _reference_index(prof):
    """The seed loop implementation of the by-name index."""
    out = {}
    ids = prof.id_column()
    for row in range(len(ids)):
        out.setdefault(ids[row] & _NAME_MASK, []).append(row)
    return out


def _mixed_trace(seed=0):
    rng = np.random.default_rng(seed)
    prof = Profiler()
    names = [f"ev:{i}" for i in range(7)]
    for i in range(200):
        prof.record(float(i), f"e{i % 13}", names[int(rng.integers(7))])
    nid = prof.name_id("bulk")
    base = prof.reserve_entities(500, lambda i: f"w.{i}")
    prof.record_fast_many(np.arange(500.0) + 200.0,
                          np.arange(base, base + 500), nid)
    return prof, names


def test_name_index_golden_vs_loop():
    prof, names = _mixed_trace()
    ref = _reference_index(prof)
    for name in names + ["bulk"]:
        nid = prof._name_ids[name]
        assert prof.rows_by_name(name) == ref.get(nid, [])


def test_name_index_extends_incrementally():
    prof, names = _mixed_trace()
    before = list(prof.rows_by_name(names[0]))   # builds the index
    eid = prof.entity_id("late")
    nid = prof.name_id(names[0])
    prof.record_fast(999.0, eid, nid)
    prof.record(1000.0, "late", names[1])
    ref = _reference_index(prof)
    assert prof.rows_by_name(names[0]) == ref[prof._name_ids[names[0]]]
    assert prof.rows_by_name(names[0])[:len(before)] == before
    assert prof.rows_by_name(names[1]) == ref[prof._name_ids[names[1]]]


def test_record_fast_many_rejects_nid_length_mismatch():
    prof = Profiler()
    nid = prof.name_id("x")
    with pytest.raises(ValueError, match="nid length mismatch"):
        prof.record_fast_many(np.arange(3.0), np.zeros(3, dtype=np.int64),
                              np.array([nid, nid]))


def test_record_fast_many_accepts_per_event_nids():
    prof = Profiler()
    na, nb = prof.name_id("a"), prof.name_id("b")
    eid = prof.entity_id("e")
    prof.record_fast_many([1.0, 2.0, 3.0], [eid] * 3, [na, nb, na])
    assert prof.times("a") == [1.0, 3.0]
    assert prof.times("b") == [2.0]


def test_numpy_accessors_match_lists_and_do_not_pin_buffers():
    prof, names = _mixed_trace()
    name = names[2]
    np.testing.assert_array_equal(prof.rows_np(name),
                                  np.asarray(prof.rows_by_name(name)))
    np.testing.assert_array_equal(prof.times_np(name),
                                  np.asarray(prof.times(name)))
    eids = prof.eids_np(name)
    assert [prof.entity_of(int(e)) for e in eids] == \
        [ev.entity for ev in prof.by_name(name)]
    # the accessors must return copies: appending afterwards would raise
    # BufferError if a frombuffer view were still alive
    prof.record(5000.0, "post", name)
    assert prof.times(name)[-1] == 5000.0
    assert prof.times_np(name)[-1] == 5000.0
    assert prof.has_name(name) and not prof.has_name("never-recorded")


# --------------------------------------------------------------------------
# lifecycle decomposition
# --------------------------------------------------------------------------

def test_lifecycle_telescopes_sim_object_path():
    tasks, cores, prof, mode = _run()
    bd = lifecycle_breakdown(tasks, prof, by="backend")
    assert bd.n_tasks == 400 and bd.n_skipped == 0
    _assert_telescopes(bd, tasks, cores, prof, mode)
    assert set(bd.groups) == {"flux"}


def test_lifecycle_telescopes_hybrid():
    tasks, cores, prof, mode = _run(hybrid=True)
    bd = lifecycle_breakdown(tasks, prof, by="backend")
    assert set(bd.groups) == {"flux", "dragon"}
    _assert_telescopes(bd, tasks, cores, prof, mode)


def test_lifecycle_telescopes_real_engine():
    tasks, cores, prof, mode = _run(n=40, mode="real")
    bd = lifecycle_breakdown(tasks, prof, by="backend")
    assert bd.n_tasks == 40
    _assert_telescopes(bd, tasks, cores, prof, mode)


def test_lifecycle_batch_vs_list_path():
    """A DescriptionBatch's bulk-stamped rows must decompose exactly like
    the same campaign submitted as a description list."""
    t_list, _, p_list, _ = _run(seed=11)
    t_bat, c_bat, p_bat, _ = _run(as_batch=True, seed=11)
    bd_list = lifecycle_breakdown(t_list, p_list, by="backend")
    bd_bat = lifecycle_breakdown(t_bat, p_bat, by="backend")
    assert bd_bat.n_tasks == bd_list.n_tasks == 400
    assert bd_bat.as_dict() == bd_list.as_dict()
    _assert_telescopes(bd_bat, t_bat, c_bat, p_bat)


def test_lifecycle_grouping_and_skips():
    tasks, cores, prof, _ = _run(n=60)
    bd_stage = lifecycle_breakdown(tasks, prof, by="stage")
    assert "default" in bd_stage.groups
    bd_none = lifecycle_breakdown(tasks, None, by=None)
    assert bd_none.groups == {} and bd_none.n_tasks == 60
    with pytest.raises(KeyError):
        lifecycle_breakdown(tasks, prof, by="nope")
    assert lifecycle_breakdown([], None).n_tasks == 0


# --------------------------------------------------------------------------
# timeseries reconstruction
# --------------------------------------------------------------------------

def test_throughput_mass_and_inflight_peak():
    tasks, cores, prof, _ = _run(n=300)
    m = A.compute_metrics(tasks, cores)
    thr = throughput(prof, tasks, dt=0.5)
    # every completion lands in exactly one bin
    assert thr.v.sum() * thr.dt == pytest.approx(m.n_done)
    infl = inflight(tasks, dt=0.01)
    assert infl.v.max() <= m.concurrency_peak
    assert infl.v.max() >= 1
    occ = occupancy(tasks, cores, dt=0.01)
    assert 0.0 < occ.v.max() <= 1.0
    # trace-derived and task-derived throughput agree
    thr2 = throughput(None, tasks, dt=0.5)
    np.testing.assert_allclose(thr.v, thr2.v)


def test_backend_inflight_partitions_by_backend():
    tasks, cores, prof, _ = _run(hybrid=True, n=200)
    per = backend_inflight(tasks, dt=0.1)
    assert set(per) == {"flux", "dragon"}
    total = inflight(tasks, dt=0.1)
    assert sum(s.v.max() for s in per.values()) >= total.v.max()


def test_sched_hold_depth_from_synthetic_trace():
    from repro.sched.scheduler import TRACE_NAMES, release_name
    prof = Profiler()
    hold = prof.name_id(TRACE_NAMES["hold"])
    rel = prof.name_id(release_name(0))
    eids = [prof.entity_id(f"t{i}") for i in range(4)]
    for i, e in enumerate(eids):
        prof.record_fast(float(i), e, hold)         # holds at t=0..3
    for i, e in enumerate(eids):
        prof.record_fast(10.0 + i, e, rel)          # released t=10..13
    s = sched_hold_depth(prof, dt=1.0)
    assert s.v.max() == 4                            # all four held at once
    assert s.v[-1] == 0                              # all released by the end
    # passthrough-only releases (never held) contribute nothing
    prof2 = Profiler()
    prof2.name_id(TRACE_NAMES["hold"])               # interned, no rows
    prof2.record_fast(1.0, prof2.entity_id("x"),
                      prof2.name_id(release_name(0)))
    assert len(sched_hold_depth(prof2, dt=1.0)) == 0


def test_service_queue_depth_from_request_log():
    class FakeService:
        name = "kv"

        def request_log(self):
            return {"submit": [0.0, 0.5, 1.0, 1.5],
                    "start": [1.0, 2.0, -1.0, 3.0],
                    "end": [2.0, 3.0, -1.0, 4.0],
                    "ok": b"\x01\x01\x00\x01", "retries": b"\x00" * 4}

    s = service_queue_depth(FakeService(), dt=0.25)
    assert s.v.max() >= 2          # requests 2 and 3 both pending at t=1.5
    assert s.name == "qdepth:kv"


def test_timeseries_dispatcher():
    tasks, cores, prof, _ = _run(n=50)
    assert timeseries(prof, tasks, "throughput", dt=1.0).name == "throughput"
    assert timeseries(None, tasks, "inflight").name == "inflight"
    with pytest.raises(KeyError):
        timeseries(prof, tasks, "bogus")
    with pytest.raises(ValueError):
        timeseries(None, tasks, "sched_hold_depth")


def test_live_sampler_autostops_on_sim_engine():
    """A self-rescheduling sampler must not hold the virtual clock open
    after the campaign drains — wait_tasks would otherwise never return."""
    with Session(mode="sim", seed=3) as session:
        pilot = PilotManager(session).submit_pilots(
            PilotDescription(nodes=4,
                             backends={"flux": {"partitions": 2}}))
        tm = TaskManager(session)
        tm.add_pilots(pilot)
        sampler = Watcher(pilot.agent, interval=0.5,
                          aggregate=False).start()
        tm.submit_tasks([TaskDescription(cores=1, duration=2.0)
                         for _ in range(40)])
        assert tm.wait_tasks(timeout=60)
        assert sampler.samples, "sampler never ticked"
        assert not sampler._armed
        series = sampler.series("n_unfinished")
        assert series.v[0] >= series.v[-1]


# --------------------------------------------------------------------------
# Chrome trace export
# --------------------------------------------------------------------------

def _validate_chrome(doc):
    assert set(doc) >= {"traceEvents", "otherData"}
    tracks = {}
    for e in doc["traceEvents"]:
        assert e["ph"] in ("X", "M", "C", "i")
        assert {"pid", "tid", "name"} <= set(e)
        if e["ph"] == "X":
            assert e["dur"] >= 1 and e["ts"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "g" and e["ts"] >= 0
        if "ts" in e:
            key = (e["pid"], e["tid"], e["ph"])
            assert e["ts"] >= tracks.get(key, -1), f"ts regress on {key}"
            tracks[key] = e["ts"]


def test_chrome_trace_roundtrip(tmp_path):
    tasks, cores, prof, _ = _run(hybrid=True, n=150)
    path = tmp_path / "trace.json"
    summary = export_chrome_trace(str(path), tasks, prof, total_cores=cores)
    doc = json.load(open(path))                      # schema-valid JSON
    _validate_chrome(doc)
    x = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(x) == 150 == summary["n_slices"]
    assert summary["n_slices_dropped"] == 0
    procs = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert {"backend:flux", "backend:dragon", "gauges"} <= procs
    assert any(e["ph"] == "C" for e in doc["traceEvents"])


def test_chrome_trace_slice_cap_is_not_silent():
    tasks, cores, prof, _ = _run(n=300)
    doc = chrome_trace(tasks, prof, total_cores=cores, max_slices=100)
    other = doc["otherData"]
    assert other["n_slices_dropped"] == 300 - other["n_slices"] > 0
    x = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(x) == other["n_slices"] <= 100
    _validate_chrome(doc)


def test_chrome_trace_lanes_never_overlap():
    tasks, cores, prof, _ = _run(n=120)
    doc = chrome_trace(tasks, prof)
    spans = {}
    for e in doc["traceEvents"]:
        if e["ph"] == "X":
            spans.setdefault((e["pid"], e["tid"]), []).append(
                (e["ts"], e["ts"] + e["dur"]))
    for lane, ss in spans.items():
        ss.sort()
        for (s1, e1), (s2, _) in zip(ss, ss[1:]):
            assert s2 >= e1, f"overlap on lane {lane}"


# --------------------------------------------------------------------------
# RunReport + CLI
# --------------------------------------------------------------------------

def test_run_report_collect_and_roundtrip(tmp_path):
    tasks, cores, prof, _ = _run(n=200)
    rep = RunReport.collect(tasks, cores, profiler=prof,
                            extra={"benchmark": "unit"})
    payload = rep.to_json()
    assert payload["report_version"] == 1
    assert payload["benchmark"] == "unit"
    assert payload["metrics"]["n_done"] == 200
    assert payload["cost"]["analysis_wall_s"] < 2.0
    assert payload["cost"]["events_per_task"] >= 5.0
    json.dumps(payload)                               # fully serializable
    path = tmp_path / "report.json"
    rep.save(str(path))
    text = rep.render()
    for needle in ("run metrics", "lifecycle breakdown", "observability "
                   "cost"):
        assert needle in text
    # CLI renders the saved payload
    assert obs_main(["report", str(path)]) == 0
    assert obs_main(["report", str(tmp_path / "missing.json")]) == 1


def test_run_report_wraps_bench_payloads():
    rep = RunReport(extra={"benchmark": "throughput_scale", "nodes": 64,
                           "seed": 0, "protocol": "x"},
                    results=[{"config": "flux x8", "n_tasks": 10,
                              "wall_s": 0.1}])
    payload = rep.to_json()
    # existing benchmark keys stay top-level and untouched
    assert payload["benchmark"] == "throughput_scale"
    assert payload["nodes"] == 64
    assert payload["results"][0]["config"] == "flux x8"
    assert payload["report_version"] == 1
    assert "metrics" not in payload
    assert "results" in render_payload(payload)  # renders without analysis


def test_run_report_with_services_and_sched():
    """Composes all four metric families when the inputs exist."""
    tasks, cores, prof, _ = _run(n=80)
    rep = RunReport.collect(tasks, cores, profiler=prof,
                            sched_by="tenant")
    payload = rep.to_json()
    assert "faults" in payload                  # profiler given
    assert payload["sched"]["fairness"] == pytest.approx(1.0)
    assert "throughput" in payload["series"]


def test_service_request_phase_breakdown():
    """Satellite: lifecycle_breakdown decomposes each service's request
    latency into queue (submit->start) and service (start->end) phases,
    and they tile the latency; the split flows into RunReport + render."""
    from repro.observability.lifecycle import service_request_breakdown

    with Session(mode="sim", seed=0) as s:
        pilot = PilotManager(s).submit_pilots(PilotDescription(
            nodes=8, backends={"flux": {"partitions": 2}}))
        tmgr = TaskManager(s)
        tmgr.add_pilots(pilot)
        svc = tmgr.start_service(replicas=2, nodes=1, rate=1.0)
        svc.submit_requests(range(20))
        svc.stop()
        assert tmgr.wait_tasks()
        sbd = service_request_breakdown(svc)
        assert sbd["n_requests"] == 20 and sbd["n_decomposed"] == 20
        q, sv = sbd["phases"]["queue"], sbd["phases"]["service"]
        assert q["n"] == sv["n"] == 20
        m = A.service_metrics(svc)
        # queue + service tiles the mean latency
        assert abs((q["sum"] + sv["sum"]) / 20 - m.latency_mean) <= REL
        # service phase matches the metrics family's handler time
        assert abs(sv["mean"] - m.service_time_mean) <= REL
        bd = lifecycle_breakdown(tmgr.tasks.values(), s.profiler,
                                 services=[svc])
        assert bd.services[svc.name] == sbd
        rep = RunReport.collect(list(tmgr.tasks.values()),
                                pilot.agent.total_cores,
                                profiler=s.profiler, services=[svc])
        assert rep.breakdown["services"][svc.name]["phases"]["queue"] == \
            sbd["phases"]["queue"]
        assert "request phases" in rep.render()


def test_report_diff_cli(tmp_path):
    """Satellite: `report BASELINE CANDIDATE --tolerance` prints per-phase
    and throughput deltas and exits nonzero on regressions only."""
    import copy

    tasks, cores, prof, _ = _run(n=200)
    base = RunReport.collect(tasks, cores, profiler=prof,
                             extra={"benchmark": "base"}).to_json()
    a = tmp_path / "a.json"
    with open(a, "w") as fh:
        json.dump(base, fh)

    # identical candidate: within tolerance
    b_same = tmp_path / "b_same.json"
    with open(b_same, "w") as fh:
        json.dump(base, fh)
    assert obs_main(["report", str(a), str(b_same)]) == 0

    # regressed candidate: exec phase mean x2, throughput halved
    worse = copy.deepcopy(base)
    worse["breakdown"]["total"]["phases"]["exec"]["mean"] *= 2.0
    worse["metrics"]["throughput_avg"] *= 0.5
    b_worse = tmp_path / "b_worse.json"
    with open(b_worse, "w") as fh:
        json.dump(worse, fh)
    assert obs_main(["report", str(a), str(b_worse)]) == 1
    # a huge tolerance swallows the regression
    assert obs_main(["report", str(a), str(b_worse),
                     "--tolerance", "5.0"]) == 0
    # improvements never trip the gate
    better = copy.deepcopy(base)
    better["breakdown"]["total"]["phases"]["exec"]["mean"] *= 0.5
    better["metrics"]["throughput_avg"] *= 2.0
    b_better = tmp_path / "b_better.json"
    with open(b_better, "w") as fh:
        json.dump(better, fh)
    assert obs_main(["report", str(a), str(b_better)]) == 0
    # three positional files is an error
    assert obs_main(["report", str(a), str(a), str(a)]) == 1
