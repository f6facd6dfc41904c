"""repro.services: persistent service tasks (replica lifecycle, request
routing, load balancing) and the funcpool function-execution path — the two
task modalities behind the paper's 1,500+ t/s function throughput and the
production campaign's service-based inference."""
import os
import time

import pytest

from repro.core import calibration as CAL
from repro.core.agent import Agent, SimEngine
from repro.core.analytics import compute_metrics, service_metrics
from repro.core.campaign import Campaign, Stage
from repro.core.pilot import PilotDescription
from repro.core.task import Task, TaskDescription, TaskState
from repro.runtime import PilotManager, Session, TaskManager
from repro.services import (LeastOutstandingBalancer, RoundRobinBalancer,
                            Service)


def _square(x):
    return x * x          # module-level: picklable for funcpool workers


def _pid_square(x):
    return (os.getpid(), x * x)


def _boom(x):
    raise ValueError(f"bad request {x}")


def _worker_env(_):
    import multiprocessing
    return (os.environ.get("JAX_PLATFORMS"),
            multiprocessing.get_start_method(allow_none=True))


# ------------------------------------------------------------ service tasks
def test_service_lifecycle_states_sim():
    """Replicas run the persistent lifecycle PROVISIONING -> READY ->
    SERVING -> DRAINING -> STOPPED with ordered timestamps, and the trace
    records every transition."""
    with Session(mode="sim", seed=0) as s:
        pilot = PilotManager(s).submit_pilots(PilotDescription(
            nodes=8, backends={"flux": {"partitions": 2}}))
        tmgr = TaskManager(s)
        tmgr.add_pilots(pilot)
        svc = tmgr.start_service(replicas=3, nodes=1, startup=5.0, rate=2.0)
        svc.submit_requests(range(30))
        svc.stop()
        assert tmgr.wait_tasks()
        assert svc.stopped and svc.n_completed == 30
        for d in svc.descriptions():
            t = tmgr.tasks[d.uid]
            assert t.state == TaskState.STOPPED
            ts = t.timestamps
            assert (ts["LAUNCHING"] <= ts["PROVISIONING"] < ts["READY"]
                    <= ts["DRAINING"] <= ts["STOPPED"])
            # provisioning took the configured startup time
            assert ts["READY"] - ts["PROVISIONING"] >= 5.0
        assert len(s.profiler.by_name("state:READY")) == 3
        assert len(s.profiler.by_name("state:STOPPED")) == 3


def test_service_requests_balanced_across_replicas():
    """Both balancers spread a buffered burst across all replicas, and
    request metrics (latency percentiles, utilization) come out sane."""
    for balancer in ("round-robin", "least-outstanding"):
        with Session(mode="sim", seed=0) as s:
            pilot = PilotManager(s).submit_pilots(PilotDescription(
                nodes=8, backends={"flux": {"partitions": 2}}))
            tmgr = TaskManager(s)
            tmgr.add_pilots(pilot)
            svc = tmgr.start_service(replicas=4, nodes=1, rate=1.0,
                                     balancer=balancer)
            svc.submit_requests(range(40))
            svc.stop()
            assert tmgr.wait_tasks()
            served = sorted(svc.served_per_replica().values())
            assert sum(served) == 40
            assert served[0] >= 8, (balancer, served)   # no starved replica
            m = service_metrics(svc)
            assert m.n_completed == 40 and m.n_failed == 0
            assert 0.0 < m.latency_p50 <= m.latency_p90 <= m.latency_p99
            assert 0.5 < m.utilization <= 1.0


def test_balancer_primitives():
    class R:
        def __init__(self, outstanding):
            self.outstanding = outstanding

    rr = RoundRobinBalancer()
    replicas = [R(0), R(0), R(0)]
    assert [rr.pick(replicas) for _ in range(4)] == [
        replicas[0], replicas[1], replicas[2], replicas[0]]
    lo = LeastOutstandingBalancer()
    replicas = [R(3), R(1), R(2)]
    assert lo.pick(replicas) is replicas[1]
    from repro.services import make_balancer
    with pytest.raises(KeyError, match="unknown balancer"):
        make_balancer("nope")


def _service_campaign_stages(holder):
    """Stage DAG with a service stage in the middle: prepare (functions) ->
    inference service fed by a request stream -> post. Carries both sim
    parameters (rate/startup/duration) and a real handler, so the same
    definition runs on either engine."""
    def mk_fns(n):
        return [TaskDescription(kind="function", duration=0.5, fn=_square,
                                args=(i,)) for i in range(n)]

    def mk_service(ctx):
        svc = Service(ctx.agent, handler=_square, replicas=2,
                      startup=2.0, rate=4.0, name="inference")
        svc.submit_requests(range(10))
        svc.stop()
        holder["svc"] = svc
        return svc.descriptions()

    return [
        Stage("prepare", lambda ctx: mk_fns(4)),
        Stage("serve", mk_service, depends_on=["prepare"]),
        Stage("post", lambda ctx: mk_fns(2), depends_on=["serve"]),
    ]


@pytest.mark.parametrize("mode", ["sim", "real"])
def test_service_campaign_cross_engine(mode):
    """Acceptance: the same service campaign (replicas + request stream)
    completes on both SimEngine and RealEngine."""
    holder = {}
    with Session(mode=mode, seed=0) as s:
        pilot = PilotManager(s).submit_pilots(PilotDescription(
            nodes=4, backends={"flux": {"partitions": 2},
                               "dragon": {"workers": 6}}))
        tmgr = TaskManager(s)
        tmgr.add_pilots(pilot)
        camp = tmgr.run_campaign(_service_campaign_stages(holder),
                                 timeout=120.0)
        assert camp.complete, mode
        svc = holder["svc"]
        assert svc.stopped and svc.n_completed == 10
        # n_completed counts failed requests too — pin that none failed
        # (a stop() racing provisioning once failed the whole buffer here)
        assert service_metrics(svc).n_failed == 0, mode
        for t in camp.stage_tasks["serve"]:
            assert t.state == TaskState.STOPPED, mode
        # the post stage started only after the service drained
        stopped_at = max(t.timestamps["STOPPED"]
                         for t in camp.stage_tasks["serve"])
        assert all(t.timestamps["RUNNING"] >= stopped_at
                   for t in camp.stage_tasks["post"])
        if mode == "real":
            assert sorted(svc.results) == sorted(i * i for i in range(10))


def test_real_service_handler_failures_recorded():
    with Session(mode="real") as s:
        pilot = PilotManager(s).submit_pilots(PilotDescription(
            nodes=1, backends={"dragon": {"workers": 3}}))
        tmgr = TaskManager(s)
        tmgr.add_pilots(pilot)
        svc = tmgr.start_service(handler=_boom, replicas=1)
        svc.submit_requests(range(3))
        svc.stop()
        assert tmgr.wait_tasks(timeout=30)
        m = service_metrics(svc)
        assert m.n_completed == 3 and m.n_failed == 3
        assert all("ValueError" in r for r in svc.results)


def test_service_requires_capable_backend():
    """srun cannot host persistent services; routing must say so."""
    with pytest.raises(RuntimeError, match="service-capable"):
        with Session(mode="sim") as s:
            pilot = PilotManager(s).submit_pilots(PilotDescription(
                nodes=4, backends={"srun": {}}))
            tmgr = TaskManager(s)
            tmgr.add_pilots(pilot)
            tmgr.start_service(replicas=1)
            tmgr.wait_tasks()


def test_adaptive_policy_respects_service_capability():
    """The dynamic policy builds eligibility from accepts(), so the
    capability restriction must hold there too — replicas never land on
    srun even when it is the emptier backend."""
    from repro.core.agent import AdaptiveRoutingPolicy

    eng = SimEngine(seed=0)
    agent = Agent(eng, 8, {"srun": {"nodes": 4},
                           "flux": {"partitions": 2, "nodes": 4}},
                  policy=AdaptiveRoutingPolicy())
    agent.start()
    svc = Service(agent, replicas=2, rate=5.0)
    svc.submit()
    svc.request()
    svc.stop()
    agent.run_until_complete()
    tasks = [agent.tasks[d.uid] for d in svc.descriptions()]
    assert {t.backend for t in tasks} == {"flux"}
    assert all(t.state == TaskState.STOPPED for t in tasks)


def test_replica_failure_requeues_requests_to_survivors():
    """Killing the executor instance under a SERVING replica re-dispatches
    its queued/in-flight requests to the surviving replica through the
    balancer (nothing is silently counted as served, nothing is lost); the
    survivor drains and the service still stops."""
    eng = SimEngine(seed=0)
    agent = Agent(eng, 8, {"flux": {"partitions": 2}})
    agent.start()
    svc = Service(agent, replicas=2, nodes=1, rate=1.0)
    svc.submit()
    svc.submit_requests(range(40))
    svc.stop()
    eng.schedule(30.0, agent.fail_flux_instance, 0, "flux", False)
    agent.run_until_complete()
    assert svc.stopped and svc.error is not None
    m = service_metrics(svc)
    assert m.n_completed == 40                  # every request accounted for
    assert m.n_failed == 0                      # requeue saved all of them
    assert m.n_retried > 0 and m.retries_total >= m.n_retried
    states = {agent.tasks[d.uid].state for d in svc.descriptions()}
    assert states == {TaskState.STOPPED, TaskState.FAILED}


def test_replica_failure_without_retries_fails_its_requests():
    """With requeue disabled (max_retries=0) the seed semantics hold: the
    dead replica's queued/in-flight requests fail with its epitaph while
    survivors keep draining."""
    eng = SimEngine(seed=0)
    agent = Agent(eng, 8, {"flux": {"partitions": 2}})
    agent.start()
    svc = Service(agent, replicas=2, nodes=1, rate=1.0, max_retries=0)
    svc.submit()
    svc.submit_requests(range(40))
    svc.stop()
    eng.schedule(30.0, agent.fail_flux_instance, 0, "flux", False)
    agent.run_until_complete()
    assert svc.stopped and svc.error is not None
    m = service_metrics(svc)
    assert m.n_completed == 40                  # every request accounted for
    assert 0 < m.n_failed < 40                  # the dead replica's share
    states = {agent.tasks[d.uid].state for d in svc.descriptions()}
    assert states == {TaskState.STOPPED, TaskState.FAILED}


# ------------------------------------------------------------ function pool
def test_funcpool_sim_beats_executable_dispatch_5x():
    """Acceptance: at 100k null tasks the sim function path sustains >=5x
    the executable-path dispatch rate (paper: 1,547 t/s function mode vs
    srun's 152 peak)."""
    def run(backends, kind):
        with Session(mode="sim", seed=0) as s:
            pilot = PilotManager(s).submit_pilots(
                PilotDescription(nodes=16, backends=backends))
            tmgr = TaskManager(s)
            tmgr.add_pilots(pilot)
            tmgr.submit_tasks([TaskDescription(cores=1, kind=kind)
                               for _ in range(100_000)])
            tmgr.wait_tasks()
            return compute_metrics(list(pilot.agent.tasks.values()),
                                   pilot.agent.total_cores)

    ex = run({"srun": {}}, "executable")
    fn = run({"funcpool": {}}, "function")
    assert fn.n_done == 100_000 and ex.n_done == 100_000
    assert fn.throughput_avg >= 5.0 * ex.throughput_avg
    # the function path flattens at the RP dispatch ceiling, like the paper
    assert fn.throughput_peak <= CAL.RP_DISPATCH_RATE * 1.05


def test_funcpool_real_no_process_per_call():
    """The real funcpool executes function tasks inside persistent workers:
    every result carries one of <= `workers` distinct PIDs, none of them the
    master's."""
    with Session(mode="real") as s:
        pilot = PilotManager(s).submit_pilots(
            PilotDescription(nodes=1, backends={"funcpool": {"workers": 3}}),
            dispatch_rate=50_000, dispatch_batch=256)
        tmgr = TaskManager(s)
        tmgr.add_pilots(pilot)
        tasks = tmgr.submit_functions(_pid_square, range(300))
        assert tmgr.wait_tasks(timeout=60)
        assert all(t.state == TaskState.DONE for t in tasks)
        pids = {t.result[0] for t in tasks}
        assert 1 <= len(pids) <= 3
        assert os.getpid() not in pids
        assert sorted(t.result[1] for t in tasks) == [i * i
                                                      for i in range(300)]


def test_funcpool_workers_spawned_cpu_only(monkeypatch):
    """Funcpool workers never fork a process that may hold the chip: they
    are spawned, with JAX_PLATFORMS=cpu whatever the parent's setting, and
    the parent's environment is left as it was."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with Session(mode="real") as s:
        pilot = PilotManager(s).submit_pilots(
            PilotDescription(nodes=1, backends={"funcpool": {"workers": 2}}))
        assert os.environ["JAX_PLATFORMS"] == "tpu"
        tmgr = TaskManager(s)
        tmgr.add_pilots(pilot)
        tasks = tmgr.submit_functions(_worker_env, range(4))
        assert tmgr.wait_tasks(timeout=60)
        assert {t.result for t in tasks} == {("cpu", "spawn")}


def test_funcpool_real_failure_and_unpicklable():
    with Session(mode="real") as s:
        pilot = PilotManager(s).submit_pilots(
            PilotDescription(nodes=1, backends={"funcpool": {"workers": 2}}))
        tmgr = TaskManager(s)
        tmgr.add_pilots(pilot)
        bad = tmgr.submit_tasks(TaskDescription(kind="function", fn=_boom,
                                                args=(1,)))
        unpicklable = tmgr.submit_tasks(TaskDescription(
            kind="function", fn=lambda: None))      # lambdas don't pickle
        ok = tmgr.submit_tasks(TaskDescription(kind="function", fn=_square,
                                               args=(7,)))
        assert tmgr.wait_tasks(timeout=60)
        assert bad.state == TaskState.FAILED and "ValueError" in bad.error
        assert unpicklable.state == TaskState.FAILED
        assert "unpicklable" in unpicklable.error
        assert ok.state == TaskState.DONE and ok.result == 49


def test_funcpool_routing_preferred_for_functions():
    """With a funcpool configured, loose function tasks route to it; tasks
    it cannot take (multi-node) keep the modality rules."""
    with Session(mode="sim", seed=0) as s:
        pilot = PilotManager(s).submit_pilots(PilotDescription(
            nodes=8, backends={"flux": {"partitions": 2, "nodes": 6},
                               "funcpool": {"nodes": 2}}))
        tmgr = TaskManager(s)
        tmgr.add_pilots(pilot)
        fn = tmgr.submit_tasks(TaskDescription(kind="function"))
        multi = tmgr.submit_tasks(TaskDescription(kind="function", nodes=2))
        tmgr.wait_tasks()
        assert fn.backend == "funcpool"
        assert multi.backend == "flux"


# ------------------------------------------------ impeccable service stage
def test_impeccable_service_inference():
    from repro.core.impeccable import run_impeccable

    agent, camp = run_impeccable("flux", 128, iterations=1,
                                 service_inference=True)
    assert camp.complete
    infer = camp.stage_tasks["inference.0"]
    assert infer and all(t.state == TaskState.STOPPED for t in infer)
    # downstream scoring waited for the drained service
    stopped_at = max(t.timestamps["STOPPED"] for t in infer)
    assert all(t.timestamps["RUNNING"] >= stopped_at
               for t in camp.stage_tasks["scoring.0.0"])


# ------------------------------------------- satellite: callback chaining
def test_campaign_composes_with_existing_done_callback():
    """Campaign registration must not clobber previously installed task
    watchers (e.g. service readiness hooks)."""
    eng = SimEngine(seed=0)
    agent = Agent(eng, 4, {"flux": {"partitions": 2}})
    agent.start()
    seen = []
    agent.on_task_done = lambda t: seen.append(t.uid)
    camp = Campaign(agent, [Stage("only", lambda ctx: [
        TaskDescription(duration=1.0) for _ in range(5)])])
    camp.start()
    agent.run_until_complete()
    assert camp.complete
    assert len(seen) == 5          # the legacy watcher still fired


# --------------------------------------- satellite: quantile speculation
def test_quantile_speculation_clones_duration_free_straggler():
    """ROADMAP item: tasks with no ``duration`` get speculation deadlines
    from the observed-duration quantile; a straggler is cloned and the
    clone's result lands."""
    eng = SimEngine(seed=0)
    straggler = {}

    def duration_fn(task):
        if task.uid not in straggler and not straggler:
            straggler[task.uid] = True
            return 500.0
        return 1.0

    eng.duration_fn = duration_fn
    agent = Agent(eng, 8, {"flux": {"partitions": 2}}, speculation=True,
                  speculation_factor=3.0, speculation_min_samples=10)
    agent.start()
    # duration=0.0 descriptions: the old deadline rule had nothing to arm
    agent.submit([TaskDescription(cores=1, duration=0.0) for _ in range(40)])
    agent.run_until_complete()
    assert len(eng.profiler.by_name("agent:speculate")) >= 1
    clones = [t for t in agent.tasks.values() if t.speculative_of]
    assert clones and any(t.state == TaskState.DONE for t in clones)
    # the campaign did not wait the straggler's full 500 virtual seconds
    assert eng.now() < 400.0


def test_real_engine_speculation_clones_straggler():
    """The same quantile deadlines drive the RealEngine: a payload that
    hangs past the observed-duration quantile gets a speculative clone whose
    result lands without waiting the straggler out."""
    import threading

    release = threading.Event()
    calls = {"n": 0}
    guard = threading.Lock()

    def work():
        with guard:
            calls["n"] += 1
            first = calls["n"] == 1
        if first:                      # the original hangs; the clone flies
            release.wait(timeout=15.0)
            return "slow"
        return "fast"

    t0 = time.monotonic()
    try:
        with Session(mode="real") as s:
            pilot = PilotManager(s).submit_pilots(
                PilotDescription(nodes=1, backends={"dragon": {"workers": 4}}),
                speculation=True, speculation_factor=2.0,
                speculation_min_samples=5)
            tmgr = TaskManager(s)
            tmgr.add_pilots(pilot)
            # fast duration-free tasks seed the quantile
            fast = tmgr.submit_tasks([TaskDescription(kind="function",
                                                      fn=lambda: None)
                                      for _ in range(8)])
            assert tmgr.wait_tasks(fast, timeout=30)
            straggler = tmgr.submit_tasks(TaskDescription(kind="function",
                                                          fn=work))
            assert tmgr.wait_tasks(timeout=30)
            assert len(s.profiler.by_name("agent:speculate")) >= 1
            clones = [t for t in pilot.agent.tasks.values()
                      if t.speculative_of == straggler.uid]
            assert clones and any(t.state == TaskState.DONE for t in clones)
            assert straggler.result == "fast"      # clone's result landed
            assert time.monotonic() - t0 < 15.0    # did not wait the hang out
    finally:
        release.set()                  # unblock the hung payload thread


# ---------------------------------------- satellite: wall-clock analytics
def test_compute_metrics_real_mode_wallclock():
    def mk(uid, start, end, state=TaskState.DONE, nodes=2):
        t = Task(TaskDescription(uid=uid, nodes=nodes))
        for s, at in ((TaskState.SCHEDULING, 0.0), (TaskState.QUEUED, 0.0),
                      (TaskState.LAUNCHING, start), (TaskState.RUNNING,
                                                     start)):
            t.advance(s, at)
        t.advance(state, end)
        return t

    tasks = [mk("a", 1.0, 3.0), mk("b", 2.0, 5.0),
             mk("c", 4.0, 9.0, state=TaskState.FAILED)]
    # sim mode charges the fictional 2-node footprint and ignores failures
    # in the makespan; real mode charges one local worker per task and
    # extends the makespan to the last terminal event
    sim = compute_metrics(tasks, total_cores=4 * 56, mode="sim")
    real = compute_metrics(tasks, total_cores=2, mode="real")
    assert sim.makespan == 5.0 and real.makespan == 9.0
    # busy worker-seconds = (3-1) + (5-2) = 5 over 2 workers x (5-1) window
    assert abs(real.utilization - 5.0 / (2 * 4.0)) < 1e-9
    assert sim.utilization == pytest.approx(
        (2 + 3) * 2 * 56 / (4 * 56 * 4.0))
