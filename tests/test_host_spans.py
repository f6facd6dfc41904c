"""The real engine's host path on the profiler's clock: the per-task stamps
beside the state stamps (``tick_due``, ``picked``, ``returned``, ``ready``),
exec ending at device completion, the ``rp:*`` spans in a ``jax.profiler``
trace, and a sim engine that carries none of it."""
import glob
import os
import threading
import time

import jax
import jax.numpy as jnp

from repro.core.pilot import PilotDescription
from repro.core.task import TaskDescription, TaskState
from repro.runtime.session import PilotManager, Session, TaskManager

ORDER = ("SCHEDULING", "tick_due", "QUEUED", "picked", "RUNNING",
         "returned", "ready", "DONE")
NEW_KEYS = {"tick_due", "picked", "returned", "ready"}


def _real(workers=2):
    session = Session(mode="real", seed=0)
    pilot = PilotManager(session).submit_pilots(PilotDescription(
        nodes=1, backends={"dragon": {"workers": workers}}))
    tm = TaskManager(session)
    tm.add_pilots(pilot)
    return session, pilot, tm


def _square(i):
    return i * i


def test_real_dragon_tasks_carry_the_stamps_in_order():
    session, _, tm = _real()
    with session:
        tasks = tm.submit_tasks([TaskDescription(kind="function", fn=_square,
                                                 args=(i,))
                                 for i in range(12)])
        assert tm.wait_tasks(timeout=60)
    for t in tasks:
        assert t.state is TaskState.DONE and t.result == t.description.args[0] ** 2
        ts = t.timestamps
        assert NEW_KEYS <= set(ts), sorted(ts)
        seq = [ts[k] for k in ORDER]
        assert seq == sorted(seq), dict(zip(ORDER, seq))


def test_exec_of_a_device_array_ends_at_device_completion():
    x = jnp.full((512, 512), 1e-3, jnp.float32)

    @jax.jit
    def heavy(x):
        for _ in range(24):
            x = jnp.tanh(x @ x)
        return x

    heavy(x).block_until_ready()                 # compiled outside the task
    session, pilot, tm = _real(workers=1)
    ready_at_done = []
    pilot.agent.add_done_callback(
        lambda t: ready_at_done.append(t.result.is_ready()))
    with session:
        task = tm.submit_tasks(TaskDescription(kind="function", fn=heavy,
                                               args=(x,)))
        assert tm.wait_tasks(timeout=60)
    assert task.state is TaskState.DONE
    ts = task.timestamps
    assert ts["RUNNING"] <= ts["returned"] <= ts["ready"] <= ts["DONE"]
    # the array was computed when the task was committed DONE
    assert ready_at_done == [True]


def _mixed_keys():
    return {1: jnp.ones(2), "a": 0}


def test_a_result_jax_cannot_flatten_fails_its_task():
    # the dict's keys do not sort, so its arrays cannot be waited for: the
    # task fails rather than being committed DONE before they are computed
    session, _, tm = _real(workers=1)
    with session:
        task = tm.submit_tasks(TaskDescription(kind="function",
                                               fn=_mixed_keys))
        assert tm.wait_tasks(timeout=60)
    assert task.state is TaskState.FAILED
    assert task.error.startswith("ValueError"), task.error
    assert "returned" in task.timestamps and "ready" not in task.timestamps


def test_sim_tasks_carry_no_new_stamp():
    with Session(mode="sim", seed=5) as session:
        pilot = PilotManager(session).submit_pilots(PilotDescription(
            nodes=2, backends={"dragon": {}, "flux": {"partitions": 1}}))
        tm = TaskManager(session)
        tm.add_pilots(pilot)
        tasks = tm.submit_tasks(
            [TaskDescription(kind="function", duration=1.0)
             for _ in range(20)]
            + [TaskDescription(cores=1, duration=2.0) for _ in range(20)])
        assert tm.wait_tasks(timeout=60)
    assert all(t.state is TaskState.DONE for t in tasks)
    for t in tasks:
        assert all(k.isupper() for k in t.timestamps), sorted(t.timestamps)


def _xplane_span_names(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))[-1]
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("rp:"):
                    spans.setdefault(e.name, []).append(e.duration_ns * 1e-9)
    return spans


def test_profiler_trace_holds_the_runtime_spans(tmp_path):
    running, release = threading.Event(), threading.Event()

    def held(i):
        running.set()
        release.wait(30)
        return i

    session, _, tm = _real(workers=1)
    with session:
        jax.profiler.start_trace(str(tmp_path))
        try:
            task = tm.submit_tasks(TaskDescription(kind="function", fn=held,
                                                   args=(3,)))
            assert running.wait(30)
            # the task's commit has to wait while the lock is held here
            with session.engine.lock:
                release.set()
                time.sleep(0.2)
            assert tm.wait_tasks(timeout=60)
        finally:
            jax.profiler.stop_trace()
    assert task.state is TaskState.DONE and task.result == 3
    spans = _xplane_span_names(str(tmp_path))
    for name in ("rp:submit", "rp:dispatch", "rp:exec:start",
                 "rp:exec:payload", "rp:exec:device_wait", "rp:exec:commit",
                 "rp:lock:wait"):
        assert name in spans, sorted(spans)
    assert max(spans["rp:lock:wait"]) >= 0.1


def test_every_wait_for_the_real_engine_lock_is_a_span(tmp_path):
    session, _, _ = _real()
    lock = session.engine.lock
    with session:
        jax.profiler.start_trace(str(tmp_path))
        try:
            entered = threading.Event()

            def take():
                with lock:
                    entered.set()

            with lock:
                other = threading.Thread(target=take)
                other.start()
                time.sleep(0.2)
                assert not entered.is_set()
            other.join(30)
            assert entered.is_set()
        finally:
            jax.profiler.stop_trace()
    waits = _xplane_span_names(str(tmp_path))["rp:lock:wait"]
    # the session's own timers may wait briefly too
    assert sum(w >= 0.1 for w in waits) == 1, waits
