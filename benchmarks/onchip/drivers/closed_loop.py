"""Closed loop: ``clients`` clients, each submitting one task and waiting
for it before it submits the next, through ``TaskManager.submit_tasks``.
One client thread serves them all: a done-listener on the agent hands each
finished task back, and every client whose task came back is resubmitted in
one bulk. A task's latency runs from its client's submit to the moment the
client sees it finished.

Traffic keys: ``clients``, ``task`` (the payload and its shape).
"""
from __future__ import annotations

import queue
import time

from harness import pilot as P
from harness.cell import TRACE_SECONDS
from harness.spec import load_module

WARM_K = 1 << 30          # request index of the set-up round
DRAIN_SECONDS = 60.0      # how long past the window in-flight tasks may take


def setup(run):
    t = run.cell.traffic
    payload = load_module("payloads", t["task"]["payload"],
                          run.cell.root).Payload(run, t["task"])
    payload.setup()
    run.payloads[payload.name] = payload
    session, tmgr = P.build(run)
    done = queue.SimpleQueue()
    calls = {}

    def listen(task):
        calls[task.uid] = calls.get(task.uid, 0) + 1
        done.put(task)

    tmgr.agent.add_done_callback(listen)
    state = dict(payload=payload, session=session, tmgr=tmgr, done=done,
                 calls=calls)
    # one round through the runtime: every worker thread has run a task
    warm = _submit(run, state, list(range(t["clients"])), k=WARM_K)
    tmgr.wait_tasks(list(warm.values()), timeout=300)
    while not done.empty():
        done.get()
    calls.clear()
    return state


def _submit(run, state, clients, k):
    from repro.core import TaskDescription
    payload = state["payload"]
    descs, recs = [], []
    for c in clients:
        kc = state.setdefault("next_k", {}).get(c, 0) if k is None else k
        rid = (c << 32) | (kc & 0xFFFFFFFF)
        tokens = payload.tokens(c, kc)
        descs.append(TaskDescription(kind="function", fn=payload.fn,
                                     args=(rid, tokens), stage=payload.name))
        recs.append(dict(client=c, k=kc, rid=rid, tokens=tokens))
        if k is None:
            state["next_k"][c] = kc + 1
    import jax
    t_submit = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench:client:submit"):
        tasks = state["tmgr"].submit_tasks(descs)
    out = {}
    for rec, task in zip(recs, tasks):
        rec["submit_t"] = t_submit
        out[task.uid] = task
        state.setdefault("open", {})[task.uid] = rec
    return out


def window(run, state):
    """The window, then (``--trace 1``) the traced span: the loop runs on
    for ``TRACE_SECONDS`` under the profiler. Tasks submitted after the
    window closed are drained but neither counted nor checked."""
    t = run.cell.traffic
    done, payload = state["done"], state["payload"]
    state["open"] = {}
    t0 = run.open_window()
    deadline = t0 + run.seconds
    stop_at = deadline
    hard_stop = deadline + DRAIN_SECONDS
    _submit(run, state, list(range(t["clients"])), k=None)
    closed = False
    import jax
    while state["open"]:
        try:
            with jax.profiler.TraceAnnotation("bench:client:wait"):
                first = done.get(timeout=0.5)
        except queue.Empty:
            first = None
        now = time.perf_counter()
        if not closed and now >= deadline:
            run.close_window(deadline)
            closed = True
            if run.start_trace():
                stop_at = time.perf_counter() + TRACE_SECONDS
                hard_stop = stop_at + DRAIN_SECONDS
        if run.trace_due():
            run.stop_trace()
        batch = [] if first is None else [first]
        while not done.empty():
            batch.append(done.get())
        again = []
        for task in batch:
            rec = state["open"].pop(task.uid, None)
            if rec is None:
                continue
            rec.update(P.record(task, payload.name,
                                in_window=rec["submit_t"] < deadline,
                                seen_t=now, flops=payload.flops,
                                submit_t=rec["submit_t"]))
            run.tasks.append(rec)
            if now < stop_at:
                again.append(rec["client"])
        if again:
            _submit(run, state, again, k=None)
        if now > hard_stop:
            break
    if not closed:
        run.close_window(deadline)
    run.stop_trace()
    for uid, rec in state["open"].items():      # never came back
        rec.update(uid=uid, payload=payload.name, state="LOST",
                   in_window=rec["submit_t"] < deadline, seen_t=None,
                   result=None, stamps={}, flops=payload.flops)
        run.tasks.append(rec)
    run.done_calls = dict(state["calls"])


def teardown(run, state):
    state["session"].close()
    state["payload"].free()
