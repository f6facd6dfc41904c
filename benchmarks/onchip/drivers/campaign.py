"""IMPECCABLE-style campaign iterations, back to back: each iteration runs
its stages in order, each stage a bulk of tasks submitted through
``TaskManager.submit_tasks`` and awaited with ``wait_tasks``, then selects
the next docking batch from the docking scores. Iterations are counted
whole until ``--seconds`` have passed; the window ends with the last one.

Traffic keys: ``stages`` (each: ``name``, ``payload`` and its shape, and
``count`` for docking), ``select`` (how many top molecules seed the next
batch and the noise added to them).
"""
from __future__ import annotations

import time

import numpy as np

from harness import pilot as P
from harness.seeds import rng
from harness.spec import load_module
from payloads.train import seed_for

STAGE_TIMEOUT = 600       # seconds a stage's tasks may take


def setup(run):
    t = run.cell.traffic
    session, tmgr = P.build(run)
    payloads = {}
    for st in t["stages"]:
        pl = load_module("payloads", st["payload"], run.cell.root).Payload(
            run, st)
        payloads[st["name"]] = pl
        run.payloads[pl.name] = pl
    state = dict(session=session, tmgr=tmgr, payloads=payloads, calls={})

    def listen(task):
        state["calls"][task.uid] = state["calls"].get(task.uid, 0) + 1

    tmgr.agent.add_done_callback(listen)
    for pl in payloads.values():
        pl.setup()
    # one whole iteration through the runtime warms every shape, including
    # what the training task compiles inside its own call
    state["mols"] = _first_batch(run)
    _iteration(run, state, it=-1, in_window=False)
    run.tasks.clear()
    state["calls"].clear()
    return state


def _first_batch(run):
    st = next(s for s in run.cell.traffic["stages"]
              if s["payload"] == "docking")
    return rng(run.seed, 6).standard_normal((st["count"], st["width"]))


def _descs(run, st, pl, it, state):
    from repro.core import TaskDescription
    name = st["name"]
    if pl.name == "docking":
        return [(TaskDescription(kind="function", fn=pl.fn, args=(m,),
                                 stage=name), dict(mol=m))
                for m in state["mols"]]
    if pl.name == "train":
        seed = seed_for(run, it)
        return [(TaskDescription(kind="executable", coupling="tight",
                                 fn=pl.fn, args=pl.args(seed), stage=name),
                 dict(seed=seed, tokens=pl.tokens))]
    if pl.name == "generate":
        prompts = pl.prompt_tokens(it)
        return [(TaskDescription(kind="function", fn=pl.fn, args=(prompts,),
                                 stage=name), dict(prompts=prompts))]
    raise KeyError(f"campaign: no stage driver for payload {pl.name!r}")


def _iteration(run, state, it, in_window):
    import jax
    t = run.cell.traffic
    tmgr = state["tmgr"]
    rec = dict(it=it, start=time.perf_counter(), stages={})
    scores = None
    for st in t["stages"]:
        pl = state["payloads"][st["name"]]
        with jax.profiler.TraceAnnotation(f"bench:stage:{st['name']}"):
            pairs = _descs(run, st, pl, it, state)
            t_submit = time.perf_counter()
            tasks = tmgr.submit_tasks([d for d, _ in pairs])
            ok = tmgr.wait_tasks(tasks, timeout=STAGE_TIMEOUT)
            t_seen = time.perf_counter()
        if not ok:
            run.problem(f"stage {st['name']} of iteration {it} did not "
                        "finish in time")
        for task, (_, inputs) in zip(tasks, pairs):
            if not in_window and isinstance(task.result, dict):
                task.result.pop("params", None)     # only the window's
            run.tasks.append(P.record(
                task, pl.name, in_window=in_window, submit_t=t_submit,
                seen_t=t_seen, flops=pl.flops, iteration=it, stage=st["name"],
                **inputs))
        rec["stages"][st["name"]] = (t_submit, t_seen)
        if pl.name == "docking":
            scores = np.asarray([x.result if isinstance(x.result, float)
                                 else np.nan for x in tasks])
    with jax.profiler.TraceAnnotation("bench:select"):
        state["mols"] = _select(run, state["mols"], scores, it)
    rec["end"] = time.perf_counter()
    return rec


def _select(run, mols, scores, it):
    """The next batch: the best-scoring molecules, each perturbed into
    several new candidates."""
    sel = run.cell.traffic["select"]
    k = int(sel["top"])
    order = np.argsort(np.nan_to_num(scores, nan=np.inf))[:k]
    reps = -(-len(mols) // k)
    noise = rng(run.seed, 7, it + 1).standard_normal(
        (k * reps,) + mols.shape[1:]) * float(sel["noise"])
    return (np.repeat(mols[order], reps, axis=0) + noise)[:len(mols)]


def window(run, state):
    """The window, then (``--trace 1``) one more iteration under the
    profiler, neither counted nor checked."""
    t0 = run.open_window()
    deadline = t0 + run.seconds
    it = 0
    while True:
        rec = _iteration(run, state, it, in_window=True)
        run.iterations.append(rec)
        it += 1
        if rec["end"] >= deadline:
            break
    run.close_window(run.iterations[-1]["end"])
    if run.start_trace():
        _iteration(run, state, it, in_window=False)
        run.stop_trace()
    run.done_calls = dict(state["calls"])


def teardown(run, state):
    state["session"].close()
    for pl in state["payloads"].values():
        pl.free()
