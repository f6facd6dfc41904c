"""Pinned outputs of the simulated task path: each scenario runs one
campaign on ``SimEngine`` through ``Session``/``TaskManager`` and is
compared with values recorded in ``sim_golden.json`` — the
``compute_metrics`` fields, the ``concurrency_series``, the ``state:*``
trace counts, the engine clock at drain, and a digest of every task's
transition timestamps in submission order.

Integer fields, the series, the counts, the clock and the digest must
match exactly; ``compute_metrics`` floats to <=1e-9 relative, the
tolerance of the vectorized analytics against their sequential reference
(numpy's pairwise summation).

Regenerate the data only for a deliberate change of the sim model:
``PYTHONPATH=src python tests/test_sim_golden.py``."""
import hashlib
import json
import pathlib
import random

import pytest

from repro.core import analytics as A
from repro.core.pilot import PilotDescription
from repro.core.task import DescriptionBatch, TaskDescription
from repro.runtime.session import PilotManager, Session, TaskManager

_DATA = pathlib.Path(__file__).with_name("sim_golden.json")
_INT_FIELDS = {"n_tasks", "n_done", "n_failed", "concurrency_peak"}


def _null(n, hybrid=False, cores=1, duration=0.0, seed=None):
    rng = random.Random(seed) if seed is not None else None
    out = []
    for i in range(n):
        kind = "function" if (hybrid and i % 2) else "executable"
        dur = rng.uniform(0.0, 0.2) if rng is not None else duration
        out.append(TaskDescription(kind=kind, cores=cores, duration=dur))
    return out


def _ineligible():
    out = _null(600)
    out[300] = TaskDescription(cores=1, duration=0.0, max_retries=2)
    return out


def _capacity_bound():
    return [TaskDescription(uid=f"cap.{i}", cores=1, duration=180.0)
            for i in range(896)]


def _capacity_bound_varied():
    rng = random.Random(11)
    return [TaskDescription(uid=f"cv.{i}", cores=2,
                            duration=round(rng.uniform(1.0, 30.0), 6),
                            kind="function" if i % 2 else "executable")
            for i in range(4000)]


def _mixed(n, seed):
    rng = random.Random(seed)
    return [TaskDescription(uid=f"g{seed}.{i:06d}",
                            cores=rng.choice((1, 2, 4)),
                            duration=round(rng.uniform(0.0, 3.0), 6))
            for i in range(n)]


# name -> (descriptions builder, how they are submitted, hybrid, nodes,
# partitions per backend); "wave" submits 2,500 clones of one template
SCENARIOS = {
    "flux_null": (lambda: _null(2500), "list", False, 64, 8),
    "hybrid_null": (lambda: _null(2500, hybrid=True), "list", True, 64, 8),
    "uniform_duration_pool_binding": (
        lambda: _null(2000, cores=8, duration=0.5), "list", False, 64, 8),
    "random_durations_hybrid": (
        lambda: _null(2000, hybrid=True, cores=2, seed=7), "list", True,
        64, 8),
    "template_wave": (None, "wave", False, 64, 8),
    "gang_tasks": (
        lambda: [TaskDescription(cores=1, nodes=2, duration=0.0)
                 for _ in range(600)], "list", False, 64, 8),
    "ineligible_shapes": (_ineligible, "list", False, 64, 8),
    "small_list": (lambda: _null(300), "list", False, 64, 8),
    "capacity_bound_batch": (_capacity_bound, "batch", False, 4, 2),
    "capacity_bound_varied_batch": (_capacity_bound_varied, "batch", True,
                                    4, 2),
    "mixed_batch": (lambda: _mixed(1500, seed=5), "batch", False, 32, 4),
}


def _digest(tasks, order):
    by_uid = {t.uid: t for t in tasks}
    h = hashlib.sha256()
    for uid in order:
        t = by_uid[uid]
        for key, v in sorted(t.timestamps.items()):
            h.update(f"{key}={float(v).hex()};".encode())
        h.update(b"\n")
    return h.hexdigest()


def _run(name, **agent_options):
    build, how, hybrid, nodes, partitions = SCENARIOS[name]
    with Session(mode="sim", seed=42) as session:
        if hybrid:
            backends = {"flux": {"nodes": nodes // 2,
                                 "partitions": partitions},
                        "dragon": {"nodes": nodes // 2,
                                   "partitions": partitions}}
        else:
            backends = {"flux": {"partitions": partitions}}
        pilot = PilotManager(session).submit_pilots(
            PilotDescription(nodes=nodes, backends=backends),
            **agent_options)
        tm = TaskManager(session)
        tm.add_pilots(pilot)
        if how == "wave":
            submitted = tm.submit_wave(
                TaskDescription(cores=1, duration=0.0), 2500)
            order = [t.uid for t in submitted]
        else:
            descs = build()
            order = [d.uid for d in descs]
            tm.submit_tasks(DescriptionBatch.from_descriptions(descs)
                            if how == "batch" else descs)
        assert tm.wait_tasks(timeout=120)
        agent = pilot.agent
        tasks = agent.all_tasks()
        metrics = A.compute_metrics(tasks, agent.total_cores)
        return {
            "metrics": metrics.as_dict(),
            "series": [[s, c] for s, c in A.concurrency_series(tasks)],
            "trace_counts": {k: v for k, v in
                             sorted(session.profiler.counts_by_name()
                                    .items())
                             if k.startswith("state:")},
            "end": session.engine.now(),
            "n_unfinished": agent.n_unfinished,
            "digest": _digest(tasks, order),
        }


def _assert_matches(got, ref):
    for field, ref_v in ref["metrics"].items():
        got_v = got["metrics"][field]
        if field in _INT_FIELDS or ref_v == 0.0:
            assert got_v == ref_v, f"{field}: {got_v} != {ref_v}"
        else:
            rel = abs(got_v - ref_v) / abs(ref_v)
            assert rel <= 1e-9, f"{field}: {got_v} vs {ref_v} (rel {rel})"
    assert got["series"] == ref["series"]
    assert got["trace_counts"] == ref["trace_counts"]
    assert got["end"] == ref["end"]
    assert got["n_unfinished"] == ref["n_unfinished"] == 0
    assert got["digest"] == ref["digest"]


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_sim_trace_golden(scenario):
    ref = json.loads(_DATA.read_text())[scenario]
    _assert_matches(_run(scenario), ref)


if __name__ == "__main__":
    _DATA.write_text(json.dumps({name: _run(name) for name in SCENARIOS},
                                indent=1, sort_keys=True) + "\n")
