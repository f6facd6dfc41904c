"""Golden-equivalence suite for the columnar DescriptionBatch submission
path: the same campaign submitted as a ``DescriptionBatch`` (or a
``submit_wave`` template wave) vs a ``List[TaskDescription]`` must produce
identical ``compute_metrics`` (ints exact, floats <=1e-9), identical
``state:*`` trace event counts, and — under a gated scheduler — the
identical per-pilot release order, on the flux-only and flux+dragon hybrid
configs, on both engines. Plus batch round-trips, the scheduler's
conservative fallback gates, dependency-target visibility into pending
batch rows, and property tests over random uniform waves and random mixed
batches (sparse fields, deps, priorities) with seeded fallbacks when
hypothesis is absent."""
import random

import pytest

from repro.core import analytics as A
from repro.core.pilot import PilotDescription
from repro.core.task import DescriptionBatch, TaskDescription, TaskState
from repro.runtime import PilotManager, Session, TaskManager
from repro.sched import CampaignScheduler, FairSharePolicy, PriorityPolicy
from repro.sched.scheduler import release_name

_INT_FIELDS = {"n_tasks", "n_done", "n_failed", "concurrency_peak"}


# --------------------------------------------------------------------------
# harness: run one task set, either as objects or as a batch
# --------------------------------------------------------------------------

def _mixed_descs(n, *, hybrid=False, seed=5, priorities=(0,), tenants=("",),
                 with_deps=False, with_sparse=False, max_duration=3.0):
    """Deterministic mixed description set with explicit uids, so the
    object and batch runs are row-for-row comparable across sessions."""
    rng = random.Random(seed)
    out = []
    for i in range(n):
        kind = "function" if (hybrid and i % 2) else "executable"
        d = TaskDescription(
            uid=f"g{seed}.{i:06d}", kind=kind,
            cores=rng.choice((1, 2, 4)),
            duration=round(rng.uniform(0.0, max_duration), 6),
            priority=rng.choice(priorities),
            tenant=rng.choice(tenants))
        if with_sparse and rng.random() < 0.2:
            d.arguments = ("--row", str(i))
        if with_deps and i >= n // 2 and rng.random() < 0.3:
            d.after = (out[rng.randrange(n // 2)].uid,)
        out.append(d)
    return out


def _null_descs(n, hybrid=False, cores=1, duration=0.0, seed=None):
    """Builder of ``n`` uniform descriptions (kinds alternate when
    ``hybrid``); ``seed`` draws each duration from U(0, 0.2) instead, the
    same draws on every call."""
    def build():
        rng = random.Random(seed) if seed is not None else None
        out = []
        for i in range(n):
            kind = "function" if (hybrid and i % 2) else "executable"
            dur = rng.uniform(0.0, 0.2) if rng is not None else duration
            out.append(TaskDescription(kind=kind, cores=cores, duration=dur))
        return out
    return build


def _run(descs_fn, *, as_batch, hybrid=False, mode="sim", seed=42,
         sched_fn=None, nodes=32, partitions=4, wave=None):
    with Session(mode=mode, seed=seed) as session:
        if hybrid:
            backends = {"flux": {"nodes": nodes // 2,
                                 "partitions": partitions},
                        "dragon": {"nodes": nodes // 2,
                                   "partitions": partitions}}
        else:
            backends = {"flux": {"partitions": partitions}}
        pilot = PilotManager(session).submit_pilots(
            PilotDescription(nodes=nodes, backends=backends))
        sched = sched_fn() if sched_fn is not None else None
        tm = (TaskManager(session, scheduler=sched) if sched is not None
              else TaskManager(session))
        tm.add_pilots(pilot)
        if wave is not None:
            submitted = tm.submit_wave(*wave)
        else:
            descs = descs_fn()
            payload = (DescriptionBatch.from_descriptions(descs) if as_batch
                       else descs)
            submitted = tm.submit_tasks(payload)
        assert tm.wait_tasks(timeout=120)
        agent = pilot.agent
        tasks = agent.all_tasks()
        prof = session.profiler
        release = {}
        i = 0
        while prof.has_name(release_name(i)):
            release[i] = [prof.entity_of(int(e))
                          for e in prof.eids_np(release_name(i))]
            i += 1
        return {
            "submitted": submitted,
            "metrics": A.compute_metrics(tasks, agent.total_cores),
            "series": A.concurrency_series(tasks),
            "occupancy": A.occupancy_utilization(tasks, agent.total_cores),
            "completed": {name: ex.stats["completed"]
                          for name, ex in agent.backends.items()},
            "trace_counts": {k: v for k, v in
                             prof.counts_by_name().items()
                             if k.startswith("state:")},
            "release": release,
            "n_unfinished": agent.n_unfinished,
            "end": session.engine.now(),
        }


def _assert_equivalent(off, on, exact_floats=True):
    m_off, m_on = off["metrics"], on["metrics"]
    for fname, ref_v in m_off.__dict__.items():
        got_v = m_on.__dict__[fname]
        if fname in _INT_FIELDS:
            assert got_v == ref_v, f"{fname}: {got_v} != {ref_v}"
        elif not exact_floats:
            continue
        elif ref_v == 0.0:
            assert got_v == 0.0, f"{fname}: {got_v} != 0"
        else:
            rel = abs(got_v - ref_v) / abs(ref_v)
            assert rel <= 1e-9, f"{fname}: {got_v} vs {ref_v} (rel {rel})"
    assert off["trace_counts"] == on["trace_counts"]
    assert off["completed"] == on["completed"]
    assert off["n_unfinished"] == on["n_unfinished"] == 0
    if exact_floats:
        assert off["series"] == on["series"]
        assert off["occupancy"] == on["occupancy"]
        assert off["end"] == on["end"]


# --------------------------------------------------------------------------
# passthrough equivalence: list vs batch vs template wave
# --------------------------------------------------------------------------

def test_batch_golden_flux_sim():
    kw = dict(n=1500, seed=5)
    off = _run(lambda: _mixed_descs(**kw), as_batch=False)
    on = _run(lambda: _mixed_descs(**kw), as_batch=True)
    _assert_equivalent(off, on)


def test_batch_golden_hybrid_sim():
    kw = dict(n=1500, seed=6, hybrid=True)
    off = _run(lambda: _mixed_descs(**kw), as_batch=False, hybrid=True)
    on = _run(lambda: _mixed_descs(**kw), as_batch=True, hybrid=True)
    _assert_equivalent(off, on)


def _big(descs_fn, hybrid=False):
    """List vs batch of one description set on the 64-node config."""
    off = _run(descs_fn, as_batch=False, hybrid=hybrid, nodes=64,
               partitions=8)
    on = _run(descs_fn, as_batch=True, hybrid=hybrid, nodes=64,
              partitions=8)
    assert isinstance(on["submitted"], list)
    _assert_equivalent(off, on)


def test_batch_golden_flux_null():
    _big(_null_descs(2500))


def test_batch_golden_hybrid_null():
    _big(_null_descs(2500, hybrid=True), hybrid=True)


def test_batch_golden_uniform_duration_pool_binding():
    # nonzero durations make allocations outlive launches
    _big(_null_descs(2000, cores=8, duration=0.5))


def test_batch_golden_random_durations_hybrid():
    _big(_null_descs(2000, hybrid=True, cores=2, seed=7), hybrid=True)


def test_wave_api_matches_descs():
    off = _run(_null_descs(2500), as_batch=False, nodes=64, partitions=8)
    on = _run(None, as_batch=True, nodes=64, partitions=8,
              wave=(TaskDescription(cores=1, duration=0.0), 2500))
    assert isinstance(on["submitted"], list)
    assert len(on["submitted"]) == 2500
    _assert_equivalent(off, on)


def test_batch_capacity_bound_wave_matches_object():
    # nonzero durations on a small cluster: the pool binds (8x more tasks
    # than cores), so launches are paced by real finishes
    def descs():
        return [TaskDescription(uid=f"cap.{i}", cores=1, duration=180.0)
                for i in range(896)]
    off = _run(descs, as_batch=False, nodes=4, partitions=2)
    on = _run(descs, as_batch=True, nodes=4, partitions=2)
    _assert_equivalent(off, on)
    assert on["metrics"].concurrency_peak <= 4 * 56


def test_batch_capacity_bound_varied_durations_match_object():
    # per-row durations + capacity-bound pool on both backends
    def descs():
        rng = random.Random(11)
        return [TaskDescription(uid=f"cv.{i}", cores=2,
                                duration=round(rng.uniform(1.0, 30.0), 6),
                                kind="function" if i % 2 else "executable")
                for i in range(4000)]
    off = _run(descs, as_batch=False, hybrid=True, nodes=4, partitions=2)
    on = _run(descs, as_batch=True, hybrid=True, nodes=4, partitions=2)
    _assert_equivalent(off, on)
    assert on["metrics"].concurrency_peak <= 4 * 56 // 2


def test_batch_round_trip_preserves_descriptions():
    descs = _mixed_descs(64, seed=9, priorities=(0, 2), tenants=("", "b"),
                         with_deps=True, with_sparse=True)
    batch = DescriptionBatch.from_descriptions(descs)
    assert batch.n == 64 and batch.has_explicit_uids()
    back = batch.to_descriptions()
    assert [d.uid for d in back] == [d.uid for d in descs]
    for a, b in zip(descs, back):
        assert (a.cores, a.duration, a.priority, a.tenant, a.after,
                a.arguments) == (b.cores, b.duration, b.priority, b.tenant,
                                 b.after, b.arguments)
    # per-row views read through to the columns
    v = batch.view(10)
    assert v.uid == descs[10].uid and v.cores == descs[10].cores


# --------------------------------------------------------------------------
# gated scheduler: release order on column slices vs per-entry pushes
# --------------------------------------------------------------------------

def _gated(policy_fn):
    return lambda: CampaignScheduler(policy=policy_fn(), admission=True)


@pytest.mark.parametrize("policy_fn,kw", [
    (lambda: "fifo", dict(n=300, seed=11)),
    (lambda: PriorityPolicy(), dict(n=300, seed=12, priorities=(0, 1, 3))),
    (lambda: FairSharePolicy(), dict(n=300, seed=13,
                                     tenants=("a", "b", "c"))),
])
def test_batch_gated_release_order_flux(policy_fn, kw):
    off = _run(lambda: _mixed_descs(**kw), as_batch=False,
               sched_fn=_gated(policy_fn), nodes=4, partitions=1)
    on = _run(lambda: _mixed_descs(**kw), as_batch=True,
              sched_fn=_gated(policy_fn), nodes=4, partitions=1)
    assert off["release"] and off["release"] == on["release"]
    _assert_equivalent(off, on)


def test_batch_gated_release_order_hybrid():
    kw = dict(n=300, seed=14, hybrid=True, priorities=(0, 2))
    off = _run(lambda: _mixed_descs(**kw), as_batch=False, hybrid=True,
               sched_fn=_gated(PriorityPolicy), nodes=4, partitions=1)
    on = _run(lambda: _mixed_descs(**kw), as_batch=True, hybrid=True,
              sched_fn=_gated(PriorityPolicy), nodes=4, partitions=1)
    assert off["release"] and off["release"] == on["release"]
    _assert_equivalent(off, on)


def test_batch_with_deps_falls_back_and_matches():
    # sparse `after` routes the batch through the object gated path; the
    # dependency graph must still release identically
    kw = dict(n=240, seed=15, with_deps=True)
    off = _run(lambda: _mixed_descs(**kw), as_batch=False,
               sched_fn=_gated(lambda: "fifo"), nodes=4, partitions=1)
    on = _run(lambda: _mixed_descs(**kw), as_batch=True,
              sched_fn=_gated(lambda: "fifo"), nodes=4, partitions=1)
    assert off["release"] == on["release"]
    _assert_equivalent(off, on)


def test_batch_ref_rows_are_dependency_targets():
    """A task submitted with `after` pointing into a still-pending gated
    batch row must hold until that row materializes and finishes."""
    with Session(mode="sim", seed=21) as session:
        pilot = PilotManager(session).submit_pilots(
            PilotDescription(nodes=2, backends={"flux": {"partitions": 1}}))
        tm = TaskManager(session,
                         scheduler=CampaignScheduler(policy="fifo",
                                                     admission=True))
        tm.add_pilots(pilot)
        batch = DescriptionBatch.from_template(
            TaskDescription(cores=1, duration=2.0), 50)
        ref = tm.submit_tasks(batch)
        up_uid = batch.uid(40)
        dn = tm.submit_tasks([TaskDescription(cores=1, duration=1.0,
                                              after=(up_uid,))])[0]
        assert tm.wait_tasks(timeout=120)
        assert ref.done and dn.state is TaskState.DONE
        upstream = pilot.agent.tasks[up_uid]
        assert dn.timestamps["RUNNING"] >= upstream.timestamps["DONE"]


# --------------------------------------------------------------------------
# real engine: object-ingestion batch path, function payloads
# --------------------------------------------------------------------------

def test_batch_golden_real_engine_functions():
    def run(as_batch):
        with Session(mode="real", seed=0) as session:
            pilot = PilotManager(session).submit_pilots(
                PilotDescription(nodes=2,
                                 backends={"dragon": {"workers": 4}}))
            tm = TaskManager(session, scheduler=CampaignScheduler(
                policy=PriorityPolicy()))
            tm.add_pilots(pilot)
            descs = [TaskDescription(uid=f"r{int(as_batch)}.{i}",
                                     kind="function", fn=lambda x=i: x * 2,
                                     priority=i % 3)
                     for i in range(40)]
            payload = (DescriptionBatch.from_descriptions(descs)
                       if as_batch else descs)
            tasks = tm.submit_tasks(payload)
            assert tm.wait_tasks(timeout=60)
            tasks = list(tasks)
            prof = session.profiler
            return {
                "results": sorted(t.result for t in tasks),
                "states": [t.state for t in tasks],
                "trace_counts": {k: v for k, v in
                                 prof.counts_by_name().items()
                                 if k.startswith("state:")},
            }

    off, on = run(False), run(True)
    assert off["results"] == on["results"] == [i * 2 for i in range(40)]
    assert all(s is TaskState.DONE for s in on["states"])
    assert off["trace_counts"] == on["trace_counts"]


def test_batch_golden_real_engine_flux_functions():
    def run(as_batch):
        with Session(mode="real", seed=0) as session:
            pilot = PilotManager(session).submit_pilots(
                PilotDescription(nodes=2,
                                 backends={"flux": {"partitions": 1}}))
            tm = TaskManager(session)
            tm.add_pilots(pilot)
            descs = [TaskDescription(uid=f"x{int(as_batch)}.{i}", cores=1,
                                     fn=lambda: None)
                     for i in range(30)]
            payload = (DescriptionBatch.from_descriptions(descs)
                       if as_batch else descs)
            tasks = tm.submit_tasks(payload)
            assert tm.wait_tasks(timeout=60)
            prof = session.profiler
            return {
                "states": [t.state for t in tasks],
                "trace_counts": {k: v for k, v in
                                 prof.counts_by_name().items()
                                 if k.startswith("state:")},
            }

    off, on = run(False), run(True)
    assert all(s is TaskState.DONE for s in off["states"] + on["states"])
    assert off["trace_counts"] == on["trace_counts"]


# --------------------------------------------------------------------------
# property test: random mixed batches (hypothesis when available)
# --------------------------------------------------------------------------

def _property_case(n, seed, hybrid, with_deps, with_sparse, priorities):
    kw = dict(n=n, seed=seed, hybrid=hybrid, with_deps=with_deps,
              with_sparse=with_sparse, priorities=priorities,
              max_duration=1.0)
    off = _run(lambda: _mixed_descs(**kw), as_batch=False, hybrid=hybrid,
               sched_fn=_gated(PriorityPolicy), nodes=4, partitions=1)
    on = _run(lambda: _mixed_descs(**kw), as_batch=True, hybrid=hybrid,
              sched_fn=_gated(PriorityPolicy), nodes=4, partitions=1)
    assert off["release"] == on["release"]
    _assert_equivalent(off, on)


try:
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False


def _uniform_case(n, cores, duration, hybrid):
    """A uniform list against the same tasks as a batch (hybrid: kinds
    alternate) or as one template wave (flux only)."""
    descs = _null_descs(n, hybrid=hybrid, cores=cores, duration=duration)
    off = _run(descs, as_batch=False, hybrid=hybrid, nodes=64, partitions=8)
    wave = (None if hybrid
            else (TaskDescription(cores=cores, duration=duration), n))
    on = _run(descs, as_batch=True, hybrid=hybrid, nodes=64, partitions=8,
              wave=wave)
    _assert_equivalent(off, on)


if _HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(n=st.integers(min_value=500, max_value=1500),
           cores=st.integers(min_value=1, max_value=16),
           duration=st.floats(min_value=0.0, max_value=1.0,
                              allow_nan=False, allow_infinity=False),
           hybrid=st.booleans())
    def test_property_uniform_waves(n, cores, duration, hybrid):
        _uniform_case(n, cores, duration, hybrid)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_uniform_waves():
        pass


def test_property_uniform_waves_seeds_fallback():
    """Seeded stand-in for the uniform-wave sweep (always runs): random
    wave shapes across both configs."""
    rng = random.Random(11)
    for _ in range(4):
        _uniform_case(n=rng.randint(500, 1200),
                      cores=rng.choice((1, 2, 8, 16)),
                      duration=rng.choice((0.0, rng.uniform(0.0, 1.0))),
                      hybrid=rng.random() < 0.5)


if _HAVE_HYPOTHESIS:
    @settings(max_examples=6, deadline=None)
    @given(n=st.integers(min_value=60, max_value=200),
           seed=st.integers(min_value=0, max_value=10_000),
           hybrid=st.booleans(),
           with_deps=st.booleans(),
           with_sparse=st.booleans(),
           priorities=st.sampled_from(((0,), (0, 1), (0, 2, 5))))
    def test_batch_property_random_mixed(n, seed, hybrid, with_deps,
                                         with_sparse, priorities):
        _property_case(n, seed, hybrid, with_deps, with_sparse, priorities)
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_batch_property_random_mixed():
        pass


def test_batch_property_random_seeds_fallback():
    """Seeded stand-in for the hypothesis sweep (always runs)."""
    rng = random.Random(23)
    for _ in range(3):
        _property_case(n=rng.randint(60, 200), seed=rng.randint(0, 10_000),
                       hybrid=rng.random() < 0.5,
                       with_deps=rng.random() < 0.5,
                       with_sparse=rng.random() < 0.5,
                       priorities=rng.choice(((0,), (0, 1), (0, 2, 5))))
