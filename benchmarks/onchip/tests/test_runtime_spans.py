"""The readers of the real engine's own stamps and spans: the three stamp
metrics on synthetic records (and nothing read from a program without the
stamps), the device-idle time under ``rp:*`` host spans on a CPU trace, and
a whole traced run whose stamps tile the lifecycle phases."""
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from harness import spec as S
from harness.spans import program_idle
from harness.trace import CPU, reduce_trace
from tests.small import run_cell

STAMPED = ("dispatch_sleep_ms.stream", "pool_wait_ms.stream",
           "lock_wait_ms.stream")


def _reader(name):
    return S.load_module("metrics", name)


def _record(stamps, state="DONE", payload="score", seen_t=5.0):
    return dict(state=state, in_window=True, seen_t=seen_t, payload=payload,
                stamps=stamps)


def _stamps(t, sleep, pool, lock0, run, device, lock1):
    """One task's stamps from the lengths of its intervals (s)."""
    s = {"SCHEDULING": t}
    s["tick_due"] = s["SCHEDULING"] + sleep
    s["QUEUED"] = s["tick_due"] + 0.0001
    s["picked"] = s["QUEUED"] + pool
    s["LAUNCHING"] = s["RUNNING"] = s["picked"] + lock0
    s["returned"] = s["RUNNING"] + run
    s["ready"] = s["returned"] + device
    s["DONE"] = s["ready"] + lock1
    return s


@pytest.mark.parametrize("name,expect_ms", [
    ("dispatch_sleep_ms.stream", (1.0 + 3.0) / 2),
    ("pool_wait_ms.stream", (80.0 + 90.0) / 2),
    ("lock_wait_ms.stream", (0.2 + 0.1 + 0.4 + 0.3) / 2)])
def test_stamp_readers_on_synthetic_records(name, expect_ms):
    run = SimpleNamespace(window=(0.0, 10.0), tasks=[
        _record(_stamps(1.0, 0.001, 0.080, 0.0002, 0.005, 0.001, 0.0001)),
        _record(_stamps(2.0, 0.003, 0.090, 0.0004, 0.005, 0.002, 0.0003)),
        # not counted: failed, another payload, seen after the window
        _record(_stamps(3.0, 9, 9, 9, 9, 9, 9), state="FAILED"),
        _record(_stamps(3.0, 9, 9, 9, 9, 9, 9), payload="train"),
        _record(_stamps(3.0, 9, 9, 9, 9, 9, 9), seen_t=11.0)])
    assert _reader(name).read(run) == pytest.approx(expect_ms, rel=1e-9)


@pytest.mark.parametrize("name", STAMPED)
def test_stamp_readers_read_nothing_without_the_stamps(name):
    """A program that stamps only the states (the engine before these
    stamps) gives no value, and no error."""
    states = ("SCHEDULING", "QUEUED", "LAUNCHING", "RUNNING", "DONE")
    run = SimpleNamespace(window=(0.0, 10.0), tasks=[
        _record({k: float(i) for i, k in enumerate(states)})])
    assert _reader(name).read(run) is None
    assert _reader(name).read(SimpleNamespace(window=(0.0, 10.0),
                                              tasks=[])) is None


def test_program_idle_on_a_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("rp:exec:payload"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench:client:sleep"):
                with jax.profiler.TraceAnnotation("rp:lock:wait"):
                    time.sleep(0.05)
    jax.profiler.stop_trace()
    r = reduce_trace(str(tmp_path), CPU)
    idle = program_idle(str(tmp_path), CPU)
    assert set(idle) == {"rp:exec:payload", "rp:lock:wait"}
    # the sleeps are idle time under the lock-wait span, and every span's
    # idle seconds lie inside the window's idle seconds
    assert idle["rp:lock:wait"] >= 0.1
    for v in idle.values():
        assert 0.0 <= v <= r["window_s"] - r["busy_s"] + 1e-9
    # the rp: spans leave the bench: labelling of the idle gaps as it was
    assert not any(n.startswith("rp:") for n, _ in r["idle_gaps"])
    assert dict(r["idle_gaps"]).get("bench:client:sleep", 0.0) >= 0.1


def test_program_idle_without_a_window_reads_nothing(tmp_path):
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("rp:exec:payload"):
        f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    assert program_idle(str(tmp_path), CPU) is None


def test_traced_stream_run_reads_the_stamps_and_spans():
    kept = {}

    def keep(run):
        reduce = run.reduce_trace

        def read_then_reduce():
            if run._trace_dir is not None:
                kept["idle"] = program_idle(run._trace_dir, CPU)
            reduce()
        run.reduce_trace = read_then_reduce

    res, _ = run_cell("stream.short", trace=True, extra=keep)
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    for name in STAMPED:
        assert res["metrics"][name]["unit"] == "ms"
        assert m[name] >= 0.0
    # the stamps tile the phases read from the state stamps
    assert m["dispatch_sleep_ms.stream"] <= m["dispatch_ms.stream"]
    assert m["pool_wait_ms.stream"] <= m["queue_ms.stream"]
    assert m["lock_wait_ms.stream"] < m["queue_ms.stream"] + m["exec_ms.stream"]
    for name in ("rp:submit", "rp:dispatch", "rp:exec:payload",
                 "rp:exec:commit"):
        assert name in kept["idle"], sorted(kept["idle"])

