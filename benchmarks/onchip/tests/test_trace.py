"""The trace reduction, on a small trace recorded on the CPU."""
import time

import jax
import jax.numpy as jnp

from harness.trace import CPU, reduce_trace


def test_reduces_a_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench:exec:step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench:client:sleep"):
                time.sleep(0.05)
    jax.profiler.stop_trace()
    r = reduce_trace(str(tmp_path), CPU)
    assert r is not None
    assert 0.15 <= r["window_s"] < 5.0
    assert 0.0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"] and all(s > 0 for _, s in r["device_ops"])
    gaps = dict(r["idle_gaps"])
    # the sleeps are idle time, charged to the span the host was in
    assert gaps.get("bench:client:sleep", 0.0) >= 0.1
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10


def test_no_window_span_reads_nothing(tmp_path):
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    assert reduce_trace(str(tmp_path), CPU) is None


def test_an_operation_is_charged_its_own_time():
    from harness.trace import _self_times
    evs = [("while", 0.0, 10.0), ("a", 1.0, 3.0), ("b", 4.0, 5.0),
           ("c", 11.0, 12.0)]
    assert sorted(_self_times(evs)) == [("a", 2.0), ("b", 1.0), ("c", 1.0),
                                        ("while", 7.0)]
