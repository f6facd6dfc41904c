"""``generate.zamba2`` driven whole on the CPU at a small size, the chip's
look skipped: a sound run is ``correct`` and reports its per-layer metrics,
and a run whose served path departs from the published block, planted in
the system's own code or weights, is not: one shared block serving every
invocation, x0 left out of the blocks' input, the invocations' adapters
left out, and the gated norm taken over all channels at once."""
import io
import json
import time

import jax.numpy as jnp
import pytest

from tests.small import shrink

# the published layout at a CPU size: two blocks, irregular ids, 2 groups
ZAMBA2 = dict(num_layers=6, hybrid_layer_ids=[1, 3, 4], num_mem_blocks=2,
              adapter_rank=8, d_model=64, num_heads=4, num_kv_heads=4,
              head_dim=32, d_ff=128, vocab_size=300, vocab_pad_multiple=32,
              ssm_state=16, ssm_head_dim=16, ssm_chunk=32, ssm_groups=2,
              use_pallas=False)


def small_zamba2(run):
    shrink(run)
    run.cell.config["models"]["zamba2"]["sizes"].update(ZAMBA2)
    run.cell.traffic.update(clients=4)
    run.cell.traffic["task"].update(prompts=2, prompt_len=32, new_tokens=6)


def run_zamba2(extra=None, trace=False):
    from harness import spec as S
    from harness.cell import execute
    from repro.launch import serve

    def tweak(run):
        small_zamba2(run)
        if extra is not None:
            extra(run)

    serve.serve_steps.cache_clear()        # trace the planted fault anew
    out, err = io.StringIO(), io.StringIO()
    try:
        rc = execute("generate.zamba2", 2**31 + 9, 1.0, trace,
                     t_process=time.perf_counter(), require_tpu=False,
                     bench=S.load_benchmark(), out=out, err=err, tweak=tweak)
    finally:
        serve.serve_steps.cache_clear()
    assert rc == 0, err.getvalue()[-2000:]
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct():
    res = run_zamba2(trace=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    m = res["metrics"]
    for name in ("prefill_ms.zamba2", "decode_step_ms.zamba2", "mfu.zamba2"):
        assert m[name]["value"] > 0


def _params_planted(monkeypatch, change):
    import repro.models.model as M
    init = M.init_params

    def planted(key, cfg):
        return change(init(key, cfg))

    monkeypatch.setattr(M, "init_params", planted)


def _one_block(p):
    return {**p, "shared": (p["shared"][0],) * len(p["shared"])}


def _no_adapters(p):
    return {**p, "invocations": tuple(
        {**inv, "lora_b": {"w": jnp.zeros_like(inv["lora_b"]["w"])}}
        for inv in p["invocations"])}


def _no_x0(monkeypatch):
    import repro.models.model as M
    block = M.shared_block

    def planted(p, inv, h, x0, *a, **k):
        return block(p, inv, h, jnp.zeros_like(x0), *a, **k)

    monkeypatch.setattr(M, "shared_block", planted)


def _ungrouped_norm(monkeypatch):
    import repro.models.ssm as SSM
    norm = SSM.group_rmsnorm
    monkeypatch.setattr(SSM, "group_rmsnorm",
                        lambda p, x, eps, groups: norm(p, x, eps, 1))


@pytest.mark.parametrize("fault", ["one_block", "no_adapters", "no_x0",
                                   "ungrouped_norm"])
def test_planted_fault_fails(monkeypatch, fault):
    if fault == "one_block":
        _params_planted(monkeypatch, _one_block)
    elif fault == "no_adapters":
        _params_planted(monkeypatch, _no_adapters)
    elif fault == "no_x0":
        _no_x0(monkeypatch)
    else:
        _ungrouped_norm(monkeypatch)
    res = run_zamba2()
    assert not res["correct"]
    c = res["checks"]["served_gap"]
    assert c["value"] > c["limit"], c


def test_chip_turns_are_granted_in_the_order_asked():
    import threading
    from payloads.generate_loop import Turns

    turns, order = Turns(), []
    turns.__enter__()                       # the chip is busy
    waiting = []
    for i in range(4):
        def ask(i=i):
            with turns:
                order.append(i)
        t = threading.Thread(target=ask)
        t.start()
        while turns._asked < i + 2:         # this one has asked
            time.sleep(0.001)
        waiting.append(t)
    turns.__exit__(None, None, None)
    for t in waiting:
        t.join(10)
    assert order == [0, 1, 2, 3]


def test_a_failed_task_gives_its_turn_up():
    from payloads.generate_loop import Turns

    turns = Turns()
    with pytest.raises(ValueError):
        with turns:
            raise ValueError("the task failed")
    with turns:
        pass
    assert turns._serving == 2
