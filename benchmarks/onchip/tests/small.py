"""Small sizes at which the tests drive whole runs on the CPU."""
from __future__ import annotations

import time

from harness import device as D

SSM = dict(num_layers=2, d_model=64, vocab_size=300, vocab_pad_multiple=32,
           ssm_state=16, ssm_head_dim=16, ssm_chunk=32, use_pallas=False)
DENSE = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
             head_dim=16, d_ff=128, vocab_size=300, vocab_pad_multiple=32,
             use_pallas=False)


def shrink(run):
    """Every model and traffic of the run at a size the CPU runs in
    seconds; the CPU gets a stand-in peak so that shares can be read."""
    D.PEAKS.setdefault("cpu", {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11,
                               "hbm_bytes": 1e10})
    for e in run.cell.config["models"].values():
        e["sizes"].update(SSM if e["family"] == "ssm" else DENSE)
    t = run.cell.traffic
    if t["kind"] == "closed_loop":
        t.update(clients=4)
        t["task"]["seq_len"] = 64
    if t["kind"] == "campaign":
        for st in t["stages"]:
            if st["payload"] == "docking":
                st["count"] = 16
            if st["payload"] == "train":
                # few rows, so that leaving half of them out shows
                st.update(steps=4, batch=2, seq_len=32)
            if st["payload"] == "generate":
                st.update(prompts=2, prompt_len=32, new_tokens=6)


def run_cell(workload, seed=2**31 + 5, seconds=1.0, trace=False, extra=None):
    """Drive a whole run of ``workload`` on the CPU; returns its result."""
    import io
    import json
    from harness import spec as S
    from harness.cell import execute

    def tweak(run):
        shrink(run)
        if extra is not None:
            extra(run)

    out, err = io.StringIO(), io.StringIO()
    rc = execute(workload, seed, seconds, trace, t_process=time.perf_counter(),
                 require_tpu=False, bench=S.with_held(S.load_benchmark()),
                 out=out, err=err, tweak=tweak)
    assert rc == 0, err.getvalue()[-2000:]
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()
