"""Quickstart: the three layers of the framework in ~60 lines.

1. simulate a paper-scale runtime experiment (srun vs flux),
2. train a small LM for a few steps on this host,
3. push a hybrid task mix through the real middleware.

Both 1. and 3. go through the same RP-style Session API — only the session
``mode`` ("sim" vs "real") swaps the execution substrate.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp

from repro.core import (PilotDescription, Session, PilotManager, TaskManager,
                        TaskDescription, compute_metrics)
from repro.configs import get_smoke_config


def sim_experiment():
    print("== 1. simulated runtime experiment (4 Frontier nodes) ==")
    for backend in ({"srun": {}}, {"flux": {"partitions": 2}}):
        with Session(mode="sim", seed=0) as session:
            pilot = PilotManager(session).submit_pilots(
                PilotDescription(nodes=4, backends=backend))
            tmgr = TaskManager(session)
            tmgr.add_pilots(pilot)
            tmgr.submit_tasks([TaskDescription(cores=1, duration=180.0)
                               for _ in range(896)])
            tmgr.wait_tasks()
            agent = pilot.agent
            m = compute_metrics(list(agent.tasks.values()), agent.total_cores)
        name = list(backend)[0]
        print(f"  {name:5s}: makespan={m.makespan:7.0f}s "
              f"util={m.utilization:.2f} peak_conc={m.concurrency_peak}")


def tiny_training():
    print("== 2. real training (reduced gemma-7b family config) ==")
    from repro.launch.train import train
    cfg = get_smoke_config("gemma-7b")
    out = train(cfg, steps=5, global_batch=2, seq_len=32, quiet=True)
    print(f"  5 steps, loss {out['losses'][0]:.3f} -> {out['final_loss']:.3f}")


def hybrid_middleware():
    print("== 3. hybrid task mix through the real middleware ==")
    with Session(mode="real") as session:
        pilot = PilotManager(session).submit_pilots(PilotDescription(
            nodes=1, backends={"dragon": {"workers": 2},
                               "flux": {"partitions": 1},
                               "popen": {}}))
        tmgr = TaskManager(session)
        tmgr.add_pilots(pilot)
        tasks = tmgr.submit_tasks(
            [TaskDescription(kind="function",
                             fn=lambda i=i: float(jnp.sum(jnp.arange(i + 1))))
             for i in range(4)]
            + [TaskDescription(kind="executable",
                               fn=lambda: "co-scheduled step done")]
            + [TaskDescription(kind="executable", executable="uname",
                               arguments=("-s",))])
        tmgr.wait_tasks(timeout=60)
        print(f"  {sum(t.state.value == 'DONE' for t in tasks)}/6 tasks done; "
              f"backends used: {sorted({t.backend for t in tasks})}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sim_experiment()
    tiny_training()
    hybrid_middleware()
