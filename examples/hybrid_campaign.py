"""Mini-IMPECCABLE, for real: the end-to-end hybrid AI-HPC driver.

A scaled-down drug-discovery-style campaign where every task actually
executes on this host through the middleware:
  * docking        -> CPU function tasks (numpy scoring),
  * SST training   -> co-scheduled JAX train steps (executable modality)
                      on a ~100M-param reduced transformer,
  * surrogate inference -> JAX serve steps as function tasks,
  * selection      -> feedback: inference scores pick the next docking batch.

Drives the RP-style Session API with a real (wall-clock) engine: the same
pipeline a simulated campaign runs on, but every task payload executes here.

Run:  PYTHONPATH=src python examples/hybrid_campaign.py [--iterations 2]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core import (PilotDescription, PilotManager, Session,
                        TaskDescription, TaskManager)
from repro.distributed.train_step import make_train_step
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.optim import adamw


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iterations", type=int, default=2)
    ap.add_argument("--docking-batch", type=int, default=16)
    ap.add_argument("--train-steps", type=int, default=3)
    args = ap.parse_args()
    enable_compile_cache()

    # the "SST surrogate": a reduced transformer trained on the fly
    cfg = get_smoke_config("stablelm-3b", d_model=96, num_layers=2)
    key = jax.random.PRNGKey(0)
    state = {"params": M.init_params(key, cfg)}
    state["opt"] = adamw.init(state["params"])
    step = jax.jit(make_train_step(cfg, adamw.OptimizerConfig(
        total_steps=64, warmup_steps=2)))

    session = Session(mode="real")
    pilot = PilotManager(session).submit_pilots(PilotDescription(
        nodes=1, backends={"dragon": {"workers": 4},
                           "flux": {"partitions": 1}}))
    tmgr = TaskManager(session)
    tmgr.add_pilots(pilot)
    rng = np.random.default_rng(0)
    candidates = rng.standard_normal((args.docking_batch, 8))

    def docking(mol):
        # CPU-bound scoring stand-in (AutoDock analogue)
        return float(np.sum(np.sin(mol) ** 2))

    def train_task(batch_tokens, mesh=None):
        B, S = batch_tokens.shape
        batch = {"tokens": jnp.asarray(batch_tokens),
                 "labels": jnp.asarray(batch_tokens),
                 "positions": jnp.broadcast_to(jnp.arange(S)[None], (B, S))}
        loss = None
        for _ in range(args.train_steps):
            state["params"], state["opt"], metrics = step(
                state["params"], state["opt"], batch)
            loss = float(metrics["loss"])
        return loss

    def inference(mol_scores):
        # surrogate inference: forward pass scores the docking results
        toks = jnp.asarray(
            (np.abs(mol_scores) * 1000).astype(np.int32) % cfg.vocab_size
        ).reshape(1, -1)
        pos = jnp.broadcast_to(jnp.arange(toks.shape[1])[None], toks.shape)
        logits, _, _ = M.forward(state["params"], cfg,
                                 {"tokens": toks, "positions": pos},
                                 mode="train")
        return np.asarray(jnp.mean(logits, axis=(-1, -2)))

    t0 = time.time()
    for it in range(args.iterations):
        # stage 1: docking fan-out (dragon modality)
        dock_tasks = tmgr.submit_tasks([
            TaskDescription(kind="function", fn=docking, args=(m,),
                            stage="docking") for m in candidates])
        if not tmgr.wait_tasks(dock_tasks, timeout=300):
            raise TimeoutError("docking stage exceeded 300s")
        scores = np.asarray([t.result for t in dock_tasks])

        # stage 2: surrogate training (flux modality, co-scheduled)
        toks = (np.abs(candidates @ rng.standard_normal((8, 32))) * 100
                ).astype(np.int32) % cfg.vocab_size
        train_task_h = tmgr.submit_tasks(TaskDescription(
            kind="executable", coupling="tight", fn=train_task,
            args=(toks,), stage="sst_train"))
        if not tmgr.wait_tasks([train_task_h], timeout=600):
            raise TimeoutError("sst_train stage exceeded 600s")
        loss = train_task_h.result

        # stage 3: surrogate inference + adaptive selection
        inf_task = tmgr.submit_tasks(TaskDescription(
            kind="function", fn=inference, args=(scores,),
            stage="inference"))
        if not tmgr.wait_tasks([inf_task], timeout=300):
            raise TimeoutError("inference stage exceeded 300s")
        pick = np.argsort(scores)[: args.docking_batch // 2]
        candidates = np.concatenate(
            [candidates[pick],
             rng.standard_normal((args.docking_batch - len(pick), 8))])
        print(f"[campaign] iter {it}: docked {len(dock_tasks)} "
              f"(best {scores.min():.3f}), sst loss {loss:.3f}, "
              f"selected {len(pick)} for refinement")

    all_tasks = pilot.agent.tasks
    n = len(all_tasks)
    done = sum(t.state.value == "DONE" for t in all_tasks.values())
    print(f"[campaign] complete: {done}/{n} tasks in {time.time()-t0:.1f}s; "
          f"backends: {sorted({t.backend for t in all_tasks.values()})}")
    session.close()


if __name__ == "__main__":
    main()
