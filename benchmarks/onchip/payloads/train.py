"""Training: the system's ``launch.train.train`` on the task's flux
partition (flux hands the partition's mesh in as ``mesh``), from the task's
own seed. ``train`` makes its parameters and optimizer state and jits its
step on every call, and compiles its init anew for every seed, so each task
pays that inside its exec phase."""
from __future__ import annotations

import functools

from harness import flops

SEED_TAG = 3         # the training seeds' place among the run's seeds


def seed_for(run, it: int) -> int:
    """The seed of the training task of campaign iteration ``it`` (set-up's
    is -1): every task trains on new data from a new initialization, as a
    campaign retraining its surrogate would."""
    return run.derive(SEED_TAG, it + 1)


def leaf_floats(tree) -> dict:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): float(v) for k, v in flat}


def train_task(cfg, steps, batch, seq_len, seed, mesh=None):
    """The task: each step's loss as host values, and the final parameters
    as the last step left them on the chip (the check reads their change
    after the window)."""
    import jax
    from repro.launch.train import train
    with jax.profiler.TraceAnnotation("bench:exec:train"):
        out = train(cfg, steps=steps, global_batch=batch, seq_len=seq_len,
                    mesh=mesh, seed=seed, quiet=True)
        return {"seed": seed, "losses": [float(x) for x in out["losses"]],
                "params": out["params"], "mesh": mesh}


def initial_params(cfg, batch, seq_len, seed, mesh):
    """``train``'s own initial parameters for ``seed`` on ``mesh``: the
    same call with no step, so the same init program."""
    from repro.launch.train import train
    return train(cfg, steps=0, global_batch=batch, seq_len=seq_len,
                 mesh=mesh, seed=seed, quiet=True)["params"]


@functools.lru_cache(maxsize=None)
def _change_norms():
    import jax
    import jax.numpy as jnp

    def norms(final, init):
        return jax.tree.map(lambda a, b: jnp.linalg.norm(jnp.ravel(
            a.astype(jnp.float32) - b.astype(jnp.float32))), final, init)
    return jax.jit(norms)


def change_norms(cfg, batch, seq_len, result) -> dict:
    """The norm of each leaf's change over a task's steps, from ``train``'s
    own initial parameters."""
    init = initial_params(cfg, batch, seq_len, result["seed"],
                          result["mesh"])
    return leaf_floats(_change_norms()(result["params"], init))


class Payload:
    name = "train"

    def __init__(self, run, spec):
        self.run = run
        self.model = run.model(spec["model"])
        self.steps = int(spec["steps"])
        self.batch = int(spec["batch"])
        self.seq = int(spec["seq_len"])
        self.tokens = self.steps * self.batch * self.seq
        self.flops = flops.train(self.model.m, self.batch, self.seq,
                                 self.steps, self.model.family)
        self.fn = train_task

    def args(self, seed: int):
        return (self.model.cfg, self.steps, self.batch, self.seq, seed)

    def changes(self, result) -> dict:
        return change_norms(self.model.cfg, self.batch, self.seq, result)

    def setup(self):
        """Nothing to hold: each task builds its own state. A driver warms
        ``train``'s programs by running one task through the runtime."""

    def free(self):
        pass
