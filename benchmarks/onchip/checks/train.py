"""Training against the plain reference: a sample of the training tasks
that finished in the window, drawn from the seed, trained again by
``reference/training.py`` on ``reference/mamba2.py`` in float32, from the
task's own seed, on the same stream. Each number is the widest over the
sampled tasks:

* ``loss_gap``: the widest gap over the steps between the task's loss and
  the reference's, relative to the reference's;
* ``change_gap``: by the worst leaf, the gap between the task's norm of the
  leaf's change over the steps (from ``train``'s own initial parameters)
  and the reference's, over the larger of the reference's norm for that
  leaf and for the median leaf. Leaves whose first gradient in the reference
is under a thousandth of the median leaf's move by round-off alone and are
left out.
"""
from __future__ import annotations

import numpy as np

from checks.score import sample


def reference_training(m, family, seed, steps, batch, seq, prec="f32"):
    import jax
    from reference import mamba2 as RM
    from reference import training as RT
    if family != "ssm":
        raise NotImplementedError(f"no training reference for {family!r}")
    params = jax.jit(lambda k: RM.init(k, m))(jax.random.PRNGKey(seed))
    return RT.train(params, lambda p, b: RM.loss(p, b, m, prec),
                    lambda p: RM.round_params(p, m), seed=seed, steps=steps,
                    batch=batch, seq_len=seq, vocab=m["vocab_size"])


def loss_gaps(got, ref):
    """Per-step loss gaps relative to the reference's."""
    want = np.asarray(ref["losses"], np.float64)
    return np.abs(np.asarray(got, np.float64) - want) / np.abs(want)


def leaf_gaps(got: dict, want: dict, ref) -> dict:
    """Per leaf, the gap between two norms over the larger of the
    reference's norm for that leaf and for the median leaf; leaves whose
    first gradient in the reference is under a thousandth of the median
    leaf's are left out."""
    g = ref["first_grad"]
    med_g = float(np.median(list(g.values())))
    keep = [k for k in want if g[k] >= 1e-3 * med_g]
    med = float(np.median([want[k] for k in keep]))
    return {k: abs(got[k] - want[k]) / max(want[k], med) for k in keep}


def gaps(got, ref) -> dict:
    """Every compared number of one task: ``got`` holds its ``losses`` and
    its ``change`` (leaf norms)."""
    return {"loss_gap": float(loss_gaps(got["losses"], ref).max()),
            "change_gap": max(leaf_gaps(got["change"], ref["change"],
                                        ref).values())}


def check(run):
    lim = run.cell.limits["train"]
    recs = [r for r in run.tasks if r["payload"] == "train"
            and r["in_window"] and r["state"] == "DONE"]
    pl = run.payloads["train"]
    wrong = sum(1 for r in recs if r["result"]["seed"] != r["seed"]
                or len(r["result"]["losses"]) != pl.steps)
    run.compare("train_answers_misrouted", wrong, 0)
    if not recs:
        run.problem("no training task finished")
        return
    picked = [dict(seed=r["seed"], losses=r["result"]["losses"],
                   change=pl.changes(r["result"]))
              for r in sample(run, recs, int(lim["sample"]), 1)]
    for r in recs:                      # the chip's copies are read
        r["result"].pop("params", None)
    worst = {}
    for got in picked:
        ref = reference_training(pl.model.m, pl.model.family, got["seed"],
                                 pl.steps, pl.batch, pl.seq)
        for k, v in gaps(got, ref).items():
            worst[k] = max(worst.get(k, 0.0), v)
    for k in ("loss_gap", "change_gap"):
        run.compare(k, worst[k], lim[k])
