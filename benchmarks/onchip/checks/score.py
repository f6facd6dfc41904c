"""Scores against the plain reference: a sample of the scoring tasks that
finished, drawn from the seed, scored again by ``reference/mamba2.py`` in
float32 on the same token ids and weights made from the same seed. The
number compared is the widest absolute gap in nats (``score_gap``).
"""
from __future__ import annotations

import functools

import numpy as np

from harness.seeds import rng

BLOCK = 16           # rows per reference call


@functools.lru_cache(maxsize=None)
def _scorer(m_items, prec):
    import jax
    from reference import mamba2 as RM
    m = dict(m_items)
    return (jax.jit(lambda key: RM.init(key, m)),
            jax.jit(lambda p, tok: RM.mean_logprob(
                RM.logits(p, tok, m, prec), tok)))


def reference_scores(m, weight_seed, tokens, prec="f32"):
    """Mean log-probabilities of ``tokens`` (rows, S) under the reference."""
    import jax
    init, score = _scorer(tuple(sorted(m.items())), prec)
    params = init(jax.random.PRNGKey(weight_seed))
    out = [np.asarray(score(params, tokens[i:i + BLOCK]))
           for i in range(0, len(tokens), BLOCK)]
    return np.concatenate(out)


def sample(run, recs, n, tag):
    pick = rng(run.seed, 9, tag).choice(len(recs), size=min(n, len(recs)),
                                        replace=False)
    return [recs[i] for i in sorted(pick)]


def check(run):
    lim = run.cell.limits["score"]
    recs = [r for r in run.tasks if r["payload"] == "score"
            and r["in_window"] and r["state"] == "DONE"]
    wrong = sum(1 for r in recs if r["result"][0] != r["rid"]
                or np.shape(r["result"][1]) != (r["tokens"].shape[0],))
    run.compare("score_answers_misrouted", wrong, 0)
    if not recs:
        run.problem("no scoring task finished")
        return
    pl = run.payloads["score"]
    picked = sample(run, recs, int(lim["sample"]), 0)
    tokens = np.concatenate([r["tokens"] for r in picked])
    got = np.concatenate([np.asarray(r["result"][1], np.float64)
                          for r in picked])
    want = reference_scores(pl.model.m, pl.weight_seed, tokens)
    run.compare("score_gap", float(np.max(np.abs(got - want))),
                lim["score_gap"])
