"""Served tokens against the plain reference: a sample of the inference
tasks that finished, drawn from the seed. ``reference/dense_lm.py`` runs
once in float32 over each prompt with its served tokens, layer by layer,
with weights made from the same seed; at each served position the gap is
how far the served token's logit lies below the reference's best. The
number compared is the widest gap (``served_gap``). Every task's output
must also begin with its own prompt.
"""
from __future__ import annotations

import functools

import numpy as np

from checks.score import sample


@functools.lru_cache(maxsize=None)
def _layer_fn(m_items, prec):
    import jax
    from reference import dense_lm as RD
    m = dict(m_items)
    return jax.jit(lambda key, x: RD.layer(RD.layer_init(key, m), x, m, prec))


def reference_logits(m, weight_seed, seqs, new, prec="f32"):
    """Logits (rows, new, padded vocab) at the positions that predicted the
    last ``new`` tokens of ``seqs``."""
    import jax
    from reference import dense_lm as RD
    return np.asarray(RD.logits(
        jax.random.PRNGKey(weight_seed), seqs[:, :-1], m, prec,
        layer_fn=_layer_fn(tuple(sorted(m.items())), prec), tail=new))


def served_gaps(logits, served, vocab):
    lg = logits[..., :vocab]
    best = lg.max(axis=-1)
    got = np.take_along_axis(lg, served[..., None], axis=-1)[..., 0]
    return best - got


def check(run):
    lim = run.cell.limits["generate"]
    pl = run.payloads["generate"]
    recs = [r for r in run.tasks if r["payload"] == "generate"
            and r["in_window"] and r["state"] == "DONE"]
    S, new = pl.prompt_len, pl.new
    wrong = sum(1 for r in recs
                if np.shape(r["result"]) != (pl.prompts, S + new)
                or not np.array_equal(r["result"][:, :S], r["prompts"]))
    run.compare("generate_answers_misrouted", wrong, 0)
    if not recs:
        run.problem("no inference task finished")
        return
    picked = sample(run, recs, int(lim["sample"]), 2)
    seqs = np.concatenate([np.asarray(r["result"], np.int32) for r in picked])
    logits = reference_logits(pl.model.m, pl.weight_seed, seqs, new)
    gaps = served_gaps(logits, seqs[:, S:], pl.model.m["vocab_size"])
    run.compare("served_gap", float(gaps.max()), lim["served_gap"])
