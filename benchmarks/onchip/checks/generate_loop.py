"""Generation tasks of the closed loop against the plain reference: a sample
of the tasks that finished in the window, drawn from the seed, their
prompts with the served continuations run once through
``reference/zamba2.py`` in float32 at full matmul precision, layer by layer
with weights made from the same seed. At each served position the gap is how
far the served token's logit lies below the reference's best; the number
compared is the widest (``served_gap``). Every task must hand back its own
request id and begin with its own prompts (``generate_loop_misrouted``).
"""
from __future__ import annotations

import numpy as np

from checks.generate import served_gaps
from checks.score import sample


def reference_logits(m, weight_seed, seqs, new, prec="f32"):
    """Logits (rows, new, padded vocab) at the positions that predicted the
    last ``new`` tokens of ``seqs``."""
    import jax
    from reference import zamba2 as RZ
    with jax.default_matmul_precision("highest"):
        return np.asarray(RZ.logits(jax.random.PRNGKey(weight_seed),
                                    seqs[:, :-1], m, prec, tail=new))


def check(run):
    lim = run.cell.limits["generate_loop"]
    pl = run.payloads["generate_loop"]
    recs = [r for r in run.tasks if r["payload"] == "generate_loop"
            and r["in_window"] and r["state"] == "DONE"]
    S, new = pl.prompt_len, pl.new
    wrong = sum(1 for r in recs
                if r["result"][0] != r["rid"]
                or np.shape(r["result"][1]) != (pl.prompts, S + new)
                or not np.array_equal(r["result"][1][:, :S], r["tokens"]))
    run.compare("generate_loop_misrouted", wrong, 0)
    if not recs:
        run.problem("no generation task finished")
        return
    picked = sample(run, recs, int(lim["sample"]), 3)
    seqs = np.concatenate([np.asarray(r["result"][1], np.int32)
                           for r in picked])
    logits = reference_logits(pl.model.m, pl.weight_seed, seqs, new)
    gaps = served_gaps(logits, seqs[:, S:], pl.model.m["vocab_size"])
    run.compare("served_gap", float(gaps.max()), lim["served_gap"])
