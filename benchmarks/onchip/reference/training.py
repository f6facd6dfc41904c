"""Plain training of a language model: the synthetic token stream, AdamW
with decoupled weight decay, global-norm clipping and a warmup-cosine
schedule, stepped one batch at a time in float32.

Parameters are held as the configuration stores them: after every update
each is rounded to its stored type (``round_params`` of the model's
reference). The optimizer's moments are float32.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

NO_DECAY = ("scale", "b", "A_log", "D", "dt_bias")


def batch_at(seed: int, step: int, batch: int, seq_len: int, vocab: int
             ) -> Dict[str, np.ndarray]:
    """The synthetic stream's batch ``step``: zipf(1.3) token ids folded into
    [1, vocab - 2], labels shifted by one."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step, 0]))
    z = rng.zipf(1.3, size=(batch, seq_len + 1)).astype(np.int64)
    tokens = (z % (vocab - 2)) + 1
    return {"tokens": tokens[:, :seq_len].astype(np.int32),
            "labels": tokens[:, 1:].astype(np.int32)}


def opt_defaults(steps: int) -> Dict[str, float]:
    return {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
            "weight_decay": 0.1, "clip_norm": 1.0,
            "warmup_steps": max(2, steps // 10),
            "total_steps": max(steps, 2), "min_lr_ratio": 0.1}


def lr_at(o, step: int) -> float:
    warm = min(1.0, (step + 1.0) / max(1, o["warmup_steps"]))
    prog = min(max((step - o["warmup_steps"])
                   / max(1, o["total_steps"] - o["warmup_steps"]), 0.0), 1.0)
    cos = 0.5 * (1.0 + math.cos(math.pi * prog))
    return o["lr"] * warm * (o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * cos)


def _loss_and_grads(loss_fn, params, batch):
    """The batch's mean loss and its gradient, one row at a time (the mean
    of the rows' means: every row has the same length)."""
    rows = batch["tokens"].shape[0]
    per_row = jax.tree.map(lambda x: x[:, None], batch)

    def body(acc, b):
        loss, g = jax.value_and_grad(loss_fn)(params, b)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (loss, g), _ = jax.lax.scan(body, (jnp.float32(0.0), zeros), per_row)
    return loss / rows, jax.tree.map(lambda x: x / rows, g)


def make_step(loss_fn, round_params, o):
    """One jitted step: loss and update from the parameters before it."""

    def step(params, mu, nu, batch, lr, t):
        loss, g = _loss_and_grads(loss_fn, params, batch)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(
            1.0, o["clip_norm"] / (gnorm + 1e-9)), g)
        mu = jax.tree.map(lambda m_, x: o["b1"] * m_ + (1 - o["b1"]) * x, mu, g)
        nu = jax.tree.map(lambda v, x: o["b2"] * v + (1 - o["b2"]) * x * x,
                          nu, g)
        bc1 = 1.0 - o["b1"] ** t
        bc2 = 1.0 - o["b2"] ** t

        def upd(path, p, m_, v):
            u = (m_ / bc1) / (jnp.sqrt(v / bc2) + o["eps"])
            if str(path[-1].key) not in NO_DECAY:
                u = u + o["weight_decay"] * p
            return p - lr * u

        params = round_params(
            jax.tree_util.tree_map_with_path(upd, params, mu, nu))
        return params, mu, nu, loss, gnorm

    return jax.jit(step)


def leaf_norms(tree) -> Dict[str, float]:
    """Float32 norm of each leaf, by its path."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): float(jnp.linalg.norm(
        jnp.ravel(x).astype(jnp.float32))) for k, x in flat}


def train(params, loss_fn, round_params, *, seed: int, steps: int,
          batch: int, seq_len: int, vocab: int) -> Dict:
    """``steps`` steps from ``params`` on the seed's stream: each step's
    loss, the norm of each parameter's change over all steps, and the norm
    of each leaf's first gradient as the optimizer got it (clipped; from
    the first moment after one step)."""
    o = opt_defaults(steps)
    step = make_step(loss_fn, round_params, o)
    p0 = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for s in range(steps):
        b = {k: jnp.asarray(v) for k, v in
             batch_at(seed, s, batch, seq_len, vocab).items()}
        params, mu, nu, loss, _ = step(params, mu, nu, b,
                                       jnp.float32(lr_at(o, s)),
                                       jnp.float32(s + 1))
        losses.append(float(loss))
        if s == 0:
            first_grad = leaf_norms(jax.tree.map(
                lambda m_: m_ / (1 - o["b1"]), mu))
    change = leaf_norms(jax.tree.map(jnp.subtract, params, p0))
    return {"losses": losses, "change": change, "first_grad": first_grad}
