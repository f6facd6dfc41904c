"""Mixture-of-Experts layer: top-k routing, capacity-based scatter/gather
dispatch (PAX/MaxText style — no O(T^2) one-hot dispatch matmuls), optional
shared experts (DeepSeek), load-balance aux loss.

Experts are stacked (E, ...) so they shard over the ``model`` mesh axis
(expert parallelism); tokens are grouped along the batch dim so routing stays
group-local and the expert GEMM resharding is the only cross-shard exchange
(the all-to-all of classic EP).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import init_linear, linear, init_mlp, mlp, act


def init_moe(key, cfg: ModelConfig, dtype):
    E, d, ff = cfg.num_experts, cfg.d_model, cfg.d_ff_expert
    ks = jax.random.split(key, 5)
    std_in = 1.0 / math.sqrt(d)
    std_out = 1.0 / math.sqrt(ff * 2 * cfg.num_layers)
    p = {
        "router": {"w": (std_in * jax.random.truncated_normal(
            ks[0], -2, 2, (d, E))).astype(jnp.float32)},
        "w_in": (std_in * jax.random.truncated_normal(
            ks[1], -2, 2, (E, d, ff))).astype(dtype),
        "w_gate": (std_in * jax.random.truncated_normal(
            ks[2], -2, 2, (E, d, ff))).astype(dtype),
        "w_out": (std_out * jax.random.truncated_normal(
            ks[3], -2, 2, (E, ff, d))).astype(dtype),
    }
    if cfg.num_shared_experts:
        import dataclasses
        shared_cfg = dataclasses.replace(cfg, gated_mlp=True)
        p["shared"] = init_mlp(ks[4], shared_cfg,
                               cfg.num_shared_experts * ff, dtype)
    return p


def moe_capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    c = int(math.ceil(tokens_per_group / cfg.num_experts
                      * cfg.capacity_factor * cfg.top_k))
    return max(cfg.top_k, min(c, tokens_per_group))


def _route(logits: jnp.ndarray, cfg: ModelConfig):
    """logits (G, Tg, E) f32 -> (probs, top_p (G,Tg,K), top_i (G,Tg,K))."""
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)   # renormalize
    return probs, top_p, top_i


def moe_apply(p, x: jnp.ndarray, cfg: ModelConfig
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, d) -> (y (B, S, d), aux_loss scalar).

    Token groups: one group per batch row when S > 1 (train/prefill), a single
    global group for decode (S == 1).
    """
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.top_k
    if S > 1:
        G, Tg = B, S
        xg = x
    else:
        G, Tg = 1, B
        xg = x.reshape(1, B, d)

    C = moe_capacity(cfg, Tg)
    logits = (xg.astype(jnp.float32) @ p["router"]["w"])        # (G,Tg,E)
    probs, top_p, top_i = _route(logits, cfg)

    # --- slot assignment: k-priority, token-order within expert -------------
    counts = jnp.zeros((G, E), jnp.int32)
    dests, keeps, gates = [], [], []
    for j in range(K):
        mask_j = jax.nn.one_hot(top_i[..., j], E, dtype=jnp.int32)   # (G,Tg,E)
        pos_j = jnp.cumsum(mask_j, axis=1) - 1 + counts[:, None, :]
        pos_in_e = jnp.sum(pos_j * mask_j, axis=-1)                  # (G,Tg)
        counts = counts + jnp.sum(mask_j, axis=1)
        keep = pos_in_e < C
        e_j = top_i[..., j]
        dest = jnp.where(keep, e_j * C + pos_in_e, E * C)            # dump slot
        dests.append(dest)
        keeps.append(keep)
        gates.append(top_p[..., j] * keep)

    # --- scatter tokens into expert buffers (G, E, C, d) ---------------------
    n_slots = (E + 1) * C                                            # +dump expert
    buf = jax.vmap(lambda xg_, *ds: _scatter(xg_, ds, n_slots))(xg, *dests)
    x_e = buf.reshape(G, E + 1, C, d)[:, :E]                         # (G,E,C,d)
    if cfg.moe_dispatch_constraint:
        # pin the expert-parallel layout: groups stay data-sharded, experts
        # model-sharded -> the reshard is a single all-to-all-shaped exchange
        from jax.sharding import PartitionSpec as _P
        try:
            x_e = jax.lax.with_sharding_constraint(
                x_e, _P("data" if G > 1 else None, "model", None, None))
        except ValueError:
            # under shard_map manual over the data axis (int8-compressed
            # grads path) only the model axis is Auto-visible
            x_e = jax.lax.with_sharding_constraint(
                x_e, _P(None, "model", None, None))

    # --- expert GEMMs ---------------------------------------------------------
    h = jnp.einsum("gecd,edf->gecf", x_e, p["w_in"])
    g = jnp.einsum("gecd,edf->gecf", x_e, p["w_gate"])
    h = act(cfg.act, g) * h
    y_e = jnp.einsum("gecf,efd->gecd", h, p["w_out"])                # (G,E,C,d)

    # --- gather back ----------------------------------------------------------
    y_flat = jnp.concatenate(
        [y_e.reshape(G, E * C, d), jnp.zeros((G, C, d), y_e.dtype)], axis=1)
    out = jnp.zeros_like(xg)
    for j in range(K):
        picked = jnp.take_along_axis(y_flat, dests[j][..., None], axis=1)
        out = out + picked * gates[j][..., None].astype(picked.dtype)

    # --- shared experts --------------------------------------------------------
    if "shared" in p:
        out = out + mlp(p["shared"], xg, cfg)

    # --- load-balance aux loss (Switch-style) -----------------------------------
    frac = jnp.mean(jax.nn.one_hot(top_i[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = cfg.router_aux_coef * E * jnp.sum(frac * mean_prob)

    return out.reshape(B, S, d), aux


def _scatter(x_g: jnp.ndarray, dests, n_slots: int) -> jnp.ndarray:
    buf = jnp.zeros((n_slots, x_g.shape[-1]), x_g.dtype)
    for dest in dests:
        buf = buf.at[dest].add(x_g)
    return buf
