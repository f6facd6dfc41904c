"""Mamba2 (SSD) block: projections, causal depthwise convs (with bias where
``cfg.ssm_conv_bias``), SSD scan, gated RMSNorm (per B/C group), output
projection. Full-sequence (train/prefill) and single-step (decode) paths
share parameters.

Deviation from the published fused implementation:
z/x/B/C/dt use separate projection matrices and x/B/C separate depthwise convs
— mathematically identical to the fused in_proj/conv (depthwise convs are
per-channel), but each tensor gets a clean mesh sharding.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (group_rmsnorm, init_linear, init_rmsnorm,
                                 linear)


def init_mamba2(key, cfg: ModelConfig, dtype):
    d, di = cfg.d_model, cfg.ssm_d_inner
    H, P, G, N, K = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                     cfg.ssm_state, cfg.ssm_conv)
    ks = jax.random.split(key, 10)
    # dt bias: softplus^-1 of dt ~ Uniform[1e-3, 0.1]
    dt_init = jnp.exp(jax.random.uniform(ks[0], (H,),
                      minval=math.log(1e-3), maxval=math.log(0.1)))
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))
    A_log = jnp.log(jax.random.uniform(ks[1], (H,), minval=1.0, maxval=16.0))
    std_conv = 1.0 / math.sqrt(K)
    p = {
        "wz": init_linear(ks[2], d, di, dtype),
        "wx": init_linear(ks[3], d, di, dtype),
        "wB": init_linear(ks[4], d, G * N, dtype),
        "wC": init_linear(ks[5], d, G * N, dtype),
        "wdt": init_linear(ks[6], d, H, dtype),
        "conv_x": (std_conv * jax.random.normal(ks[7], (K, di))).astype(dtype),
        "conv_B": (std_conv * jax.random.normal(ks[8], (K, G * N))).astype(dtype),
        "conv_C": (std_conv * jax.random.normal(ks[9], (K, G * N))).astype(dtype),
        "A_log": A_log.astype(jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "norm": init_rmsnorm(di, dtype),
        "w_out": init_linear(jax.random.fold_in(key, 99), di, d, dtype,
                             stddev=1.0 / math.sqrt(di * 2 * cfg.num_layers)),
    }
    if cfg.ssm_conv_bias:
        # a depthwise convolution's default: U(-1/sqrt(K), 1/sqrt(K))
        for i, (name, c) in enumerate((("conv_x", di), ("conv_B", G * N),
                                       ("conv_C", G * N))):
            p[name + "_bias"] = jax.random.uniform(
                jax.random.fold_in(key, 100 + i), (c,), minval=-std_conv,
                maxval=std_conv).astype(dtype)
    return p


def causal_conv(x: jnp.ndarray, w: jnp.ndarray, b=None) -> jnp.ndarray:
    """Depthwise causal conv. x (B, S, C), w (K, C), bias b (C,) or None
    -> (B, S, C)."""
    K = w.shape[0]
    S = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = jnp.zeros_like(x)
    for k in range(K):
        y = y + w[k][None, None, :] * jax.lax.dynamic_slice_in_dim(xp, k, S, axis=1)
    return y if b is None else y + b


def causal_conv_step(x_t: jnp.ndarray, w: jnp.ndarray, cache: jnp.ndarray,
                     b=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x_t (B, C), cache (B, K-1, C) of previous inputs -> (y_t, new_cache)."""
    K = w.shape[0]
    window = jnp.concatenate([cache, x_t[:, None, :]], axis=1)     # (B,K,C)
    y = jnp.einsum("bkc,kc->bc", window, w)
    return (y if b is None else y + b), window[:, 1:, :]


def _ssd_dispatch(cfg: ModelConfig, x4, dt, A, B4, C4, h0=None,
                  return_state: bool = True):
    from repro.kernels.ssd import ops as ssd_ops
    return ssd_ops.ssd(x4, dt, A, B4, C4, chunk=cfg.ssm_chunk,
                       use_pallas=cfg.use_pallas, h0=h0,
                       precision=cfg.ssd_precision,
                       return_state=return_state)


def mamba2_full(p, x, cfg: ModelConfig, *, return_cache: bool = False):
    """Full-sequence SSD block. x (B, S, d) -> (y, cache or None)."""
    B, S, _ = x.shape
    H, P, G, N, K = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                     cfg.ssm_state, cfg.ssm_conv)
    di = cfg.ssm_d_inner
    z = linear(p["wz"], x)
    xin_raw = linear(p["wx"], x)
    B_raw = linear(p["wB"], x)
    C_raw = linear(p["wC"], x)
    dt_raw = linear(p["wdt"], x)

    xin = jax.nn.silu(causal_conv(xin_raw, p["conv_x"], p.get("conv_x_bias")))
    Bc = jax.nn.silu(causal_conv(B_raw, p["conv_B"], p.get("conv_B_bias")))
    Cc = jax.nn.silu(causal_conv(C_raw, p["conv_C"], p.get("conv_C_bias")))
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])

    x4 = xin.reshape(B, S, H, P)
    B4 = Bc.reshape(B, S, G, N)
    C4 = Cc.reshape(B, S, G, N)
    A = -jnp.exp(p["A_log"])

    y4, h_final = _ssd_dispatch(cfg, x4, dt, A, B4, C4,
                                return_state=return_cache)
    y4 = y4 + (p["D"][None, None, :, None] * x4.astype(jnp.float32)).astype(y4.dtype)

    y = y4.reshape(B, S, di)
    y = group_rmsnorm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps, G)
    out = linear(p["w_out"], y)

    cache = None
    if return_cache:
        cache = {
            "conv_x": _tail(xin_raw, K - 1),
            "conv_B": _tail(B_raw, K - 1),
            "conv_C": _tail(C_raw, K - 1),
            "state": h_final.astype(jnp.float32),
        }
    return out, cache


def _tail(t: jnp.ndarray, n: int) -> jnp.ndarray:
    """Last n positions along axis 1, left-padded with zeros if S < n."""
    S = t.shape[1]
    if S >= n:
        return t[:, S - n:, :]
    return jnp.pad(t, ((0, 0), (n - S, 0), (0, 0)))


def mamba2_decode(p, x, cfg: ModelConfig, cache):
    """Single-token decode. x (B, 1, d), cache dict -> (y (B,1,d), new_cache)."""
    from repro.kernels.ssd.ref import ssd_step
    B = x.shape[0]
    H, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    di = cfg.ssm_d_inner
    xt = x[:, 0, :]
    z = linear(p["wz"], xt)
    xin_raw = linear(p["wx"], xt)
    B_raw = linear(p["wB"], xt)
    C_raw = linear(p["wC"], xt)
    dt_raw = linear(p["wdt"], xt)

    xin, conv_x = causal_conv_step(xin_raw, p["conv_x"], cache["conv_x"],
                                   p.get("conv_x_bias"))
    Bc, conv_B = causal_conv_step(B_raw, p["conv_B"], cache["conv_B"],
                                  p.get("conv_B_bias"))
    Cc, conv_C = causal_conv_step(C_raw, p["conv_C"], cache["conv_C"],
                                  p.get("conv_C_bias"))
    xin, Bc, Cc = jax.nn.silu(xin), jax.nn.silu(Bc), jax.nn.silu(Cc)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])

    A = -jnp.exp(p["A_log"])
    y3, h = ssd_step(xin.reshape(B, H, P), dt, A,
                     Bc.reshape(B, G, N), Cc.reshape(B, G, N), cache["state"])
    y3 = y3 + (p["D"][None, :, None]
               * xin.reshape(B, H, P).astype(jnp.float32)).astype(y3.dtype)
    y = y3.reshape(B, di)
    y = group_rmsnorm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps, G)
    out = linear(p["w_out"], y)[:, None, :]
    new_cache = {"conv_x": conv_x, "conv_B": conv_B, "conv_C": conv_C, "state": h}
    return out, new_cache
