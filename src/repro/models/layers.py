"""Shared layer primitives: norms, linears, embeddings, positional encodings.

Pure-functional: params are nested dicts of jnp arrays; every ``init_*`` returns a
pytree, every ``apply`` is a pure function of (params, inputs).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


def truncated_normal_init(key, shape, stddev, dtype):
    return (stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


def init_linear(key, d_in: int, d_out: int, dtype, *, bias: bool = False,
                stddev: Optional[float] = None):
    stddev = stddev if stddev is not None else 1.0 / math.sqrt(d_in)
    p = {"w": truncated_normal_init(key, (d_in, d_out), stddev, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


# --------------------------------------------------------------------------- norm
def init_rmsnorm(d: int, dtype):
    return {"scale": jnp.zeros((d,), dtype)}     # stored as (w - 1): apply uses 1+w


def rmsnorm(p, x, eps: float):
    """RMSNorm with (1 + w) parametrization (covers both llama & gemma styles:
    llama-style init w=1 is stored as scale=0)."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + p["scale"].astype(jnp.float32))).astype(dt)


def group_rmsnorm(p, x, eps: float, groups: int):
    """RMSNorm of each of ``groups`` equal slices of the last axis on its
    own (Mamba2's gated norm with B/C groups); one group is ``rmsnorm``."""
    if groups == 1:
        return rmsnorm(p, x, eps)
    dt = x.dtype
    xg = x.astype(jnp.float32).reshape(x.shape[:-1] + (groups, -1))
    var = jnp.mean(jnp.square(xg), axis=-1, keepdims=True)
    y = (xg * jax.lax.rsqrt(var + eps)).reshape(x.shape)
    return (y * (1.0 + p["scale"].astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------- embedding
def init_embedding(key, vocab: int, d: int, dtype):
    # 1/sqrt(d): keeps tied-unembedding logits O(1); gemma's sqrt(d) input
    # scaling (below) restores unit-variance embeddings where the arch wants it
    return {"table": truncated_normal_init(key, (vocab, d),
                                           1.0 / math.sqrt(d), dtype)}


def embed(p, tokens, cfg: ModelConfig):
    x = jnp.take(p["table"], tokens, axis=0)
    if cfg.gemma_norm:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def unembed(p, x, cfg: ModelConfig):
    """Project to (padded) vocab logits. ``p`` is the embedding table when tied."""
    return x @ p["table"].T if "table" in p else x @ p["w"]


# --------------------------------------------------------------------------- RoPE
def _rope_angles(positions, inv_freq):
    """positions (..., S) int32 -> angles (..., S, dim/2) f32."""
    return positions.astype(jnp.float32)[..., None] * inv_freq


def rope_cos_sin(cfg: ModelConfig, positions: jnp.ndarray, rot_dim: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables.

    positions: (B, S) for full/partial RoPE; (3, B, S) for M-RoPE (t, h, w
    streams, qwen2-vl style).
    Returns cos, sin of shape (B, S, rot_dim // 2), float32.
    """
    half = rot_dim // 2
    inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if cfg.rope_kind == "mrope":
        assert positions.ndim == 3, "mrope needs (3, B, S) position streams"
        sections = cfg.mrope_sections
        assert sum(sections) == half, (sections, half)
        parts = []
        start = 0
        for stream, sec in enumerate(sections):
            ang = _rope_angles(positions[stream], inv_freq[start:start + sec])
            parts.append(ang)
            start += sec
        ang = jnp.concatenate(parts, axis=-1)            # (B, S, half)
    else:
        ang = _rope_angles(positions, inv_freq)          # (B, S, half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """NeoX-style rotate-half on the leading ``2 * cos.shape[-1]`` channels of x.

    x: (B, S, H, hd); cos/sin: (B, S, half). Channels beyond rot_dim pass through
    (partial RoPE, chatglm/stablelm style).
    """
    half = cos.shape[-1]
    rot_dim = 2 * half
    x_rot, x_pass = x[..., :rot_dim], x[..., rot_dim:]
    x1, x2 = x_rot[..., :half], x_rot[..., half:]
    c = cos[:, :, None, :].astype(jnp.float32)
    s = sin[:, :, None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * c - x2f * s, x2f * c + x1f * s], axis=-1)
    out = out.astype(x.dtype)
    if x_pass.shape[-1]:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out


def rot_dim_for(cfg: ModelConfig, head_dim: int) -> int:
    if cfg.rope_kind == "none":
        return 0
    if cfg.rope_kind == "partial":
        rd = int(cfg.rotary_pct * head_dim)
        return rd - (rd % 2)
    return head_dim


# --------------------------------------------------------------- sinusoidal (musicgen)
def sinusoidal_pos_embed(positions: jnp.ndarray, d_model: int, dtype) -> jnp.ndarray:
    """positions (B, S) -> (B, S, d_model), classic transformer sin/cos."""
    half = d_model // 2
    freq = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# --------------------------------------------------------------------------- MLP
def init_mlp(key, cfg: ModelConfig, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"w_in": init_linear(k1, cfg.d_model, d_ff, dtype),
         "w_out": init_linear(k2, d_ff, cfg.d_model, dtype)}
    if cfg.gated_mlp:
        p["w_gate"] = init_linear(k3, cfg.d_model, d_ff, dtype)
    return p


def act(name: str, x):
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if name == "gelu_exact":
        return jax.nn.gelu(x, approximate=False)
    raise ValueError(name)


def mlp(p, x, cfg: ModelConfig, d_ff_override: Optional[int] = None):
    h = linear(p["w_in"], x)
    if cfg.gated_mlp:
        h = act(cfg.act, linear(p["w_gate"], x)) * h
    else:
        h = act(cfg.act, h)
    return linear(p["w_out"], h)
